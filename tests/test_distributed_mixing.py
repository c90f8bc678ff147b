"""Multi-device tests for the shard_map mixing collectives and the sharded
train step. These need >1 device, so each test body runs in a subprocess
with XLA_FLAGS=--xla_force_host_platform_device_count set (the main pytest
process must keep the default single device for all other tests)."""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


def run_sub(code: str, devices: int = 8):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_mix_all_gather_matches_dense_oracle():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType, PartitionSpec as P
        from repro.core import band_graph
        from repro.core.distributed import mix_all_gather

        m, d = 8, 64
        g = band_graph(m, 2)
        mu = jnp.asarray(g.bol_mixing(0.5, 2.0, 0.05), jnp.float32)
        mesh = jax.make_mesh((m,), ("task",), axis_types=(AxisType.Auto,))
        rng = np.random.default_rng(0)
        theta = jnp.asarray(rng.standard_normal((m, d)), jnp.float32)

        def local_fn(th, mu_col):
            return mix_all_gather(th, mu_col[:, 0], "task")

        fn = jax.shard_map(local_fn, mesh=mesh,
                       in_specs=(P("task", None), P(None, "task")),
                       out_specs=P("task", None))
        got = fn(theta, mu)
        want = mu.T @ theta
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        print("OK")
    """)


def test_mix_ring_matches_band_mixing():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType, PartitionSpec as P
        from repro.core import band_graph
        from repro.core.distributed import mix_ring, mixing_spec_for_band_graph

        m, d = 8, 32
        g = band_graph(m, 2)
        eta, tau, alpha = 0.5, 2.0, 0.04
        spec = mixing_spec_for_band_graph(g, eta, tau, alpha)
        assert spec is not None
        self_w, nbr = spec
        mesh = jax.make_mesh((m,), ("task",), axis_types=(AxisType.Auto,))
        rng = np.random.default_rng(1)
        theta = jnp.asarray(rng.standard_normal((m, d)), jnp.float32)

        fn = jax.shard_map(
            lambda th: mix_ring(th, self_w, nbr, "task", m),
            mesh=mesh, in_specs=P("task", None), out_specs=P("task", None))
        got = fn(theta)
        mu = jnp.asarray(g.bol_mixing(eta, tau, alpha), jnp.float32)
        want = mu.T @ theta  # symmetric mu
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        print("OK")
    """)


def test_sharded_train_step_matches_single_device():
    """The pjit'd multi-task train step on a 2x2 mesh must produce the same
    loss as the unsharded step (sharding is an implementation detail)."""
    run_sub("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
        from repro.configs import get
        from repro.core import GraphMultiTask, band_graph
        from repro.models import TransformerLM
        from repro.optim import sgd
        from repro.sharding.rules import MeshAxes, batch_specs, param_specs, train_state_specs
        from repro.train.trainer import init_state, make_train_step

        cfg = dataclasses.replace(get("olmo_1b", smoke=True), num_tasks=2)
        model = TransformerLM(cfg)
        opt = sgd(1e-2)
        gmt = GraphMultiTask(band_graph(cfg.num_tasks, 1), eta=0.1, tau=1.0)
        step = make_train_step(model, opt, multitask=gmt)
        state = init_state(model, opt, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        batch = {
            "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32), dtype=np.int64), jnp.int32),
            "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32), dtype=np.int64), jnp.int32),
            "task_ids": jnp.asarray([0, 0, 1, 1], jnp.int32),
        }
        _, m_single = jax.jit(step)(state, batch)

        mesh = jax.make_mesh((2, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        ax = MeshAxes(("data",), "model", 2, 2)
        sspec = train_state_specs(cfg, state, ax)
        bspec = batch_specs(cfg, batch, ax)
        sh = lambda tree, specs: jax.tree.map(lambda s: NamedSharding(mesh, s), specs)
        with mesh:
            _, m_shard = jax.jit(step, in_shardings=(sh(state, sspec), sh(batch, bspec)))(state, batch)
        np.testing.assert_allclose(float(m_single["loss"]), float(m_shard["loss"]),
                                   rtol=2e-3, atol=2e-3)
        print("OK", float(m_single["loss"]), float(m_shard["loss"]))
    """, devices=4)


def test_dryrun_single_combo_compiles(tmp_path):
    """End-to-end dry-run smoke (production 16x16 mesh on 512 host devices)."""
    run_sub(f"""
        import repro.launch.dryrun as dr
        r = dr.run_one("olmo_1b", "decode_32k", multi_pod=False, probes=False,
                       out_dir={str(tmp_path)!r})
        assert r["scanned"]["memory"]["temp_bytes"] > 0
        assert r["scanned"]["collectives"]["total_wire_bytes"] > 0
        print("OK")
    """, devices=512)
