"""Granite 4.0-H (``granitemoehybrid``) in the benchmark: the plain reference
agrees with the program where both compute in float32, over one whole
period of its layers (nine Mamba-2 mixers and one NoPE GQA mixer, each with
its MLP), in the full forward pass and in every served token of a run whose
prompts span several prefill chunks; the cost counts follow the published
widths."""
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.arch import granitemoehybrid as granite_arch
from bench.lib import costs, harness, program, spec, trace, weights
from bench.lib.peaks import PEAKS
from bench.reference import granite
from bench.tests import tiny
from repro.models import TransformerLM

CELL = "granite_4_h_micro.long_context"


def _published() -> dict:
    return json.loads((spec.BENCH / "configs" / "granite_4_h_micro.json")
                      .read_text())


def config(backend: str = "pallas") -> dict:
    """One whole period of granite-4.0-h-micro's layers at tiny widths."""
    cfg = _published()
    cfg.update(vocab_size=256, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=2, intermediate_size=128,
               shared_intermediate_size=128, mamba_n_heads=8,
               mamba_d_head=16, mamba_d_state=16, mamba_chunk_size=16,
               num_hidden_layers=10, layer_types=cfg["layer_types"][:10])
    cfg["deployment"].update(attn_backend=backend, dtype="float32",
                             num_slots=3, max_seq=256, pool_tokens=768,
                             prefill_chunk=64, chunk_budget=128)
    return cfg


def test_forward_matches_program_in_float32():
    cfg = config()
    w = weights.model_weights(cfg, 2**33 + 1, program.num_tasks(cfg),
                              dtype=jnp.float32)
    model = TransformerLM(granite_arch.arch(cfg), dtype=jnp.float32)
    params = program.checked(model, granite_arch.params(w))
    tokens = np.random.default_rng(0).integers(0, cfg["vocab_size"], 48)
    want, _ = model.forward(params, {"tokens": jnp.asarray(tokens)[None]})
    with jax.default_matmul_precision("highest"):
        got = granite.forward(w, jnp.asarray(tokens), cfg=cfg)
    err = float(jnp.max(jnp.abs(got - want[0])))
    assert err < 1e-4 * float(jnp.max(jnp.abs(want))), err


def test_served_tokens_match_in_float32(monkeypatch):
    """Prompts of 130-220 tokens in 64-token chunks beside decoding rows:
    the SSM state crosses chunks, decode continues from prefill, and every
    served token is the float32 reference's best within 1e-3."""
    cfg = config()
    mix = {"loop": "closed", "clients": 3,
           "prompt": {"dist": "uniform", "lo": 130, "hi": 220},
           "output": {"dist": "uniform", "lo": 4, "hi": 10}, "prime": True}
    orig = weights.model_weights
    monkeypatch.setattr(weights, "model_weights",
                        lambda c, s, t, dtype=None: orig(c, s, t, jnp.float32))
    cell = {"name": tiny.CELL, "config": "tiny", "traffic": "tiny",
            "chips": 1}
    r = harness.run(tiny.bench(), cell, cfg, mix, 2**32 + 11, 2.0, False,
                    time.perf_counter(), jax.devices(), PEAKS["TPU v5 lite"],
                    {"widest_logit_gap": 1e-3}, say=lambda m: None)
    assert r["correct"], r["checks"]
    assert r["checks"]["widest_logit_gap"]["value"] < 1e-3


def test_weights_fill_the_programs_tree_at_published_widths():
    """The published configuration's weights, laid out for the program,
    have exactly the shapes and dtypes of its parameter tree (checked on
    shapes alone: 3.19 B parameters)."""
    cfg = _published()
    model = TransformerLM(granite_arch.arch(cfg), dtype=jnp.bfloat16)
    tasks = program.num_tasks(cfg)
    w = jax.eval_shape(lambda k: granite_arch.weights(cfg, k, tasks,
                                                      jnp.bfloat16),
                       jax.random.PRNGKey(0))
    got = jax.eval_shape(granite_arch.params, w)
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    n = sum(int(np.prod(t.shape)) for t in jax.tree.leaves(w)) - tasks * 100352
    assert 3.18e9 < n < 3.20e9, n


def test_cost_counts_at_published_widths():
    """36 mixers of 64 heads x 64 x d_state 128 beside 4 GQA layers of
    32/8 heads x 64 (hidden_size / num_attention_heads)."""
    cfg = _published()
    s = granite_arch.shape(cfg)
    assert (s.attn_layers, s.heads, s.kv_heads, s.head_dim) == (4, 32, 8, 64)
    mamba = 2048 * 8512 + 4096 * 2048
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512
    assert s.body_params == 36 * mamba + 4 * attn + 40 * 3 * 2048 * 8192
    assert s.body_params == 2_984_771_584
    # one slot's state: the float32 SSM state and the conv's last 3 inputs
    assert granite_arch.mixer_state_bytes(cfg) == 36 * (
        64 * 64 * 128 * 4 + 3 * 4352 * 2)
    per_token = 2 * mamba + 2 * 4 * 4352 + 5 * 64 * 64 * 128
    assert granite_arch.mixer_flops(cfg, 3) == 3 * 36 * per_token
    assert granite_arch.recurrence_flops(cfg, 1) == 36 * (
        per_token - 2 * mamba)


@pytest.mark.parametrize("key,value", [("num_local_experts", 72),
                                       ("position_embedding_type", "rope"),
                                       ("mamba_n_groups", 2)])
def test_unsupported_settings_are_refused(key, value):
    cfg = dict(_published(), **{key: value})
    with pytest.raises(ValueError, match=key):
        granite_arch.arch(cfg)


def test_cell_has_no_adapters():
    with pytest.raises(NotImplementedError, match="adapters"):
        granite_arch.adapter_raw(_published(), jax.random.PRNGKey(0),
                                 jnp.bfloat16)
    bench = spec.load()
    cfg = spec.config(bench, spec.cell(bench, CELL)["config"])
    assert cfg["adapters"] is None and cfg["reference"] == "granite"


def _window(cfg, scope):
    """A recorded 200 ms window of two step executions: 40 ms of device
    time under ``scope``, 10 ms under the MLP's scope."""
    path = "jit(prefill_chunk_parallel)/while/body/{}/dot_general"
    ops = [trace.Event("fusion.1", 0, 40_000_000, path.format(scope)),
           trace.Event("fusion.2", 50_000_000, 10_000_000,
                       path.format("mlp"))]
    modules = [trace.Event("prefill_chunk_parallel.3", t, 100_000_000)
               for t in (0, 100_000_000)]
    ticks = [harness.Tick(1, 16, 0, [(100, 64), (5000, 1)], 2, 0),
             harness.Tick(1, 16, 0, [(5001, 1)], 1, 0)]
    return harness.Context(
        cfg=cfg, mix={}, peak=PEAKS["TPU v5 lite"],
        shape=costs.Shape.of(cfg), num_slots=16, chunk=64, pool_blocks=0,
        t_open=0.0, t_close=0.2, ticks=ticks, obs={},
        trace=trace.Trace((0, 200_000_000), ops, modules, [], 1))


def test_mixer_readers_on_a_recorded_window():
    """``mamba_ms`` is the scope's time per step execution; the roofline
    takes each step's least time (65 real tokens and two live slots, then
    one and one) over that time; ``hybrid_mfu`` adds the recurrence to the
    useful operations. An OLMo window has neither mixers nor recurrence."""
    cfg = _published()
    ctx = _window(cfg, "mamba2")
    assert spec.reader("mamba_ms")(ctx) == pytest.approx(20.0)
    a, peak = granite_arch, ctx.peak
    least = sum(costs.least_time(
        a.mixer_flops(cfg, n),
        a.mixer_weight_bytes(cfg) + 2 * live * a.mixer_state_bytes(cfg), peak)
        for n, live in ((65, 2), (1, 1)))
    got = spec.reader("mamba_roofline")(ctx)
    assert got == pytest.approx(100 * least / 0.04) and 0 < got <= 100
    flops = (costs.useful_flops(ctx.ticks, ctx.shape)
             + a.recurrence_flops(cfg, 66))
    assert spec.reader("hybrid_mfu")(ctx) == pytest.approx(
        100 * flops / (0.2 * peak["bf16_flops"]))
    olmo = _window(tiny.config(adapters=False), "attention")
    for name in ("mamba_ms", "mamba_roofline", "hybrid_mfu"):
        assert spec.reader(name)(olmo) is None
