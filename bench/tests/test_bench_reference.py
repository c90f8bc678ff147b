"""The plain reference agrees with the program where both compute in
float32: the full forward pass, and every served token of a run with the
graph-mixed adapter store updating between steps."""
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.arch import olmo as olmo_arch
from bench.lib import harness, program, weights
from bench.lib.peaks import PEAKS
from bench.reference import olmo
from bench.tests import tiny
from repro.models import TransformerLM


def test_forward_matches_program_in_float32():
    cfg = tiny.config(adapters=False)
    w = weights.model_weights(cfg, 3, 16, dtype=jnp.float32)
    model = TransformerLM(olmo_arch.arch(cfg), dtype=jnp.float32)
    params = program.checked(model, olmo_arch.params(w))
    tokens = np.random.default_rng(0).integers(0, cfg["vocab_size"], 40)
    want, _ = model.forward(params, {"tokens": jnp.asarray(tokens)[None]})
    got = olmo.forward(w, jnp.asarray(tokens), None, None,
                       heads=cfg["num_attention_heads"],
                       theta=cfg["rope_theta"])
    err = float(jnp.max(jnp.abs(got - want[0])))
    assert err < 1e-4 * float(jnp.max(jnp.abs(want))), err


def test_served_tokens_match_in_float32():
    cfg = tiny.config(adapters=True)
    cfg["deployment"]["dtype"] = "float32"
    cell = {"name": tiny.CELL, "config": "tiny", "traffic": "tiny",
            "chips": 1}
    orig = weights.model_weights
    f32 = lambda c, s, t, dtype=None: orig(c, s, t, dtype=jnp.float32)  # noqa: E731
    weights.model_weights, raw0 = f32, weights.adapter_raw
    weights.adapter_raw = lambda c, s, dtype=None: raw0(c, s, jnp.float32)
    try:
        r = harness.run(tiny.bench(), cell, cfg, tiny.OPEN, 5, 1.5, False,
                        time.perf_counter(), jax.devices(),
                        PEAKS["TPU v5 lite"], {"widest_logit_gap": 1e-3},
                        say=lambda m: None)
    finally:
        weights.model_weights, weights.adapter_raw = orig, raw0
    assert r["correct"], r["checks"]
    assert r["checks"]["widest_logit_gap"]["value"] < 1e-3


def test_mixing_weights_are_powers_of_mu():
    adj = olmo.band_adjacency(8, 2)
    assert adj.sum(axis=1).tolist() == [4.0] * 8 and (adj == adj.T).all()
    mu = olmo.bsr_mixing(adj, 1.0, 1.0, 1.0)
    np.testing.assert_allclose(mu.sum(axis=0), 1.0, atol=1e-12)
    raw = {"head_bias": jnp.asarray(np.eye(8, dtype=np.float32))}
    ad = olmo.adapter_versions(mu, dict(raw, **{
        k: jnp.zeros((8, 1, 2, 2)) for k in ("attn_a", "attn_b", "mlp_a",
                                               "mlp_b")}), 3, [0, 2])
    np.testing.assert_allclose(ad["head_bias"][0], mu[:, 3], atol=1e-6)
    np.testing.assert_allclose(ad["head_bias"][1],
                               np.linalg.matrix_power(mu, 3)[:, 3], atol=1e-6)
