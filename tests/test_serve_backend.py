"""Attention-backend regression: ``attn_backend="pallas"`` must serve
token-for-token identically to the jnp default, across the whole fallback
matrix (repro.kernels.runtime.resolve_attn_backend):

  * GQA (qwen: QKV bias; olmo: MHA) — flash decode + chunked flash prefill,
    dense AND block-table paged (block_size 8/16),
  * sliding-window GQA + MoE (mixtral) — the windowed kernel masks,
  * MLA (deepseek_v2_236b) — silent fallback to the jnp absorbed-matrix
    decode (no materialized K/V heads to flash),
  * recurrent / hybrid (zamba2_7b: mamba + shared GQA; xlstm_350m: no
    attention anywhere) — recurrent state updates are untouched, the hybrid
    still serves its attention layers from the kernels.

All runs go through ``ContinuousBatcher`` with staggered prompt lengths so
the kernels see ragged per-slot positions, exactly as in production ticks.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get
from repro.kernels.runtime import resolve_attn_backend
from repro.models import TransformerLM
from repro.serve import ContinuousBatcher, PagingSpec, Request


def _greedy_outputs(cfg, params, backend, paging=None, max_seq=24):
    """Run a fixed staggered workload, return {uid: tokens}."""
    model = TransformerLM(dataclasses.replace(cfg, attn_backend=backend))
    batcher = ContinuousBatcher(
        model, params, num_slots=2, max_seq=max_seq, prefill_chunk=3,
        paging=paging,
    )
    rng = np.random.default_rng(0)
    # 3 requests over 2 slots: forces a second admission round (slot reuse,
    # reset path) with ragged prompt lengths
    for i, (n, mn) in enumerate(((5, 6), (8, 4), (3, 5))):
        batcher.submit(Request(
            uid=i, tokens=rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32),
            max_new=mn, task_id=i % cfg.num_tasks,
        ))
    done = batcher.run()
    assert len(done) == 3
    return {r.uid: r.out for r in done}


def _smoke(arch):
    cfg = get(arch, smoke=True)
    if cfg.uses_moe:
        # dropless capacity: parity must not hinge on capacity-overflow
        # drops (same convention as the other serving parity tests)
        cfg = dataclasses.replace(cfg, capacity_factor=float(cfg.num_experts))
    params = TransformerLM(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


# ----------------------------------------------------- GQA: kernels active
@pytest.mark.parametrize(
    "arch", ["qwen2_5_14b", "mixtral_8x22b", "granite_4_h_micro"]
)
def test_pallas_backend_dense_parity(arch):
    """Flash decode + flash prefill == jnp masked einsum, token-for-token
    (qwen: GQA with QKV bias; mixtral: sliding-window GQA + MoE; granite:
    NoPE GQA at softmax scale 1/64 beside mamba2 mixers)."""
    cfg, params = _smoke(arch)
    assert _greedy_outputs(cfg, params, "pallas") == _greedy_outputs(
        cfg, params, "jnp"
    )


@pytest.mark.parametrize("block_size", [8, 16])
def test_pallas_backend_paged_parity(block_size):
    """Paged flash kernels (block-table grid walk) == jnp gather_pages path
    at serving block sizes — and == the dense jnp run."""
    cfg, params = _smoke("qwen2_5_14b")
    spec = PagingSpec.sized(block_size, 24, pool_tokens=2 * 24)
    paged_pallas = _greedy_outputs(cfg, params, "pallas", paging=spec)
    paged_jnp = _greedy_outputs(cfg, params, "jnp", paging=spec)
    dense_jnp = _greedy_outputs(cfg, params, "jnp")
    assert paged_pallas == paged_jnp == dense_jnp


def test_pallas_backend_paged_parity_hybrid():
    """The hybrid on the paged kernels: granite's NoPE GQA at scale 1/64
    walks the block table beside dense mamba2 states, token-for-token with
    the jnp gather path and the dense jnp run."""
    cfg, params = _smoke("granite_4_h_micro")
    spec = PagingSpec.sized(8, 24, pool_tokens=2 * 24)
    paged_pallas = _greedy_outputs(cfg, params, "pallas", paging=spec)
    assert paged_pallas == _greedy_outputs(cfg, params, "jnp", paging=spec)
    assert paged_pallas == _greedy_outputs(cfg, params, "jnp")


def test_pallas_backend_paged_parity_sliding_window():
    cfg, params = _smoke("mixtral_8x22b")
    spec = PagingSpec.sized(8, 24, pool_tokens=2 * 24)
    assert _greedy_outputs(cfg, params, "pallas", paging=spec) == (
        _greedy_outputs(cfg, params, "jnp", paging=spec)
    )


# ------------------------------------------------- fallback: kernels inert
@pytest.mark.parametrize("arch", ["deepseek_v2_236b", "zamba2_7b", "xlstm_350m"])
def test_pallas_backend_fallback_parity(arch):
    """Configs with unsupported layers run under attn_backend="pallas"
    WITHOUT error and match the pure-jnp run token-for-token: MLA falls
    back silently, recurrent blocks have no attention to dispatch, and the
    hybrid's shared GQA block still uses the kernels."""
    cfg, params = _smoke(arch)
    assert _greedy_outputs(cfg, params, "pallas") == _greedy_outputs(
        cfg, params, "jnp"
    )


def test_resolve_attn_backend_matrix():
    assert resolve_attn_backend("jnp") == "jnp"
    assert resolve_attn_backend("pallas") == "pallas"
    assert resolve_attn_backend("pallas", mla=True) == "jnp"  # silent fallback
    assert resolve_attn_backend("jnp", mla=True) == "jnp"
    with pytest.raises(ValueError):
        resolve_attn_backend("triton")


def test_attn_backend_config_validation():
    cfg = get("qwen2_5_14b", smoke=True)
    with pytest.raises(AssertionError):
        dataclasses.replace(cfg, attn_backend="cuda").validate()
