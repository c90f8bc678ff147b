"""Ahead-of-time compiles for a described TPU v5e chip — no chip needed.

The TPU compiler ships with JAX and compiles for a topology that is
described, not attached (``jax.experimental.topologies``). These compiles
catch what interpret mode cannot: block shapes Mosaic's tiling rule
refuses, kernels over the VMEM limit, programs over one chip's HBM.

This is the only test file that describes the chip. Only one process at a
time may load the TPU library, and it keeps it until it exits, so the
description is made inside a module fixture (never at import) and every
test skips from there when it cannot be made.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get
from repro.kernels.decode_attention.kernel import (
    decode_attention_pallas,
    paged_decode_attention_pallas,
)
from repro.kernels.graph_mix.kernel import graph_mix_pallas
from repro.kernels.prefill_attention import kernel as flash_kernel
from repro.kernels.prefill_attention.kernel import (
    paged_prefill_attention_pallas,
    prefill_attention_pallas,
)
from repro.models import TransformerLM
from repro.serve import PagingSpec
from repro.serve.step import make_serve_step

# the serving shape chip_smoke.py drives: 8 slots of 2048 positions, paged
# KV in 16-token blocks, 64-token prefill chunks
SLOTS, MAX_SEQ, BLOCK, CHUNK = 8, 2048, 16, 64
HBM_BYTES = 16 * 2**30  # one v5e chip
STORE_ROW = 393216  # one multitask_lm adapter-store row (12 x 2 x 2 x 1024 x 8)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs outside the checkout
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent cache
    # but not read back without a chip: keep the cache off around these
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _shapes(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), tree
    )


@pytest.mark.parametrize("arch", ["olmo_1b", "multitask_lm"])
@pytest.mark.parametrize(
    "kernel", ["decode", "prefill", "paged_decode", "paged_prefill"]
)
def test_attention_kernel_compiles(one_chip, arch, kernel):
    cfg = get(arch)
    kvh, hd = cfg.num_kv_heads, cfg.head_dim
    g = cfg.num_heads // kvh

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    decode = kernel.endswith("decode")
    q = sds((SLOTS, kvh, g, hd) if decode else (SLOTS, kvh, CHUNK, g, hd))
    pos = sds((SLOTS,), jnp.int32)
    if kernel.startswith("paged"):
        pool = sds((SLOTS * MAX_SEQ // BLOCK + 1, BLOCK, kvh, hd))
        table = sds((SLOTS, MAX_SEQ // BLOCK), jnp.int32)
        fn = paged_decode_attention_pallas if decode else paged_prefill_attention_pallas
        args = (q, pool, pool, table, pos)
    else:
        cache = sds((SLOTS, MAX_SEQ, kvh, hd))
        fn = decode_attention_pallas if decode else prefill_attention_pallas
        args = (q, cache, cache, pos)
    compiled = jax.jit(functools.partial(fn, interpret=False)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_graph_mix_compiles_at_store_width(one_chip, dtype):
    """The adapter store's mixing: 256 tasks + the null row, one row wide."""
    m = 257
    mu = jax.ShapeDtypeStruct((m, m), jnp.float32, sharding=one_chip)
    theta = jax.ShapeDtypeStruct((m, STORE_ROW), dtype, sharding=one_chip)
    mix = jax.jit(functools.partial(graph_mix_pallas, interpret=False))
    assert "tpu_custom_call" in mix.lower(mu, theta).compile().as_text()


@pytest.mark.parametrize("step", ["jnp_decode", "pallas_paged_prefill"])
def test_olmo_serving_step_fits_one_chip(one_chip, step, monkeypatch):
    """The full-width olmo_1b serving step, bf16, compiles for one chip and
    fits its HBM. The Pallas step is the one chip_smoke.py serves from; the
    kernels are told to compile here (this process's backend is the CPU)."""
    backend = step.split("_")[0]
    cfg = dataclasses.replace(get("olmo_1b"), attn_backend=backend)
    model = TransformerLM(cfg, dtype=jnp.bfloat16)
    paging = PagingSpec.sized(BLOCK, MAX_SEQ, SLOTS * MAX_SEQ) if backend == "pallas" else None
    params = _shapes(one_chip, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    caches = _shapes(
        one_chip, jax.eval_shape(lambda: model.init_cache(SLOTS, MAX_SEQ, paging))
    )

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    decode_tick, prefill_chunk = make_serve_step(model, MAX_SEQ, paging)
    if backend == "jnp":
        args = (params, sds((SLOTS,), jnp.int32), sds((SLOTS,), jnp.int32),
                caches, sds((SLOTS,), jnp.int32), sds((SLOTS,), bool))
        lowered = decode_tick.lower(*args)
    else:
        monkeypatch.setattr(flash_kernel, "resolve_interpret", lambda _: False)
        args = (params, sds((SLOTS, CHUNK), jnp.int32), sds((SLOTS,), jnp.int32),
                caches, sds((SLOTS,), jnp.int32), sds((SLOTS, CHUNK), bool),
                sds((SLOTS,), bool), {},
                sds((SLOTS, paging.max_blocks_per_slot), jnp.int32))
        lowered = prefill_chunk.lower(*args)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert peak < HBM_BYTES, f"{peak} bytes do not fit one chip"
    assert ("tpu_custom_call" in compiled.as_text()) == (backend == "pallas")


def test_granite_serving_step_fits_one_chip(one_chip, monkeypatch):
    """The full-width granite-4.0-h-micro Pallas paged step, bf16, as the
    benchmark deploys it (16 slots of 8192 positions, a pool of 131072
    tokens in 16-token blocks, 64-token chunks), compiles for one chip and
    fits its HBM: weights, 36 mamba2 states and 4 layers' KV pools."""
    slots, max_seq, pool = 16, 8192, 131072
    cfg = dataclasses.replace(get("granite_4_h_micro"), attn_backend="pallas")
    model = TransformerLM(cfg, dtype=jnp.bfloat16)
    paging = PagingSpec.sized(BLOCK, max_seq, pool)
    params = _shapes(one_chip, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    caches = _shapes(
        one_chip, jax.eval_shape(lambda: model.init_cache(slots, max_seq, paging))
    )

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    monkeypatch.setattr(flash_kernel, "resolve_interpret", lambda _: False)
    _, prefill_chunk = make_serve_step(model, max_seq, paging)
    args = (params, sds((slots, CHUNK), jnp.int32), sds((slots,), jnp.int32),
            caches, sds((slots,), jnp.int32), sds((slots, CHUNK), bool),
            sds((slots,), bool), {},
            sds((slots, paging.max_blocks_per_slot), jnp.int32))
    compiled = prefill_chunk.lower(*args).compile()
    mem = compiled.memory_analysis()
    peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert peak < HBM_BYTES, f"{peak} bytes do not fit one chip"
    assert "tpu_custom_call" in compiled.as_text()
