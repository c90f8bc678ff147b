"""Flash-decode Pallas TPU kernel: one query token vs a long KV cache.

Serving's hot spot (decode_32k / long_500k shapes): per token and layer the
whole KV cache (B x S x KVH x hd) streams HBM -> VMEM exactly once while
scores/outputs accumulate on-chip with an online softmax — arithmetic
intensity is O(G) flops/byte, so the roofline is HBM bandwidth and the kernel
objective is "touch every cache byte once".

Decode is the one-token case of the chunked flash kernel
(repro.kernels.prefill_attention.kernel.flash_attention): the query slab is
the G query heads of each KV head, all at position ``pos``, so the chunk
mask ``kv_idx <= pos + row // G`` is exactly the decode mask. Both entry
points below keep their own jit (and trace cache); the kernel body is shared.

``paged_decode_attention_pallas`` is the block-table variant for the paged
serving cache (repro.serve.paging): K/V live in a shared
(num_blocks, block_size, KVH, hd) pool and each slot's pages are chased
through a (B, max_blocks) block table prefetched as a scalar argument, so
the gather never materializes a contiguous per-slot view in HBM. Each
slot's walk stops at its last live block (``kv_len``: ``pos + 1``, or 0 for
a dead slot), as the shared kernel's docstring says.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.prefill_attention.kernel import (
    DEFAULT_BLOCK_S,
    dense_as_pool,
    flash_attention,
)


@functools.partial(
    jax.jit, static_argnames=("block_s", "window", "interpret", "scale")
)
def decode_attention_pallas(
    q: jax.Array,  # (B, KVH, G, hd)
    k: jax.Array,  # (B, S, KVH, hd)
    v: jax.Array,  # (B, S, KVH, hd)
    pos: jax.Array,  # () int32 shared, or (B,) per-slot decode positions
    kv_len: jax.Array | None = None,  # (B,) keys visible (0: dead slot)
    *,
    block_s: int = DEFAULT_BLOCK_S,
    window: int | None = None,
    interpret: bool | None = None,
    scale: float | None = None,
) -> jax.Array:
    k_pool, v_pool, table = dense_as_pool(k, v, block_s)
    out = flash_attention(
        q[:, :, None], k_pool, v_pool, table, pos, window=window,
        interpret=interpret, name="dense_attention_pallas", kv_len=kv_len,
        scale=scale,
    )
    return out[:, :, 0]


@functools.partial(jax.jit, static_argnames=("window", "interpret", "scale"))
def paged_decode_attention_pallas(
    q: jax.Array,  # (B, KVH, G, hd)
    k_pool: jax.Array,  # (num_blocks, block_size, KVH, hd) shared pool
    v_pool: jax.Array,  # (num_blocks, block_size, KVH, hd)
    block_tables: jax.Array,  # (B, max_blocks) physical page ids (0 = null)
    pos: jax.Array,  # (B,) or () per-slot decode positions
    kv_len: jax.Array | None = None,  # (B,) keys visible (0: dead slot)
    *,
    window: int | None = None,
    interpret: bool | None = None,
    scale: float | None = None,
) -> jax.Array:
    """Flash-decode over the paged KV pool: the innermost grid axis walks
    each slot's block table and the scalar-prefetched table turns logical
    step ``ii`` into the physical page DMA'd for that step — O(1) extra HBM
    traffic vs dense."""
    out = flash_attention(
        q[:, :, None], k_pool, v_pool, block_tables, pos, window=window,
        interpret=interpret, name="paged_attention_pallas", kv_len=kv_len,
        scale=scale,
    )
    return out[:, :, 0]
