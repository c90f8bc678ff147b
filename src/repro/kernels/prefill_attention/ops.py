"""Public ops: prefill_attention / paged_prefill_attention — accept
model-layout tensors (q (B, C, H, hd); dense caches (B, S, KVH, hd) or a
shared (num_blocks, block_size, KVH, hd) pool + (B, max_blocks) block table;
pos () or (B,) per-slot first-token positions) and dispatch to the Pallas
kernels (compiled on TPU, interpret mode elsewhere — see
repro.kernels.runtime).

``pos`` is normalized to a (B,) int32 array HERE, before the jit boundary
(``repro.kernels.runtime.pos_vector``): a caller alternating Python ints,
numpy scalars and () arrays must hit ONE trace-cache entry per tensor
shape, not one per pos flavor (the decode ops follow the same rule —
asserted by the single-trace regression in tests/test_kernels.py)."""
import jax
import jax.numpy as jnp

from repro.kernels.runtime import pos_vector


def prefill_attention(
    q: jax.Array,  # (B, C, H, hd) query chunk
    k_cache: jax.Array,  # (B, S, KVH, hd)
    v_cache: jax.Array,
    pos: jax.Array,  # () shared or (B,) per-slot first-token positions
    *,
    window: int | None = None,
    block_s: int = 256,
    kv_len: jax.Array | None = None,  # (B,) keys visible to valid queries
    scale: float | None = None,  # softmax scale; None: 1/sqrt(hd)
) -> jax.Array:
    from repro.kernels.prefill_attention.kernel import prefill_attention_pallas

    b, cq, h, hd = q.shape
    kvh = k_cache.shape[2]
    # (B, C, H, hd) -> (B, KVH, C, G, hd): group queries per KV head so the
    # whole slab shares one streaming pass over its head's cache
    qg = q.reshape(b, cq, kvh, h // kvh, hd).transpose(0, 2, 1, 3, 4)
    out = prefill_attention_pallas(
        qg, k_cache, v_cache, pos_vector(pos, b), kv_len,
        block_s=block_s, window=window, scale=scale,
    )
    return out.transpose(0, 2, 1, 3, 4).reshape(b, cq, h, hd)


def paged_prefill_attention(
    q: jax.Array,  # (B, C, H, hd) query chunk
    k_pool: jax.Array,  # (num_blocks, block_size, KVH, hd) shared pool
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, max_blocks) physical page ids (0 = null)
    pos: jax.Array,  # () shared or (B,) per-slot first-token positions
    *,
    window: int | None = None,
    kv_len: jax.Array | None = None,  # (B,) keys visible to valid queries
    scale: float | None = None,  # softmax scale; None: 1/sqrt(hd)
) -> jax.Array:
    from repro.kernels.prefill_attention.kernel import (
        paged_prefill_attention_pallas,
    )

    b, cq, h, hd = q.shape
    kvh = k_pool.shape[2]
    qg = q.reshape(b, cq, kvh, h // kvh, hd).transpose(0, 2, 1, 3, 4)
    out = paged_prefill_attention_pallas(
        qg, k_pool, v_pool, jnp.asarray(block_tables, jnp.int32),
        pos_vector(pos, b), kv_len, window=window, scale=scale,
    )
    return out.transpose(0, 2, 1, 3, 4).reshape(b, cq, h, hd)
