"""Public ops: decode_attention / paged_decode_attention — accept
model-layout tensors (q (B, 1, H, hd); dense caches (B, S, KVH, hd) or a
shared (num_blocks, block_size, KVH, hd) pool + (B, max_blocks) block table;
pos () or (B,) per-slot) and dispatch to the Pallas kernels (compiled on
TPU, interpret mode elsewhere — see repro.kernels.runtime).

``pos`` (and the block table dtype) are normalized HERE, before the jit
boundary: the serving loop calls these once per tick with whatever the host
happens to hold (Python ints during warmup, numpy scalars, () or (B,)
device arrays), and every flavor used to be a distinct trace-cache entry on
the jitted kernels. One (B,) int32 aval per tensor shape means ONE trace —
asserted by the single-trace regression in tests/test_kernels.py."""
import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.kernel import (
    decode_attention_pallas,
    paged_decode_attention_pallas,
)
from repro.kernels.runtime import pos_vector


def decode_attention(
    q: jax.Array,  # (B, 1, H, hd)
    k_cache: jax.Array,  # (B, S, KVH, hd)
    v_cache: jax.Array,
    pos: jax.Array,  # () shared or (B,) per-slot decode positions
    *,
    window: int | None = None,
    block_s: int = 256,
    kv_len: jax.Array | None = None,  # (B,) keys visible (0: dead slot)
    scale: float | None = None,  # softmax scale; None: 1/sqrt(hd)
) -> jax.Array:
    b, one, h, hd = q.shape
    kvh = k_cache.shape[2]
    qg = q.reshape(b, kvh, h // kvh, hd)
    out = decode_attention_pallas(
        qg, k_cache, v_cache, pos_vector(pos, b), kv_len,
        block_s=block_s, window=window, scale=scale,
    )
    return out.reshape(b, 1, h, hd)


def paged_decode_attention(
    q: jax.Array,  # (B, 1, H, hd)
    k_pool: jax.Array,  # (num_blocks, block_size, KVH, hd) shared pool
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, max_blocks) physical page ids (0 = null)
    pos: jax.Array,  # () shared or (B,) per-slot decode positions
    *,
    window: int | None = None,
    kv_len: jax.Array | None = None,  # (B,) keys visible (0: dead slot)
    scale: float | None = None,  # softmax scale; None: 1/sqrt(hd)
) -> jax.Array:
    b, one, h, hd = q.shape
    kvh = k_pool.shape[2]
    qg = q.reshape(b, kvh, h // kvh, hd)
    out = paged_decode_attention_pallas(
        qg, k_pool, v_pool, jnp.asarray(block_tables, jnp.int32),
        pos_vector(pos, b), kv_len, window=window, scale=scale,
    )
    return out.reshape(b, 1, h, hd)
