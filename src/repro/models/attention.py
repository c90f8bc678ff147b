"""Attention: GQA (± QKV bias, ± sliding window) and DeepSeek-style MLA.

Full-sequence (train / prefill) attention is query-chunked (lax.scan over
query blocks) so peak score memory is (block x kv_len) instead of
(seq x seq) — the pure-JAX analogue of flash attention. The SERVING cache
paths (decode tick + parallel prefill chunk) dispatch through
``cached_attend`` on ``ArchConfig.attn_backend``: "jnp" runs the masked
einsum ``decode_attend`` below (the reference semantics), "pallas" runs the
flash kernels in repro/kernels/decode_attention (one query token) and
repro/kernels/prefill_attention (a (B, C) chunk slab), each oracle-checked
against the jnp math.

Shapes: x (B, S, d); q (B, S, H, hd); kv (B, S, KVH, hd); caches are
(B, max_seq, KVH, hd) ring-less buffers written at ``pos``.

Paged serving (repro.serve.paging) replaces the per-slot stripe with a
shared (num_blocks, block_size, ...) pool + per-slot block tables;
``paged_cache_write`` / ``gather_pages`` below are the only two primitives —
the gathered (B, max_blocks * block_size, ...) view feeds the SAME masked
``decode_attend`` / ``mla_decode`` math as the dense path (positions beyond
``pos`` are masked, so unmapped/stale pages are unreachable), which is what
makes dense-vs-paged token parity hold by construction.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope, dense_init, matmul

Array = jax.Array
NEG_INF = -1e30


# ----------------------------------------------------------- GQA parameters
def init_gqa(key, d: int, n_heads: int, n_kv: int, head_dim: int, qkv_bias: bool, dtype):
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, n_heads * head_dim), dtype=dtype),
        "wk": dense_init(ks[1], (d, n_kv * head_dim), dtype=dtype),
        "wv": dense_init(ks[2], (d, n_kv * head_dim), dtype=dtype),
        "wo": dense_init(ks[3], (n_heads * head_dim, d), dtype=dtype),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads * head_dim,), dtype)
        p["bk"] = jnp.zeros((n_kv * head_dim,), dtype)
        p["bv"] = jnp.zeros((n_kv * head_dim,), dtype)
    return p


def gqa_project(params, x, n_heads, n_kv, head_dim):
    b, s, _ = x.shape
    q = matmul(x, params["wq"])
    k = matmul(x, params["wk"])
    v = matmul(x, params["wv"])
    if "bq" in params:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    return (
        q.reshape(b, s, n_heads, head_dim),
        k.reshape(b, s, n_kv, head_dim),
        v.reshape(b, s, n_kv, head_dim),
    )


# ------------------------------------------------------- full-seq attention
def _expand_kv(k: Array, n_heads: int) -> Array:
    """(B, S, KVH, hd) -> (B, S, H, hd) by repeating groups."""
    b, s, kvh, hd = k.shape
    rep = n_heads // kvh
    if rep == 1:
        return k
    return jnp.repeat(k, rep, axis=2)


def causal_attend(
    q: Array,
    k: Array,
    v: Array,
    *,
    sliding_window: int | None = None,
    q_chunk: int = 1024,
    scale: float | None = None,
) -> Array:
    """Query-chunked causal (optionally windowed) attention.

    q: (B, S, H, hd); k, v: (B, S, KVH, hd); scale: the softmax scale
    (None: 1/sqrt(hd)). Returns (B, S, H, hd).
    """
    b, s, h, hd = q.shape
    hd_v = v.shape[-1]  # may differ from hd (MLA: qk dims != v dims)
    kvh = k.shape[2]
    g = h // kvh  # GQA group size — kept as an explicit einsum dim so the
    # partitioner never reshards the KV tensor to expanded heads
    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))

    q_chunk = min(q_chunk, s)
    if s % q_chunk != 0:  # fall back to one chunk when not divisible
        q_chunk = s
    n_chunks = s // q_chunk
    # (B, n_chunks, qc, KVH, G, hd)
    qg = q.reshape(b, n_chunks, q_chunk, kvh, g, hd)
    kv_pos = jnp.arange(s)

    def one_chunk(carry, ci):
        qi = qg[:, ci]  # (B, qc, KVH, G, hd)
        scores = jnp.einsum(
            "bqkgd,bskd->bkgqs", qi, k, preferred_element_type=jnp.float32
        ) * scale  # (B, KVH, G, qc, S)
        q_pos = ci * q_chunk + jnp.arange(q_chunk)
        mask = kv_pos[None, :] <= q_pos[:, None]
        if sliding_window is not None:
            mask &= kv_pos[None, :] > q_pos[:, None] - sliding_window
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
        w = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum(
            "bkgqs,bskd->bqkgd", w.astype(q.dtype), v,
            preferred_element_type=jnp.float32,
        )
        return carry, out.astype(q.dtype)  # (B, qc, KVH, G, hd_v)

    _, outs = jax.lax.scan(one_chunk, 0, jnp.arange(n_chunks))
    # (n_chunks, B, qc, KVH, G, hd_v) -> (B, S, H, hd_v)
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, s, h, hd_v)
    return out


def decode_attend(
    q: Array,
    k_cache: Array,
    v_cache: Array,
    pos: Array,
    *,
    sliding_window: int | None = None,
    scale: float | None = None,
) -> Array:
    """Chunk-of-queries attention against a cache.

    q: (B, C, H, hd) — C == 1 is the decode tick, C > 1 the parallel prefill
    chunk; caches: (B, max_seq, KVH, hd); pos: () shared index or (B,)
    per-slot indices of the FIRST query token (query i sits at ``pos + i``;
    the cache already contains the whole chunk, written before this call).
    Causality within the chunk falls out of the same kv-position mask that
    hides unwritten cache rows: query i reads ``kv_idx <= pos + i`` only.
    scale: the softmax scale (None: 1/sqrt(hd)). Returns (B, C, H, hd_v).
    """
    b, c, h, hd = q.shape
    kvh = k_cache.shape[2]
    g = h // kvh
    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    qg = q.reshape(b, c, kvh, g, hd)
    # NOTE: operand-dtype dots on purpose — requesting an f32 dot against the
    # bf16 cache makes XLA-CPU hoist a full f32 convert of the scanned cache
    # stack out of the layer loop (2x cache memory); the TPU MXU takes bf16
    # operands natively with f32 accumulation. Softmax itself runs in f32.
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k_cache).astype(jnp.float32)
    scores = scores * scale  # (B, KVH, G, C, S)
    kv_pos = jnp.arange(k_cache.shape[1])
    pos_b = jnp.broadcast_to(jnp.asarray(pos), (b,))
    q_pos = pos_b[:, None] + jnp.arange(c)[None, :]  # (B, C)
    mask = kv_pos[None, None, :] <= q_pos[:, :, None]  # (B, C, S)
    if sliding_window is not None:
        mask &= kv_pos[None, None, :] > q_pos[:, :, None] - sliding_window
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", w.astype(q.dtype), v_cache)
    return out.astype(q.dtype).reshape(b, c, h, v_cache.shape[-1])


# ------------------------------------------------- backend dispatch (GQA)
def cached_attend(
    q: Array,
    k_cache: Array,
    v_cache: Array,
    pos: Array,
    *,
    sliding_window: int | None = None,
    backend: str = "jnp",
    block_tables: Array | None = None,
    kv_len: Array | None = None,
    scale: float | None = None,
) -> Array:
    """GQA chunk-of-queries attention against the cache, dispatching on the
    serving attention backend (``ArchConfig.attn_backend``, already resolved
    through ``repro.kernels.runtime.resolve_attn_backend`` — MLA never
    reaches this function).

    q: (B, C, H, hd) — C == 1 is the decode tick, C > 1 the parallel
    prefill chunk. Dense caches are (B, S, KVH, hd); with ``block_tables``
    the caches are the shared (num_blocks, block_size, KVH, hd) pools.

      * "jnp"    — masked-softmax ``decode_attend`` over the dense cache or
        the ``gather_pages`` view of the pool (the reference semantics every
        other path is pinned against).
      * "pallas" — flash kernels: ``decode_attention`` / ``prefill_attention``
        stream the dense cache, ``paged_*`` walk the block table directly in
        the kernel grid (the gather is never materialized in HBM). Compiled
        on TPU, interpret mode elsewhere (repro.kernels.runtime), identical
        ``kv_idx <= pos + i`` masking — token parity with "jnp" is pinned by
        tests/test_serve_backend.py and benchmarks/serve_throughput.py.
        ``kv_len`` (B,), the keys each slot's valid queries may see (0 for
        a slot with none), bounds each slot's walk over its blocks; the
        jnp path ignores it.

    ``scale`` is the softmax scale on both backends (None: 1/sqrt(hd)).
    """
    if backend == "pallas":
        from repro.kernels.decode_attention.ops import (
            decode_attention,
            paged_decode_attention,
        )
        from repro.kernels.prefill_attention.ops import (
            paged_prefill_attention,
            prefill_attention,
        )

        decode = q.shape[1] == 1  # static under jit: C is a trace constant
        if block_tables is None:
            op = decode_attention if decode else prefill_attention
            return op(q, k_cache, v_cache, pos, window=sliding_window,
                      kv_len=kv_len, scale=scale)
        op = paged_decode_attention if decode else paged_prefill_attention
        return op(q, k_cache, v_cache, block_tables, pos,
                  window=sliding_window, kv_len=kv_len, scale=scale)
    if block_tables is not None:
        k_cache = gather_pages(k_cache, block_tables)
        v_cache = gather_pages(v_cache, block_tables)
    return decode_attend(
        q, k_cache, v_cache, pos, sliding_window=sliding_window, scale=scale
    )


# --------------------------------------------------------- paged KV cache
def paged_cache_write(
    pool: Array,
    new: Array,
    pos: Array,
    block_tables: Array,
    live: Array | None = None,
) -> Array:
    """Scatter one token per slot into the shared block pool.

    pool: (num_blocks, block_size, ...); new: (B, 1, ...); pos: (B,) logical
    positions; block_tables: (B, max_blocks) physical block ids. Dead slots
    (``live == False``) are routed to the reserved null block 0, so the
    write is unconditional — the allocator guarantees no live slot ever maps
    block 0. Live slots own disjoint blocks, so the scatter has no
    cross-slot collisions.
    """
    bs = pool.shape[1]
    # mode="clip": a dead slot's stale pos can point past its table width;
    # the clamped garbage id is immediately rerouted to the null block by
    # the live mask below, whereas the NaN-fill default would turn it into
    # an arbitrary int poisoning the scatter row (R001)
    bidx = jnp.take_along_axis(
        block_tables, (pos // bs)[:, None], axis=1, mode="clip"
    )[:, 0]
    if live is not None:
        bidx = jnp.where(live, bidx, 0)
    return pool.at[bidx, pos % bs].set(new[:, 0].astype(pool.dtype))


def paged_cache_write_slab(
    pool: Array,
    new: Array,
    pos: Array,
    block_tables: Array,
    valid: Array,
) -> Array:
    """Scatter a whole (B, C) prefill chunk into the shared block pool.

    pool: (num_blocks, block_size, ...); new: (B, C, ...); pos: (B,) logical
    position of each slot's FIRST chunk token (token i lands at ``pos + i``);
    valid: (B, C) — invalid lanes (prompt shorter than the chunk, slots not
    being prefilled) are routed to the reserved null block 0, exactly like
    dead slots in ``paged_cache_write``. Live slots own disjoint blocks and
    chunk tokens occupy distinct in-block offsets, so the scatter has no
    cross-slot collisions; null-block collisions are unobservable.
    """
    bs = pool.shape[1]
    c = new.shape[1]
    tgt = pos[:, None] + jnp.arange(c)[None, :]  # (B, C) logical positions
    blk = jnp.clip(tgt // bs, 0, block_tables.shape[1] - 1)
    # blk is explicitly clipped to the table width on the line above
    bidx = jnp.take_along_axis(
        block_tables, blk, axis=1, mode="promise_in_bounds"
    )  # (B, C)
    bidx = jnp.where(valid, bidx, 0)
    return pool.at[bidx, tgt % bs].set(new.astype(pool.dtype))


def gather_pages(pool: Array, block_tables: Array) -> Array:
    """Materialize each slot's logical KV view from the shared pool.

    pool: (num_blocks, block_size, ...); block_tables: (B, max_blocks).
    Returns (B, max_blocks * block_size, ...) — logical position p of slot b
    is row p of the view, so downstream masking by ``pos`` is unchanged from
    the dense layout. Unmapped table entries (0) surface null-block garbage
    only at positions > pos, which the mask removes.
    """
    g = pool[block_tables]  # (B, MB, bs, ...)
    return g.reshape((g.shape[0], g.shape[1] * g.shape[2]) + g.shape[3:])


# ----------------------------------------------------------------- MLA
@dataclasses.dataclass(frozen=True)
class MLADims:
    n_heads: int
    qk_nope: int  # per-head non-rotary key/query dims
    qk_rope: int  # shared rotary dims
    v_dim: int
    kv_lora: int


def init_mla(key, d: int, dims: MLADims, dtype):
    ks = jax.random.split(key, 6)
    h, dn, dr, dv, r = dims.n_heads, dims.qk_nope, dims.qk_rope, dims.v_dim, dims.kv_lora
    return {
        "wq": dense_init(ks[0], (d, h * (dn + dr)), dtype=dtype),
        "w_dkv": dense_init(ks[1], (d, r), dtype=dtype),  # compress
        "w_krope": dense_init(ks[2], (d, dr), dtype=dtype),  # shared rope key
        "w_uk": dense_init(ks[3], (r, h * dn), dtype=dtype),  # up: keys
        "w_uv": dense_init(ks[4], (r, h * dv), dtype=dtype),  # up: values
        "wo": dense_init(ks[5], (h * dv, d), dtype=dtype),
    }


def mla_full(params, x, dims: MLADims, positions, theta, q_chunk=1024):
    """Materialized MLA for train/prefill. Returns (out, (c_kv, k_rope))."""
    b, s, d = x.shape
    h, dn, dr, dv = dims.n_heads, dims.qk_nope, dims.qk_rope, dims.v_dim
    q = matmul(x, params["wq"]).reshape(b, s, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, theta)
    c_kv = matmul(x, params["w_dkv"])  # (B, S, r)
    k_rope = apply_rope(
        matmul(x, params["w_krope"])[:, :, None, :], positions, theta
    )  # (B, S, 1, dr), shared across heads
    k_nope = matmul(c_kv, params["w_uk"]).reshape(b, s, h, dn)
    v = matmul(c_kv, params["w_uv"]).reshape(b, s, h, dv)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (b, s, h, dr))], axis=-1
    )
    out = causal_attend(q_full, k_full, v, q_chunk=q_chunk)
    out = matmul(out.reshape(b, s, h * dv), params["wo"])
    return out, (c_kv, k_rope[:, :, 0, :])


def mla_decode(params, x, dims: MLADims, c_cache, krope_cache, pos, theta):
    """Absorbed-matrix MLA decode: score/value contractions happen in the
    compressed c_kv space, so the per-token cache is (kv_lora + qk_rope) —
    the whole point of MLA. x: (B, C, d) — C == 1 is the decode tick, C > 1
    the parallel prefill chunk; caches already contain the whole chunk;
    pos: () shared or (B,) per-slot positions of the FIRST query token
    (query i sits at ``pos + i`` and reads ``kv_idx <= pos + i`` only).
    """
    b, c, d = x.shape
    h, dn, dr, dv, r = dims.n_heads, dims.qk_nope, dims.qk_rope, dims.v_dim, dims.kv_lora
    pos_b = jnp.broadcast_to(jnp.asarray(pos), (b,))
    q_pos = pos_b[:, None] + jnp.arange(c)[None, :]  # (B, C)
    q = matmul(x, params["wq"]).reshape(b, c, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, q_pos, theta)
    # absorb W_uk into the query: q' = q_nope @ W_uk^T per head -> r-dim
    w_uk = params["w_uk"].reshape(r, h, dn)
    q_c = jnp.einsum("bqhn,rhn->bqhr", q_nope.astype(jnp.float32), w_uk.astype(jnp.float32))
    scale = 1.0 / jnp.sqrt(jnp.asarray(dn + dr, jnp.float32))
    scores = (
        jnp.einsum("bqhr,bkr->bhqk", q_c, c_cache.astype(jnp.float32))
        + jnp.einsum("bqhr,bkr->bhqk", q_rope.astype(jnp.float32), krope_cache.astype(jnp.float32))
    ) * scale
    mask = (
        jnp.arange(c_cache.shape[1])[None, None, :] <= q_pos[:, :, None]
    )  # (B, C, S)
    scores = jnp.where(mask[:, None, :, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhqk,bkr->bqhr", w, c_cache.astype(jnp.float32))  # (B,C,H,r)
    w_uv = params["w_uv"].reshape(r, h, dv)
    out = jnp.einsum("bqhr,rhv->bqhv", ctx, w_uv.astype(jnp.float32)).astype(x.dtype)
    out = matmul(out.reshape(b, c, h * dv), params["wo"])
    return out
