"""Chunked flash-attention Pallas TPU kernel: a (B, C) query slab vs the cache.

The serving attention hot spot: admission writes a whole (B, C) prompt chunk
into the KV cache at per-slot offsets (``model.prefill_step``), then every
chunk token attends against the cache PREFIX it is allowed to see — query i
of slot b sits at absolute position ``pos[b] + i`` and reads
``kv_idx <= pos[b] + i`` only (sliding window subtracts the tail). Decode is
the C == 1 case (repro.kernels.decode_attention calls this kernel), so ONE
kernel serves decode and prefill, dense and paged.

Every layout is walked as a paged pool: K/V live in a
(num_blocks, block, KVH, hd) pool and each slot's blocks are chased through
a (B, max_blocks) block table. The dense cache (B, S, KVH, hd) is the pool
whose table maps slot b's i-th block to physical block b * (S / block) + i
(a free reshape). The table and the per-slot positions are SCALAR-PREFETCH
arguments (pltpu.PrefetchScalarGridSpec): the grid's innermost axis walks the
slot's LOGICAL blocks while the BlockSpec index_map translates each step to
its physical block, so the gather never materializes a contiguous per-slot
view in HBM.

The walk is bounded by ``kv_len[b]``, a third scalar-prefetch argument: the
count of key positions slot b's valid queries may see (``pos + n_valid``; 0
for a slot with no valid query). Steps past the slot's last live block run
no body, and their index_map repeats that block, so the pipeline issues no
copy: a step costs grid overhead only, however long the table is. Valid rows
are bitwise those of a walk over every entry: each skipped block was masked
for all of them (``alpha = 1``, ``p = 0``). A slot that walks no block
writes 0; rows past a slot's valid queries are garbage the caller discards.

Grid (B, C*G / TQ, max_blocks), block axis innermost (sequential on TPU),
running (max, sum, acc) carried in VMEM scratch per KV head:

    (ii * BLK < kv_len only)
    s     = q_tile @ k_blk^T * scale        (TQ, BLK)   MXU
    mask  = kv_idx <= pos + row // G  [ & window ]
    m_new = max(m, rowmax(s));  p = exp(s - m_new)
    l     = exp(m - m_new) * l + rowsum(p)
    acc   = exp(m - m_new) * acc + p @ v_blk  (TQ, hd)   MXU
    (last block)  out = acc / l

Each grid step takes every KV head: a (1, BLK, KVH, hd) K/V tile, whose
last two dims are the whole array's and so pass Mosaic's tiling rule — a
(…, 1, hd) single-head tile of the same layout is refused. The kernel loops
over heads in VMEM. Query rows are tiled by TQ (``QUERY_TILE``) so VMEM use
is bounded at any chunk width; the KV prefix streams HBM -> VMEM once per
(slot, query tile) while TQ queries amortize it — arithmetic intensity grows
with the chunk width, which is what makes chunked prefill compute-bound where
decode (one tile) is bandwidth-bound.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret

DEFAULT_BLOCK_S = 256
# query rows per grid step: bounds the per-step VMEM (q/out tiles plus f32
# acc/max/sum scratch for every KV head) independently of the chunk width
QUERY_TILE = 128
NEG_INF = -1e30


def _flash_kernel(bt_ref, pos_ref, kv_len_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, block, group, scale, window):
    """One step = one block of one slot's table for one query-row tile, all
    KV heads. The physical block was selected by the BlockSpec index_map
    from the prefetched table; here the block only needs its LOGICAL span
    (ii * block + offset) for masking. Blocks at or past ``kv_len`` are
    skipped."""
    del bt_ref  # consumed by the index_maps only
    # grid indices are read here: interpret mode resolves them only outside
    # the pl.when bodies
    bb, qi, ii = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    num_ii = pl.num_programs(2)
    kvh, tq = q_ref.shape[1], q_ref.shape[2]

    @pl.when(ii == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(ii * block < kv_len_ref[bb])
    def _step():
        # row r is (chunk token r // G, group r % G) and sits at absolute
        # position pos + r // G — the same kv_idx <= pos + i mask the jnp
        # path uses (it also hides unwritten cache rows, so in-chunk
        # causality falls out for free). Padding rows past C*G are garbage
        # the wrapper drops.
        pos = pos_ref[bb]
        row = qi * tq + jax.lax.broadcasted_iota(
            jnp.int32, (tq, block), 0
        )
        q_idx = row // group
        kv_idx = ii * block + jax.lax.broadcasted_iota(
            jnp.int32, (tq, block), 1
        )
        mask = kv_idx <= pos + q_idx  # masks unmapped (null-block) pages too
        if window is not None:
            mask &= kv_idx > pos + q_idx - window

        for h in range(kvh):
            q = q_ref[0, h]  # (TQ, hd)
            k = k_ref[0, :, h, :]  # (BLK, hd)
            v = v_ref[0, :, h, :]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # (TQ, BLK)
            s = jnp.where(mask, s, NEG_INF)

            m_old = m_ref[h][:, 0]  # (TQ,)
            l_old = l_ref[h][:, 0]
            m_new = jnp.maximum(m_old, jnp.max(s, axis=1))
            alpha = jnp.exp(m_old - m_new)
            p = jnp.exp(s - m_new[:, None])  # (TQ, BLK)
            l_new = alpha * l_old + jnp.sum(p, axis=1)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (TQ, hd)
            acc_ref[h] = acc_ref[h] * alpha[:, None] + pv
            m_ref[h] = jnp.broadcast_to(m_new[:, None], m_ref.shape[1:])
            l_ref[h] = jnp.broadcast_to(l_new[:, None], l_ref.shape[1:])

    @pl.when(ii == num_ii - 1)
    def _fin():
        for h in range(kvh):
            l = l_ref[h][:, 0]
            o_ref[0, h] = (
                acc_ref[h] / jnp.maximum(l, 1e-30)[:, None]
            ).astype(o_ref.dtype)


def flash_attention(q, k_pool, v_pool, block_tables, pos, *, window,
                    interpret, name, kv_len=None):
    """Shared body of every attention kernel entry point (not jitted: the
    jitted wrappers each keep their own trace cache). ``name`` names the
    device operation in a profile.

    q: (B, KVH, C, G, hd) query slab grouped per KV head; k_pool/v_pool:
    (num_blocks, block, KVH, hd); block_tables: (B, max_blocks); pos: () or
    (B,) position of each slot's FIRST chunk token; kv_len: (B,) key
    positions each slot's valid queries may see (0: no valid query), which
    bounds the slot's walk; None means ``pos + C`` for every slot."""
    interpret = resolve_interpret(interpret)
    b, kvh, cq, g, hd = q.shape
    block_tables = jnp.asarray(block_tables, jnp.int32)
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    if kv_len is None:
        kv_len = pos + cq
    kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (b,))
    block = k_pool.shape[1]
    max_blocks = block_tables.shape[1]
    rows = cq * g
    # pad the flat C*G slab (not G) to whole sublanes, then to whole query
    # tiles; row // G still recovers the chunk token for every real row
    rp = rows + (-rows) % 8
    tq = min(rp, QUERY_TILE)
    rp += (-rp) % tq
    qf = q.reshape(b, kvh, rows, hd)
    if rp != rows:
        qf = jnp.pad(qf, ((0, 0), (0, 0), (0, rp - rows), (0, 0)))
    scale = float(1.0 / (hd ** 0.5))

    kernel = functools.partial(
        _flash_kernel, block=block, group=g, scale=scale, window=window
    )
    q_spec = pl.BlockSpec(
        (1, kvh, tq, hd), lambda bb, qi, ii, bt, ps, kl: (bb, 0, qi, 0)
    )

    def kv_block(bb, qi, ii, bt, ps, kl):
        # steps past the slot's last live block repeat it: no new copy
        last = jnp.maximum((kl[bb] + block - 1) // block - 1, 0)
        return (bt[bb, jnp.minimum(ii, last)], 0, 0, 0)

    # all KV heads per step: a (1, block, 1, hd) single-head tile of the
    # (…, KVH, hd) layout breaks Mosaic's (8, 128) tiling rule
    kv_spec = pl.BlockSpec((1, block, kvh, hd), kv_block)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # block table, positions and walk lengths drive the index_maps
        num_scalar_prefetch=3,
        grid=(b, rp // tq, max_blocks),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((kvh, tq, hd), jnp.float32),
            pltpu.VMEM((kvh, tq, 128), jnp.float32),
            pltpu.VMEM((kvh, tq, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, rp, hd), q.dtype),
        interpret=interpret,
        name=name,
    )(block_tables, pos, kv_len, qf, k_pool, v_pool)
    return out[:, :, :rows].reshape(b, kvh, cq, g, hd)


def dense_as_pool(k, v, block_s):
    """View a dense (B, S, KVH, hd) cache pair as a block pool plus the
    identity block table: slot b's block i is physical block
    b * (S / block_s) + i. Positions past S are padding the mask hides."""
    b, s = k.shape[:2]
    s_pad = (-s) % block_s
    if s_pad:
        k = jnp.pad(k, ((0, 0), (0, s_pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, s_pad), (0, 0), (0, 0)))
    nblk = (s + s_pad) // block_s
    tail = k.shape[2:]
    table = jnp.arange(b * nblk, dtype=jnp.int32).reshape(b, nblk)
    return (
        k.reshape((b * nblk, block_s) + tail),
        v.reshape((b * nblk, block_s) + tail),
        table,
    )


@functools.partial(
    jax.jit, static_argnames=("block_s", "window", "interpret")
)
def prefill_attention_pallas(
    q: jax.Array,  # (B, KVH, C, G, hd) query slab, grouped per KV head
    k: jax.Array,  # (B, S, KVH, hd)
    v: jax.Array,  # (B, S, KVH, hd)
    pos: jax.Array,  # (B,) per-slot positions of the chunk's FIRST token
    kv_len: jax.Array | None = None,  # (B,) keys visible to valid queries
    *,
    block_s: int = DEFAULT_BLOCK_S,
    window: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    k_pool, v_pool, table = dense_as_pool(k, v, block_s)
    return flash_attention(
        q, k_pool, v_pool, table, pos, window=window, interpret=interpret,
        name="dense_attention_pallas", kv_len=kv_len,
    )


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_prefill_attention_pallas(
    q: jax.Array,  # (B, KVH, C, G, hd) query slab, grouped per KV head
    k_pool: jax.Array,  # (num_blocks, block_size, KVH, hd) shared pool
    v_pool: jax.Array,  # (num_blocks, block_size, KVH, hd)
    block_tables: jax.Array,  # (B, max_blocks) physical page ids (0 = null)
    pos: jax.Array,  # (B,) per-slot positions of the chunk's FIRST token
    kv_len: jax.Array | None = None,  # (B,) keys visible to valid queries
    *,
    window: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    return flash_attention(
        q, k_pool, v_pool, block_tables, pos, window=window,
        interpret=interpret, name="paged_attention_pallas", kv_len=kv_len,
    )
