"""Paged block-table KV cache: dense-vs-paged greedy parity (GQA, MLA,
sliding-window + MoE), staggered admission with block free/realloc,
out-of-blocks admission backpressure, and allocator/submit invariants."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get
from repro.models import TransformerLM
from repro.serve import BlockAllocator, ContinuousBatcher, PagingSpec, Request

MAX_SEQ = 32
PROMPT_LENS = (5, 9, 3, 7)
MAX_NEWS = (4, 6, 5, 3)


@functools.lru_cache(maxsize=None)
def _built(arch):
    import dataclasses

    cfg = get(arch, smoke=True)
    if arch == "mixtral_8x22b":
        # smoke window (32) >= max_seq would never mask anything; shrink it
        # so windowed reads over gathered pages are actually exercised
        cfg = dataclasses.replace(cfg, sliding_window=8)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _requests(cfg):
    rng = np.random.default_rng(0)
    return [
        Request(
            uid=i,
            tokens=rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32),
            max_new=mn,
            task_id=i % cfg.num_tasks,
        )
        for i, (n, mn) in enumerate(zip(PROMPT_LENS, MAX_NEWS))
    ]


def _run_batcher(arch, paging, num_slots=2):
    cfg, model, params = _built(arch)
    batcher = ContinuousBatcher(
        model, params, num_slots=num_slots, max_seq=MAX_SEQ,
        prefill_chunk=4, paging=paging,
    )
    for r in _requests(cfg):
        batcher.submit(r)
    done = batcher.run()
    assert len(done) == len(PROMPT_LENS)
    return {r.uid: r.out for r in done}, batcher


@functools.lru_cache(maxsize=None)
def _dense_outputs(arch):
    return _run_batcher(arch, None)[0]


# ------------------------------------------------------ dense-vs-paged parity
@pytest.mark.parametrize("block_size", [8, 16])
@pytest.mark.parametrize(
    "arch",
    ["qwen2_5_14b", "deepseek_v2_236b", "mixtral_8x22b", "zamba2_7b",
     "granite_4_h_micro"],
)
def test_paged_matches_dense_token_for_token(arch, block_size):
    """Same model/requests/slots, only the cache layout differs: the paged
    batcher must reproduce the dense batcher's greedy stream exactly.
    Covers the GQA stripe, the MLA compressed (c_kv, k_rope) caches,
    sliding-window masking over gathered pages (mixtral, shrunk window,
    also exercises MoE decode), and the hybrid shared_attn + mamba stack
    (zamba2: paged attention pools and DENSE recurrent states in one cache
    pytree, including the mixed reset path on slot reuse)."""
    spec = PagingSpec.sized(block_size, MAX_SEQ, pool_tokens=2 * MAX_SEQ)
    paged, batcher = _run_batcher(arch, spec)
    assert paged == _dense_outputs(arch)
    # every block returned to the free list once all requests finished
    assert batcher.allocator.free_blocks == spec.num_blocks - 1
    assert all(not blocks for blocks in batcher.slot_blocks)


def test_staggered_admission_reuses_freed_blocks():
    """More requests than the pool can hold at once: finished requests must
    free their blocks and later admissions must recycle those SAME physical
    blocks (stale bytes are unreachable because reads mask kv_idx <= pos)."""
    cfg, model, params = _built("qwen2_5_14b")
    # pool of 6 blocks of 8 = 48 tokens; each request needs 2-3 blocks, and
    # the 6 requests need 14 blocks in total -> reuse is forced
    spec = PagingSpec(block_size=8, num_blocks=7, max_blocks_per_slot=4)
    batcher = ContinuousBatcher(
        model, params, num_slots=2, max_seq=MAX_SEQ, prefill_chunk=4,
        paging=spec,
    )
    rng = np.random.default_rng(1)
    lens = (9, 5, 17, 3, 11, 7)
    total_blocks = sum(spec.blocks_for(n + 4) for n in lens)
    assert total_blocks > spec.num_blocks - 1  # demand exceeds the pool
    for i, n in enumerate(lens):
        batcher.submit(Request(
            uid=i,
            tokens=rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32),
            max_new=4,
            task_id=i % cfg.num_tasks,
        ))
    done = batcher.run()
    assert sorted(r.uid for r in done) == list(range(len(lens)))
    assert all(len(r.out) == 4 and not r.truncated for r in done)
    assert batcher.allocator.free_blocks == spec.num_blocks - 1
    # the pool's high-water mark stayed within the physical budget the
    # whole run — slots never owned more than exists
    assert batcher.allocator.high_water <= spec.num_blocks - 1


def test_out_of_blocks_admission_backpressure():
    """When the free list cannot cover the queue head, admission WAITS
    (request stays queued, slot stays empty) instead of corrupting the pool;
    the request is admitted as soon as a finishing request frees blocks."""
    cfg, model, params = _built("qwen2_5_14b")
    # 3 allocatable blocks of 8; each request (prompt 9 + 4 new = 13 tokens)
    # needs 2 blocks -> only ONE request fits at a time despite 2 free slots
    spec = PagingSpec(block_size=8, num_blocks=4, max_blocks_per_slot=2)
    batcher = ContinuousBatcher(
        model, params, num_slots=2, max_seq=MAX_SEQ, prefill_chunk=4,
        paging=spec,
    )
    rng = np.random.default_rng(2)
    for i in range(2):
        batcher.submit(Request(
            uid=i,
            tokens=rng.integers(0, cfg.vocab_size, (9,)).astype(np.int32),
            max_new=4,
        ))
    batcher._admit()
    assert sum(r is not None for r in batcher.active) == 1  # backpressure
    assert len(batcher.queue) == 1
    assert batcher.allocator.free_blocks == 1  # 2 of 3 reserved
    done = batcher.run()
    assert sorted(r.uid for r in done) == [0, 1]
    assert all(len(r.out) == 4 for r in done)
    assert batcher.allocator.free_blocks == 3


def test_submit_rejects_request_that_can_never_fit_pool():
    cfg, model, params = _built("qwen2_5_14b")
    spec = PagingSpec(block_size=8, num_blocks=3, max_blocks_per_slot=4)
    batcher = ContinuousBatcher(
        model, params, num_slots=1, max_seq=MAX_SEQ, paging=spec,
    )
    # capacity = min(max_seq=32, 4 blocks x 8 = 32) but only 2 allocatable
    # blocks exist: 17+8 = 25 tokens -> 4 blocks can never be allocated
    with pytest.raises(ValueError, match="KV blocks"):
        batcher.submit(Request(uid=0, tokens=np.arange(17, dtype=np.int32),
                               max_new=8))


def test_submit_rejects_over_slot_capacity_paged():
    """Per-slot capacity under paging is min(max_seq, blocks x block_size)."""
    cfg, model, params = _built("qwen2_5_14b")
    spec = PagingSpec(block_size=8, num_blocks=16, max_blocks_per_slot=2)
    batcher = ContinuousBatcher(
        model, params, num_slots=1, max_seq=MAX_SEQ, paging=spec,
    )
    with pytest.raises(ValueError, match="capacity"):
        batcher.submit(Request(uid=0, tokens=np.arange(10, dtype=np.int32),
                               max_new=8))  # 18 > 2 blocks x 8 = 16


# -------------------------------------------------------------- allocator
def test_block_allocator_invariants():
    spec = PagingSpec(block_size=8, num_blocks=5, max_blocks_per_slot=4)
    alloc = BlockAllocator(spec)
    assert alloc.free_blocks == 4
    a = alloc.alloc(3)
    assert len(set(a)) == 3 and 0 not in a  # disjoint, never the null block
    assert not alloc.can_alloc(2)
    with pytest.raises(RuntimeError, match="out of KV blocks"):
        alloc.alloc(2)
    b = alloc.alloc(1)
    assert set(b).isdisjoint(a) and 0 not in b
    alloc.free(a)
    c = alloc.alloc(3)
    assert set(c) == set(a)  # freed blocks really are recycled
    assert alloc.high_water == 4


def test_paging_spec_sized():
    spec = PagingSpec.sized(8, max_seq=32, pool_tokens=64)
    assert spec.num_blocks == 9  # 64/8 allocatable + null block
    assert spec.max_blocks_per_slot == 4
    assert spec.tokens_per_slot == 32
    assert spec.blocks_for(1) == 1 and spec.blocks_for(17) == 3


# ---------------------------------------------------------- paged init_cache
def test_paged_cache_memory_is_pool_sized_not_slot_sized():
    """The whole point: attention KV memory scales with the pool, not with
    num_slots x max_seq. 16 slots over a 2-dense-slot-sized pool must not
    allocate more KV bytes than 2 dense slots (modulo the null block)."""
    cfg, model, params = _built("qwen2_5_14b")
    dense = model.init_cache(2, MAX_SEQ)
    spec = PagingSpec.sized(8, MAX_SEQ, pool_tokens=2 * MAX_SEQ)
    paged = model.init_cache(16, MAX_SEQ, spec)
    nbytes = lambda tree: sum(
        t.size * t.dtype.itemsize for t in jax.tree_util.tree_leaves(tree)
    )
    # qwen smoke is attention-only, so all cache bytes are KV bytes
    assert nbytes(paged) <= nbytes(dense) * (
        spec.num_blocks / (spec.num_blocks - 1)
    ) + 1


# ------------------------------------- paged flash-decode Pallas kernel
# (here rather than test_kernels.py so they run without hypothesis)
def _paged_case(seed, b=3, kvh=2, g=4, hd=64, page=16, nb=12, mb=4):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, kvh, g, hd)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((nb, page, kvh, hd)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((nb, page, kvh, hd)), jnp.float32)
    # non-contiguous, per-slot-permuted tables with unmapped (0) tails
    tables = np.zeros((b, mb), np.int32)
    free = rng.permutation(np.arange(1, nb))
    take = 0
    for i in range(b):
        n = rng.integers(1, mb + 1)
        tables[i, :n] = free[take : take + n]
        take += n
    pos = jnp.asarray(
        [int(rng.integers(0, np.count_nonzero(tables[i]) * page)) for i in range(b)],
        jnp.int32,
    )
    return q, kp, vp, jnp.asarray(tables), pos


@pytest.mark.parametrize("page", [8, 16])
def test_paged_decode_attention_matches_reference(page):
    """Block-table kernel == gather-then-dense oracle, per-slot positions,
    scattered physical pages, unmapped (null) table tails."""
    from repro.kernels.decode_attention.kernel import paged_decode_attention_pallas
    from repro.kernels.decode_attention.ref import paged_decode_attention_reference

    q, kp, vp, bt, pos = _paged_case(seed=7, page=page)
    got = paged_decode_attention_pallas(q, kp, vp, bt, pos, interpret=True)
    want = paged_decode_attention_reference(q, kp, vp, bt, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


def test_paged_decode_attention_sliding_window():
    from repro.kernels.decode_attention.kernel import paged_decode_attention_pallas
    from repro.kernels.decode_attention.ref import paged_decode_attention_reference

    q, kp, vp, bt, pos = _paged_case(seed=8)
    got = paged_decode_attention_pallas(q, kp, vp, bt, pos, window=12,
                                        interpret=True)
    want = paged_decode_attention_reference(q, kp, vp, bt, pos, window=12)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


def test_paged_decode_attention_matches_serving_gather_path():
    """Kernel == gather_pages + decode_attend, the jnp pair the model's
    paged decode path actually uses — ties the kernel to serving numerics."""
    from repro.kernels.decode_attention.kernel import paged_decode_attention_pallas
    from repro.models.attention import decode_attend, gather_pages

    q, kp, vp, bt, pos = _paged_case(seed=9)
    b, kvh, g, hd = q.shape
    got = paged_decode_attention_pallas(q, kp, vp, bt, pos, interpret=True)
    want = decode_attend(
        q.reshape(b, 1, kvh * g, hd),
        gather_pages(kp, bt), gather_pages(vp, bt), pos,
    )
    np.testing.assert_allclose(
        np.asarray(got.reshape(b, 1, kvh * g, hd)), np.asarray(want),
        atol=3e-5,
    )
