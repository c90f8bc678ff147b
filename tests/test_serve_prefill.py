"""Parallel-within-chunk prefill: the multi-token ``model.prefill_step``
must reproduce the per-token-scan oracle token-for-token across every model
family (GQA, MLA, sliding-window + MoE, mamba2 hybrid, xLSTM) and both
cache layouts (dense stripes, paged block pools), under staggered admission
with unequal prompt lengths — plus regressions for the PR 3 bugfixes (VLM
extras wiring, the slot-capacity off-by-one, ServeEngine validation and
PRNG hygiene).

MoE archs run with dropless capacity (capacity_factor == num_experts), the
same convention as ``test_prefill_decode_consistency``: expert capacity is
computed per DISPATCH, so the per-token oracle (B tokens per step) and the
chunk dispatch (B*C tokens) drop different tokens when capacity binds —
routing itself is per-token and identical.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get
from repro.models import TransformerLM
from repro.serve import ContinuousBatcher, PagingSpec, Request, ServeEngine
from repro.serve.step import make_serve_step

MAX_SEQ = 32
PROMPT_LENS = (5, 9, 3, 7, 11, 4)  # 6 requests on 2 slots -> forced reuse
MAX_NEWS = (4, 6, 5, 3, 4, 6)
ARCHS = [
    "qwen2_5_14b",      # GQA
    "deepseek_v2_236b", # MLA compressed caches
    "mixtral_8x22b",    # sliding window + MoE
    "zamba2_7b",        # mamba2 SSD + shared_attn hybrid
    "xlstm_350m",       # mLSTM + sLSTM recurrences
    "granite_4_h_micro",  # mamba2 + NoPE GQA, each mixer with its own MLP
]


@functools.lru_cache(maxsize=None)
def _built(arch):
    cfg = get(arch, smoke=True)
    if arch == "mixtral_8x22b":
        # real masking over gathered pages (the smoke window 32 == MAX_SEQ
        # would never mask anything)
        cfg = dataclasses.replace(cfg, sliding_window=8)
    if cfg.uses_moe:
        # dropless capacity for scan-vs-parallel parity (see module docstring)
        cfg = dataclasses.replace(
            cfg, capacity_factor=float(cfg.num_experts)
        )
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _run(arch, mode, paging=None, num_slots=2, chunk=4):
    cfg, model, params = _built(arch)
    batcher = ContinuousBatcher(
        model, params, num_slots=num_slots, max_seq=MAX_SEQ,
        prefill_chunk=chunk, paging=paging, prefill_mode=mode,
    )
    rng = np.random.default_rng(0)
    for i, (n, mn) in enumerate(zip(PROMPT_LENS, MAX_NEWS)):
        batcher.submit(Request(
            uid=i,
            tokens=rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32),
            max_new=mn,
            task_id=i % cfg.num_tasks,
        ))
    done = batcher.run()
    assert len(done) == len(PROMPT_LENS)
    assert all(not r.truncated for r in done)
    return {r.uid: r.out for r in done}


@functools.lru_cache(maxsize=None)
def _oracle(arch):
    """The PR 2 per-token-scan path: prefill numerics == decode numerics by
    construction. Everything below is pinned against this."""
    return _run(arch, "scan")


# ----------------------------------------------------- scan-vs-parallel parity
@pytest.mark.parametrize("arch", ARCHS)
def test_parallel_prefill_matches_scan_dense(arch):
    """Staggered admission, unequal prompt lengths, slot reuse mid-run:
    greedy output of the parallel prefill must be token-for-token identical
    to the per-token-scan oracle on dense caches."""
    assert _run(arch, "parallel") == _oracle(arch)


@pytest.mark.parametrize("block_size", [8, 16])
@pytest.mark.parametrize("arch", ARCHS)
def test_parallel_prefill_matches_scan_paged(arch, block_size):
    """Same pin on the paged block-pool layout: the (B, C)-slab scatter
    through block tables must land every chunk token where the per-token
    scatter put it (including recycled blocks after slot reuse)."""
    spec = PagingSpec.sized(block_size, MAX_SEQ, pool_tokens=2 * MAX_SEQ)
    assert _run(arch, "parallel", paging=spec) == _oracle(arch)


def test_parallel_prefill_exact_under_xlstm_parallel_flag():
    """cfg.xlstm_parallel switches TRAINING to the chunkwise mLSTM (exact
    algebraically, ~1e-4 in floats) — serving prefill must ignore it and
    keep the sequential cell, or near-tied greedy argmax diverges from the
    decode/scan numerics. Pin scan == parallel with the flag on."""
    cfg = dataclasses.replace(
        get("xlstm_350m", smoke=True), xlstm_parallel=True
    )
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    outs = {}
    for mode in ("scan", "parallel"):
        batcher = ContinuousBatcher(
            model, params, num_slots=2, max_seq=MAX_SEQ, prefill_chunk=4,
            prefill_mode=mode,
        )
        rng = np.random.default_rng(0)
        for i, n in enumerate((5, 9, 3)):
            batcher.submit(Request(
                uid=i,
                tokens=rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32),
                max_new=4,
            ))
        outs[mode] = {r.uid: r.out for r in batcher.run()}
    assert outs["scan"] == outs["parallel"]


def test_parallel_prefill_chunk_width_invariant():
    """Chunk width is a dispatch-shape knob, not a numerics knob: any C must
    reproduce the oracle (C == 1 degenerates to one token per dispatch,
    C == 16 covers whole prompts in one dispatch)."""
    for chunk in (1, 3, 16):
        assert _run("qwen2_5_14b", "parallel", chunk=chunk) == \
            _oracle("qwen2_5_14b")


def test_parallel_prefill_is_structurally_parallel():
    """The acceptance property itself: no per-token scan over decode-step
    bodies. For an attention-only model the lowered parallel prefill
    contains exactly the per-stage layer scan (1 while loop); the scan path
    wraps it in the per-token loop (2, nested)."""
    cfg, model, params = _built("qwen2_5_14b")
    b, c, ms = 2, 4, 16
    caches = model.init_cache(b, ms)
    args = (
        params, jnp.zeros((b, c), jnp.int32), jnp.zeros(b, jnp.int32),
        caches, jnp.zeros(b, jnp.int32), jnp.ones((b, c), bool),
        jnp.zeros(b, bool), {}, None,
    )
    whiles = {}
    for mode in ("scan", "parallel"):
        _, prefill = make_serve_step(model, ms, None, mode)
        whiles[mode] = prefill.lower(*args).as_text().count("stablehlo.while")
    assert whiles["parallel"] == 1, whiles
    assert whiles["scan"] == 2, whiles


def test_prefill_step_leaves_non_prefilled_slots_untouched():
    """An all-False valid row (a slot mid-decode while others prefill) must
    keep caches AND cumulative recurrent states bit-identical — the chunk
    analogue of the decode live-mask freeze (xlstm + mamba cover the
    recurrences; attention rows are masked writes)."""
    for arch in ("xlstm_350m", "zamba2_7b"):
        cfg, model, params = _built(arch)
        rng = np.random.default_rng(7)
        b = 2
        caches = model.init_cache(b, MAX_SEQ)
        # advance BOTH slots a few real tokens first so states are non-trivial
        _, prefill = make_serve_step(model, MAX_SEQ, None, "parallel")
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, 4)), jnp.int32)
        _, caches, pos = prefill(
            params, toks, jnp.zeros(b, jnp.int32), caches,
            jnp.zeros(b, jnp.int32), jnp.ones((b, 4), bool),
            jnp.ones(b, bool), {}, None,
        )
        # now prefill ONLY slot 0; slot 1 rides along fully invalid
        valid = jnp.asarray([[True, True, False, False],
                             [False, False, False, False]])
        toks2 = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, 4)), jnp.int32)
        before = jax.tree.map(lambda t: np.asarray(t), caches)
        _, after, pos2 = prefill(
            params, toks2, jnp.zeros(b, jnp.int32), caches, pos, valid,
            jnp.zeros(b, bool), {}, None,
        )
        assert int(pos2[0]) == 6 and int(pos2[1]) == 4
        changed = False
        for old, new in zip(
            jax.tree_util.tree_leaves(before),
            jax.tree_util.tree_leaves(after),
        ):
            # leaves are (P, B, ...): slot 1 must be bit-identical
            np.testing.assert_array_equal(old[:, 1], np.asarray(new)[:, 1])
            changed |= not np.array_equal(old[:, 0], np.asarray(new)[:, 0])
        assert changed, arch  # slot 0 really did advance


# --------------------------------------------------------- VLM extras wiring
def _vlm_request(cfg, rng, uid, n, max_new=4):
    toks = rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
    emb = rng.standard_normal((n, cfg.d_model)).astype(np.float32)
    msk = np.zeros(n, bool)
    msk[: n // 2] = True
    return Request(uid=uid, tokens=toks, max_new=max_new,
                   extras={"vision_embeds": emb, "vision_mask": msk})


def test_vlm_extras_reach_the_prefill_dispatch():
    """Admission used to dispatch extras={} unconditionally, silently
    zeroing every vision embed. Wired extras must (a) match the engine fed
    the same vision inputs token-for-token and (b) actually change the
    output vs a text-only prompt."""
    cfg, model, params = _built_vlm()
    rng = np.random.default_rng(0)
    reqs = [_vlm_request(cfg, rng, i, n) for i, n in enumerate((6, 9))]
    engine = ServeEngine(model, params, max_seq=MAX_SEQ)
    refs = []
    for r in reqs:
        refs.append(engine.generate({
            "tokens": jnp.asarray(r.tokens)[None],
            "task_ids": jnp.zeros(1, jnp.int32),
            "vision_embeds": jnp.asarray(r.extras["vision_embeds"])[None],
            "vision_mask": jnp.asarray(r.extras["vision_mask"])[None],
        }, num_tokens=r.max_new)[0].tolist())
    batcher = ContinuousBatcher(model, params, num_slots=2, max_seq=MAX_SEQ,
                                prefill_chunk=4)
    for r in reqs:
        batcher.submit(r)
    outs = {r.uid: r.out for r in batcher.run()}
    for i, ref in enumerate(refs):
        assert outs[i] == ref, (i, outs[i], ref)
    # vision embeds really flowed: text-only request diverges
    b2 = ContinuousBatcher(model, params, num_slots=1, max_seq=MAX_SEQ,
                           prefill_chunk=4)
    b2.submit(Request(uid=0, tokens=reqs[0].tokens, max_new=4))
    assert b2.run()[0].out != refs[0]


@functools.lru_cache(maxsize=None)
def _built_vlm():
    cfg = get("pixtral_12b", smoke=True)
    model = TransformerLM(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def test_submit_validates_extras():
    cfg, model, params = _built_vlm()
    rng = np.random.default_rng(1)
    batcher = ContinuousBatcher(model, params, num_slots=1, max_seq=MAX_SEQ)
    # wrong shapes (mask/embeds not aligned with the prompt)
    bad = _vlm_request(cfg, rng, 0, 6)
    bad.extras["vision_mask"] = np.zeros(5, bool)
    with pytest.raises(ValueError, match="aligned with the prompt"):
        batcher.submit(bad)
    # missing keys
    bad2 = _vlm_request(cfg, rng, 1, 6)
    del bad2.extras["vision_embeds"]
    with pytest.raises(ValueError, match="vision_embeds"):
        batcher.submit(bad2)
    # extras on a non-VLM model are an error, not a silent no-op
    cfg_t, model_t, params_t = _built("qwen2_5_14b")
    b_t = ContinuousBatcher(model_t, params_t, num_slots=1, max_seq=MAX_SEQ)
    req = _vlm_request(cfg_t, rng, 2, 6)
    with pytest.raises(ValueError, match="vlm"):
        b_t.submit(req)


# ----------------------------------------------------- capacity off-by-one
def test_slot_capacity_last_position_is_usable():
    """pos is the NEXT write position: the guard must fire at capacity, not
    capacity - 1. A request smuggled past submit() (future schedulers may
    admit speculative requests) gets exactly capacity - S0 + 1 tokens — the
    old guard cut one writable position from every slot."""
    cfg, model, params = _built("qwen2_5_14b")
    max_seq = 16
    rng = np.random.default_rng(0)
    batcher = ContinuousBatcher(model, params, num_slots=1, max_seq=max_seq)
    req = Request(uid=0,
                  tokens=rng.integers(0, cfg.vocab_size, (12,)).astype(np.int32),
                  max_new=10)
    batcher.queue.append(req)  # bypass submit validation on purpose
    (done,) = batcher.run()
    assert done.truncated
    # 12 prompt + writes at 12..15 -> 5 generated tokens (old guard: 4)
    assert len(done.out) == max_seq - 12 + 1
    assert batcher.pos[0] == max_seq  # the last position really was written


def test_request_sized_exactly_to_capacity_finishes_untruncated():
    cfg, model, params = _built("qwen2_5_14b")
    batcher = ContinuousBatcher(model, params, num_slots=1, max_seq=16)
    batcher.submit(Request(uid=0, tokens=np.arange(10, dtype=np.int32),
                           max_new=6))
    (done,) = batcher.run()
    assert len(done.out) == 6 and not done.truncated


# ------------------------------------------------- ServeEngine satellites
def test_engine_generate_rejects_over_capacity():
    """The bare assert vanished under `python -O`; over-capacity prompts
    must raise a ValueError in submit()'s message style instead."""
    cfg, model, params = _built("qwen2_5_14b")
    engine = ServeEngine(model, params, max_seq=16)
    rng = np.random.default_rng(0)
    prompt = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 10)), jnp.int32),
        "task_ids": jnp.zeros(1, jnp.int32),
    }
    with pytest.raises(ValueError, match="exceeds the cache capacity"):
        engine.generate(prompt, num_tokens=7)
    out = engine.generate(prompt, num_tokens=6)  # boundary is fine
    assert out.shape == (1, 6)


def test_engine_temperature_sampling_is_keyed_by_request_id():
    """Sampled streams used to be keyed by batch position (split the key
    once per tick, row i takes subkey i), so any scheduler reordering or
    batch recomposition changed every request's tokens. Keys are now
    derived from the REQUEST ID: a request's stream must be a pure function
    of (key, uid, its own logits) — reversing the batch with request_ids
    travelling along reproduces each stream exactly."""
    from repro.serve.engine import _request_key, _sample

    cfg, model, params = _built("qwen2_5_14b")
    engine = ServeEngine(model, params, max_seq=MAX_SEQ)
    rng = np.random.default_rng(3)
    toks = rng.integers(0, cfg.vocab_size, (2, 6)).astype(np.int32)
    prompt = {"tokens": jnp.asarray(toks), "task_ids": jnp.zeros(2, jnp.int32)}
    key = jax.random.PRNGKey(42)
    out = engine.generate(prompt, num_tokens=4, key=key, temperature=1.0)
    out2 = engine.generate(prompt, num_tokens=4, key=key, temperature=1.0)
    np.testing.assert_array_equal(out, out2)  # deterministic in the seed
    # reorder stability: same requests, reversed rows, ids travel along
    rev = {"tokens": jnp.asarray(toks[::-1].copy()),
           "task_ids": jnp.zeros(2, jnp.int32)}
    out_rev = engine.generate(rev, num_tokens=4, key=key, temperature=1.0,
                              request_ids=[1, 0])
    np.testing.assert_array_equal(out_rev[::-1], out)
    # white-box pin: request u's token t samples fold_in(fold_in(key,u),t)
    # over its own logits row (captured via the pluggable sampler)
    logits = {}

    def probe(req, row):
        logits[(req.uid, len(req.out))] = np.asarray(row)
        return np.argmax(row, axis=-1)

    b = ContinuousBatcher(model, params, num_slots=2, max_seq=MAX_SEQ,
                          sample_fn=probe)
    for u in range(2):
        b.submit(Request(uid=u, tokens=toks[u], max_new=1))
    b.run()
    for u in range(2):
        expect = np.asarray(_sample(jnp.asarray(logits[(u, 0)]),
                                    _request_key(key, u, 0), 1.0))
        np.testing.assert_array_equal(out[u, 0], expect)
