"""The serving step's own spans, stamps and counters, at a tiny OLMo shape:
every phase of ``step()`` is a ``serve.*`` span in the profiler's trace,
nested as the executor runs them; the spans change no served token; each
request carries its admission and first-token times; the attention
kernel's walk is counted as the kernel bounds it; and the Pallas kernels
carry fixed names into the device trace."""
import glob
import itertools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.jaxpr_audit import walk_eqns
from repro.configs.base import ArchConfig
from repro.core.graph import band_graph
from repro.kernels.decode_attention.kernel import (
    decode_attention_pallas,
    paged_decode_attention_pallas,
)
from repro.kernels.graph_mix.kernel import graph_mix_pallas
from repro.kernels.prefill_attention.kernel import (
    paged_prefill_attention_pallas,
    prefill_attention_pallas,
)
from repro.models import TransformerLM
from repro.serve import (
    ContinuousBatcher,
    PagingSpec,
    Request,
    TaskAdapterStore,
)

NUM_TASKS = 8
# (prompt length, max_new, task) per request: prompts cross the 16-token
# chunk, and more requests than slots force slot reuse
SHAPES = ((24, 5, 0), (7, 3, 3), (40, 4, 5), (3, 6, 3), (18, 2, 7),
          (11, 4, 1))
SERVE_NAMES = {"serve.step", "serve.retire", "serve.admit", "serve.assemble",
               "serve.dispatch", "serve.device_wait", "serve.fetch",
               "serve.emit", "serve.finish", "serve.adapters.update"}
# greedy tokens of SHAPES served by the executor before it had spans
# (seed 0, float32, both regimes give the same tokens)
EXPECTED = [[151, 147, 190, 229, 121], [98, 50, 218], [132, 132, 132, 169],
            [182, 0, 0, 0, 0, 0], [48, 39], [66, 66, 245, 136]]


def _model(attn_backend="jnp"):
    cfg = ArchConfig(
        name="olmo", family="dense", num_layers=2, d_model=64, num_heads=4,
        num_kv_heads=4, head_dim=16, d_ff=128, vocab_size=256,
        pattern=("attn",), mlp_kind="swiglu", norm_kind="nonparam_ln",
        rope_theta=10000.0, tie_embeddings=True, num_tasks=NUM_TASKS,
        adapter_rank=2, attn_backend=attn_backend,
    )
    model = TransformerLM(cfg, dtype=jnp.float32)
    return model, model.init(jax.random.PRNGKey(0))


def _batcher(chunk_budget, **kw):
    model, params = _model()
    store = TaskAdapterStore(model, band_graph(NUM_TASKS, 2), rank=2,
                             update_every=2, dtype=jnp.float32)
    store.randomize(0.05)
    return ContinuousBatcher(
        model, params, num_slots=4, max_seq=64, prefill_chunk=16,
        paging=PagingSpec.sized(16, 64, 512), chunk_budget=chunk_budget,
        adapters=store, **kw,
    )


def _requests():
    rng = np.random.default_rng(0)
    return [Request(uid=i, tokens=rng.integers(1, 256, n).astype(np.int32),
                    max_new=m, task_id=t)
            for i, (n, m, t) in enumerate(SHAPES)]


def _serve(chunk_budget, **kw):
    b = _batcher(chunk_budget, **kw)
    for r in _requests():
        b.submit(r)
    b.run()
    return b, sorted(b.finished, key=lambda r: r.uid)


def _host_spans(trace_dir):
    """(name, start, end) of every host event named ``serve.*``."""
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in data.planes if not plane.name.startswith("/device")
            for line in plane.lines for e in line.events
            if e.name.startswith("serve.")]


def _inside(span, parents):
    _, a, b = span
    return any(pa <= a and b <= pb for _, pa, pb in parents)


@pytest.mark.parametrize("chunk_budget", [None, 32],
                         ids=["gulp", "interleaved"])
def test_step_phases_are_nested_spans(tmp_path, chunk_budget):
    b = _batcher(chunk_budget)
    for r in _requests():
        b.submit(r)
    b.step()  # compile outside the trace
    before = b.ticks + b.prefill_dispatches
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            while b.queue or b.slots.any_live():
                b.step()
    dispatches = b.ticks + b.prefill_dispatches - before
    spans = _host_spans(str(tmp_path))
    by = {n: [s for s in spans if s[0] == n] for n in SERVE_NAMES}
    assert {n for n, _, _ in spans} == SERVE_NAMES
    steps = by["serve.step"]
    assert all(_inside(s, steps) for s in spans if s[0] != "serve.step")
    assert all(_inside(s, by["serve.finish"])
               for s in by["serve.adapters.update"])
    assert len(by["serve.device_wait"]) == len(by["serve.fetch"]) == dispatches
    # each dispatch ends: enqueue, then wait, then copy
    for (_, _, d_end), (_, w0, w_end), (_, f0, _) in zip(
            by["serve.dispatch"], by["serve.device_wait"], by["serve.fetch"]):
        assert d_end <= w0 and w_end <= f0


@pytest.mark.parametrize("chunk_budget", [None, 32],
                         ids=["gulp", "interleaved"])
def test_spans_leave_served_tokens_unchanged(tmp_path, chunk_budget):
    _, plain = _serve(chunk_budget)
    with jax.profiler.trace(str(tmp_path)):
        _, traced = _serve(chunk_budget)
    assert [r.out for r in plain] == [r.out for r in traced] == EXPECTED


@pytest.mark.parametrize("chunk_budget", [None, 32],
                         ids=["gulp", "interleaved"])
def test_requests_are_stamped_on_the_scheduler_clock(chunk_budget):
    b, done = _serve(chunk_budget, now_fn=itertools.count().__next__)
    assert all(r.done for r in done)
    for r in done:
        assert r.submit_time <= r.admit_time <= r.first_token_time


def test_readmitted_request_keeps_its_first_admit_time():
    """A tight pool: the low-priority hog is swapped out for a short
    request and admitted again later; its admit_time is the first one."""
    model, params = _model()
    clock = itertools.count()
    b = ContinuousBatcher(
        model, params, num_slots=2, max_seq=32, prefill_chunk=8,
        paging=PagingSpec.sized(8, 32, 4 * 8), policy="priority",
        preempt=True, now_fn=clock.__next__,
    )
    rng = np.random.default_rng(11)
    hog = Request(uid=0, priority=10, max_new=16,
                  tokens=rng.integers(1, 256, (8,)).astype(np.int32))
    b.submit(hog)
    b.step()
    b.step()
    first_admit = hog.admit_time
    short = Request(uid=1, priority=0, max_new=6,
                    tokens=rng.integers(1, 256, (6,)).astype(np.int32))
    b.submit(short)
    b.run()
    assert hog.preemptions == 1 and b.swap_ins == 1 and hog.done
    assert hog.admit_time == first_admit
    assert short.admit_time > first_admit
    assert hog.first_token_time < short.first_token_time


# (walked, listed) after each step of two requests, prompts 12 and 3,
# max_new 2 and 3, on 2 slots of 4 blocks of 8 (8 entries a dispatch),
# kernel walk cdiv(kv_len, 8) a slot:
WALKS = {
    # fused ticks, budget 8: kv_len (8, 0); (12, 3); decode (13, 4); then
    # slot 0 released at its stale pos 13 walks 0 beside (5,)
    "fused": [(1, 8), (4, 16), (7, 24), (8, 32)],
    # admission gulp (8, 3), (12, 0), decode tick (13, 4); then (0, 5)
    "gulp": [(7, 24), (8, 32)],
    # the gulp's 8 columns as decode kernels, kv_len pos + i + 1 where
    # valid: 8 + 3 blocks, then 4 x 2 blocks, 64 entries a dispatch each;
    # then the two decode ticks
    "scan": [(22, 136), (23, 144)],
}


@pytest.mark.parametrize("regime", list(WALKS))
def test_attention_walk_counters(regime):
    """``attn_blocks_walked`` / ``attn_blocks_listed`` equal a count by
    hand, step by step; the jnp backend walks no table and counts nothing,
    and serves the same tokens."""
    kw = {"fused": {"chunk_budget": 8}, "gulp": {},
          "scan": {"prefill_mode": "scan"}}[regime]
    served = {}
    for backend in ("pallas", "jnp"):
        model, params = _model(backend)
        b = ContinuousBatcher(
            model, params, num_slots=2, max_seq=32, prefill_chunk=8,
            paging=PagingSpec.sized(8, 32, 64), **kw,
        )
        rng = np.random.default_rng(1)
        for uid, (n, m) in enumerate(((12, 2), (3, 3))):
            b.submit(Request(uid=uid, max_new=m,
                             tokens=rng.integers(1, 256, n).astype(np.int32)))
        walks = []
        while b.queue or b.slots.any_live():
            b.step()
            walks.append((b.attn_blocks_walked, b.attn_blocks_listed))
        served[backend] = {r.uid: r.out for r in b.finished}
        if backend == "pallas":
            assert walks == WALKS[regime]
            assert b.slots.reqs[0] is None and b.pos[0] == 13
        else:
            assert walks == [(0, 0)] * len(WALKS[regime])
    assert served["pallas"] == served["jnp"]


def _pallas_names(fn, *args):
    jaxpr = jax.make_jaxpr(fn)(*args)
    return [e.params["name"] for e in walk_eqns(jaxpr)
            if e.primitive.name == "pallas_call"]


def _paged_attention():
    b, kvh, c, g, hd, blk = 2, 2, 8, 2, 128, 16
    q = jnp.zeros((b, kvh, c, g, hd), jnp.float32)
    pool = jnp.zeros((5, blk, kvh, hd), jnp.float32)
    table = jnp.array([[1, 2], [3, 4]], jnp.int32)
    pos = jnp.zeros((b,), jnp.int32)
    return (lambda *a: paged_prefill_attention_pallas(*a, interpret=True),
            (q, pool, pool, table, pos))


def _dense_attention():
    b, kvh, c, g, hd, s = 2, 2, 8, 2, 128, 32
    q = jnp.zeros((b, kvh, c, g, hd), jnp.float32)
    kv = jnp.zeros((b, s, kvh, hd), jnp.float32)
    pos = jnp.zeros((b,), jnp.int32)
    return (lambda *a: prefill_attention_pallas(*a, block_s=16,
                                                interpret=True),
            (q, kv, kv, pos))


def _paged_decode():
    _, (q, pool, _, table, pos) = _paged_attention()
    return (lambda *a: paged_decode_attention_pallas(*a, interpret=True),
            (q[:, :, 0], pool, pool, table, pos))


def _dense_decode():
    _, (q, kv, _, pos) = _dense_attention()
    return (lambda *a: decode_attention_pallas(*a, block_s=16,
                                               interpret=True),
            (q[:, :, 0], kv, kv, pos))


def _graph_mix():
    return (lambda m, t: graph_mix_pallas(m, t, interpret=True),
            (jnp.eye(8), jnp.ones((8, 256))))


@pytest.mark.parametrize("kernel, name", [
    (_paged_attention, "paged_attention_pallas"),
    (_dense_attention, "dense_attention_pallas"),
    (_paged_decode, "paged_attention_pallas"),
    (_dense_decode, "dense_attention_pallas"),
    (_graph_mix, "graph_mix_pallas"),
], ids=["paged_prefill", "dense_prefill", "paged_decode", "dense_decode",
        "graph_mix"])
def test_pallas_kernels_carry_fixed_names(kernel, name):
    fn, args = kernel()
    assert _pallas_names(fn, *args) == [name]
