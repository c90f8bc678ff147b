"""Serving throughput benchmark: vectorized continuous-batching decode over
dense and PAGED (block-table) KV caches.

Measures tokens/sec and jitted dispatches-per-tick as a function of slot
count, and ASSERTS the properties the serving stack exists for:

  * decode dispatch count is O(1) in ``num_slots`` (exactly one jitted
    decode dispatch per tick no matter how many slots are live),
  * the batcher's greedy output matches ``ServeEngine.generate``
    token-for-token, and
  * the PAGED cache serves >= 4x the slots of the dense layout at equal
    KV-cache memory, token-for-token identical to the dense engine, at
    block_size 8 and 16 (the dense layout spends num_slots x max_seq
    tokens of KV memory regardless of request length; the paged pool
    spends what requests actually use), and
  * the parallel-within-chunk prefill matches the per-token-scan oracle
    token-for-token at the SAME dispatch count (ceil(S0 / chunk) per
    admission round), reporting prompt tokens/sec for both paths, and
  * the "pallas" attention backend (flash-decode + chunked flash-prefill
    kernels, dense AND block-table paged) matches the "jnp" backend
    token-for-token, reporting decode and prefill tok/s for both backends,
    and
  * graph-mixed per-task adapter serving (multitask_lm arch): a zero
    adapter store is token-for-token identical to the no-adapter engine,
    a mixed-task batch with randomized adapters keeps O(1) decode
    dispatches per tick and >= 0.15x the baseline throughput while the
    online delayed-update loop re-mixes the store mid-run, and
  * prefix-shared copy-on-write KV blocks: 8 slots sharing a 100-token
    system prompt serve >= 2x the prefill tok/s and >= 2x the
    slots-per-KV-byte of the no-sharing baseline, token-for-token
    identical under both attention backends, with every request
    copy-on-writing the partially shared tail block, and
  * graceful degradation under block pressure: with the pool saturated by
    low-urgency hogs, preemptive swap-out (``preempt=True``) strictly
    improves high-urgency shorts' p99 time-to-first-token (in ticks) over
    refusal-only admission at < 2x makespan, with every swap-out restored
    exactly (token parity across both modes).

The interesting number on CPU is dispatches/tick and the slot-scaling of
tokens/sec (per-dispatch overhead dominates small smoke models, which is
exactly the regime where the old one-slot-per-dispatch loop collapsed to
1/num_slots of the throughput); the pallas kernels run in interpret mode
on CPU, so their tok/s here measures the code path, not TPU speed.

``--json [PATH]`` APPENDS a timestamped entry to the perf trajectory
(decode/prefill tok/s per backend, slots-per-KV-byte, prefix-cache
speedups) in ``BENCH_serve.json`` (default): the file holds
``{"history": [entry, ...]}`` ordered oldest-first so future PRs can
diff perf across runs; ``make bench-smoke`` emits an entry on every CI
run. A pre-history single-object file is migrated as the first entry.

  PYTHONPATH=src python benchmarks/serve_throughput.py [--arch olmo_1b]
      [--slots 1 2 4 8] [--prompt-len 8] [--max-new 16] [--skip-paged]
      [--skip-prefill] [--skip-backends] [--skip-latency]
      [--skip-multitask] [--skip-prefix] [--skip-degradation]
      [--attn-backend jnp|pallas] [--json [PATH]]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from datetime import datetime, timezone

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get
from repro.launch.compile_cache import use_compile_cache
from repro.models import TransformerLM
from repro.serve import ContinuousBatcher, PagingSpec, Request, ServeEngine


def bench_slots(model, params, cfg, num_slots, prompt_len, max_new, max_seq):
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(0, cfg.vocab_size, (prompt_len,)).astype(np.int32)
        for _ in range(num_slots)
    ]
    batcher = ContinuousBatcher(
        model, params, num_slots=num_slots, max_seq=max_seq
    )
    for i, p in enumerate(prompts):
        batcher.submit(
            Request(uid=i, tokens=p, max_new=max_new, task_id=i % cfg.num_tasks)
        )
    # warm-up compile happens on the first dispatches; time a fresh run for
    # steady-state throughput (make_serve_step memoizes, so the second
    # batcher shares the already-compiled step pair)
    batcher.run()
    compile_decode = batcher.decode_dispatches

    batcher2 = ContinuousBatcher(
        model, params, num_slots=num_slots, max_seq=max_seq
    )
    for i, p in enumerate(prompts):
        batcher2.submit(
            Request(uid=i, tokens=p, max_new=max_new, task_id=i % cfg.num_tasks)
        )
    t0 = time.perf_counter()
    done = batcher2.run()
    dt = time.perf_counter() - t0
    total_tokens = sum(len(r.out) for r in done)
    assert compile_decode == batcher2.decode_dispatches
    return {
        "num_slots": num_slots,
        "tokens": total_tokens,
        "tok_per_s": total_tokens / dt,
        "ticks": batcher2.ticks,
        "decode_dispatches": batcher2.decode_dispatches,
        "dispatches_per_tick": batcher2.decode_dispatches / max(batcher2.ticks, 1),
        "prefill_dispatches": batcher2.prefill_dispatches,
        "seconds": dt,
        "outputs": {r.uid: r.out for r in done},
    }


def _cache_nbytes(tree):
    return sum(
        t.size * t.dtype.itemsize for t in jax.tree_util.tree_leaves(tree)
    )


def bench_paged(model, cfg):
    """Paged-vs-dense: >= 4x slots at equal KV memory, token parity.

    Scenario: short requests (16 tokens) against a long-context cache
    (max_seq 128). Dense spends 2 slots x 128 tokens of KV memory; the
    paged pool of the SAME byte size (modulo the null block) serves 8
    slots concurrently because slots only hold the blocks they reserved.
    """
    params = model.init(jax.random.PRNGKey(0))
    max_seq, prompt_len, max_new = 128, 8, 8
    dense_slots, paged_slots = 2, 8
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(0, cfg.vocab_size, (prompt_len,)).astype(np.int32)
        for _ in range(paged_slots)
    ]
    # greedy references from the dense engine, one request at a time
    engine = ServeEngine(model, params, max_seq=max_seq)
    refs = [
        engine.generate(
            {
                "tokens": jnp.asarray(p)[None],
                "task_ids": jnp.full((1,), i % cfg.num_tasks, jnp.int32),
            },
            num_tokens=max_new,
        )[0].tolist()
        for i, p in enumerate(prompts)
    ]
    dense_bytes = _cache_nbytes(model.init_cache(dense_slots, max_seq))

    print(f"\npaged KV cache: dense {dense_slots} slots x {max_seq} seq "
          f"({dense_bytes / 1e3:.0f} kB KV) vs paged pool of equal size")
    report = {
        "dense_slots": dense_slots,
        "dense_kv_bytes": dense_bytes,
        "dense_slots_per_kv_byte": dense_slots / dense_bytes,
    }
    for block_size in (8, 16):
        spec = PagingSpec.sized(
            block_size, max_seq, pool_tokens=dense_slots * max_seq
        )
        paged_bytes = _cache_nbytes(
            model.init_cache(paged_slots, max_seq, spec)
        )
        # equal KV memory: the paged pool may exceed dense only by the
        # reserved null block
        assert paged_bytes * (spec.num_blocks - 1) <= dense_bytes * spec.num_blocks, (
            block_size, paged_bytes, dense_bytes,
        )
        assert paged_slots >= 4 * dense_slots
        batcher = ContinuousBatcher(
            model, params, num_slots=paged_slots, max_seq=max_seq,
            paging=spec,
        )
        for i, p in enumerate(prompts):
            batcher.submit(Request(uid=i, tokens=p, max_new=max_new,
                                   task_id=i % cfg.num_tasks))
        t0 = time.perf_counter()
        done = batcher.run()
        dt = time.perf_counter() - t0
        assert len(done) == paged_slots
        assert batcher.decode_dispatches == batcher.ticks  # one per tick
        outs = {r.uid: r.out for r in done}
        for i, ref in enumerate(refs):
            assert outs[i] == ref, (block_size, i, outs[i], ref)
        assert not any(r.truncated for r in done)
        assert batcher.allocator.free_blocks == spec.num_blocks - 1
        tok = sum(len(r.out) for r in done)
        report[f"block_{block_size}"] = {
            "slots": paged_slots,
            "kv_bytes": paged_bytes,
            "slots_per_kv_byte": paged_slots / paged_bytes,
            "tok_per_s": tok / dt,
        }
        print(f"  block_size={block_size:>2}: {paged_slots} slots "
              f"({paged_slots // dense_slots}x dense) on "
              f"{paged_bytes / 1e3:.0f} kB KV, {tok} tokens in {dt:.1f}s "
              f"({tok / dt:.1f} tok/s), {batcher.decode_dispatches} decode "
              f"dispatches / {batcher.ticks} ticks, parity OK")
    print(f"OK: paged cache serves {paged_slots // dense_slots}x the slots "
          f"at equal KV memory, token-for-token with the dense engine "
          f"(block_size 8 and 16)")
    return report


def bench_prefill(model, params, cfg, num_slots=2, prompt_len=16,
                  chunk=4, max_new=4):
    """Prefill throughput: parallel-within-chunk vs the per-token-scan
    oracle. Asserts (a) both paths cost the SAME number of jitted prefill
    dispatches — ceil(prompt_len / chunk) per admission round — and (b)
    greedy output parity token-for-token; reports prompt tokens/sec for
    each path (the parallel step computes a chunk's C tokens in one
    dispatch instead of C sequential decode-step bodies)."""
    if cfg.uses_moe:
        # expert capacity is computed per DISPATCH (B tokens per scan step
        # vs B*C per parallel slab), so drops differ when capacity binds;
        # pin dropless capacity for the parity assert, same convention as
        # tests/test_serve_prefill.py (params are capacity-independent)
        import dataclasses

        cfg = dataclasses.replace(
            cfg, capacity_factor=float(cfg.num_experts)
        )
        model = TransformerLM(cfg)
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(0, cfg.vocab_size, (prompt_len,)).astype(np.int32)
        for _ in range(num_slots)
    ]
    max_seq = prompt_len + max_new + 4

    def run(mode):
        # first run compiles; the timed second run shares the memoized step
        stats = {}
        for attempt in ("warmup", "timed"):
            batcher = ContinuousBatcher(
                model, params, num_slots=num_slots, max_seq=max_seq,
                prefill_chunk=chunk, prefill_mode=mode,
            )
            for i, p in enumerate(prompts):
                batcher.submit(Request(uid=i, tokens=p, max_new=max_new,
                                       task_id=i % cfg.num_tasks))
            t0 = time.perf_counter()
            batcher._admit()
            stats["prefill_s"] = time.perf_counter() - t0
            batcher._finish_ready()
            done = batcher.run()
            stats["outputs"] = {r.uid: r.out for r in done}
            stats["dispatches"] = batcher.prefill_dispatches
        return stats

    results = {mode: run(mode) for mode in ("scan", "parallel")}
    want_disp = -(-prompt_len // chunk)
    print(f"\nprefill throughput: {num_slots} slots x {prompt_len} prompt "
          f"tokens, chunk={chunk}")
    for mode, r in results.items():
        assert r["dispatches"] == want_disp, (mode, r["dispatches"], want_disp)
        tok = num_slots * prompt_len
        print(f"  {mode:>8}: {tok} prompt tokens in {r['prefill_s']*1e3:.1f} ms "
              f"({tok / r['prefill_s']:.1f} tok/s), "
              f"{r['dispatches']} prefill dispatches")
    assert results["scan"]["outputs"] == results["parallel"]["outputs"], (
        "parallel prefill diverged from the per-token-scan oracle"
    )
    speed = results["scan"]["prefill_s"] / results["parallel"]["prefill_s"]
    print(f"OK: parallel == scan token-for-token at {want_disp} dispatches "
          f"each; parallel prefill ran {speed:.2f}x the scan path")
    tok = num_slots * prompt_len
    return {
        mode: {"prefill_tok_per_s": tok / r["prefill_s"]}
        for mode, r in results.items()
    }


def bench_backends(cfg, params, num_slots=2, prompt_len=6, max_new=6,
                   chunk=3, block_size=8):
    """jnp-vs-pallas attention backend over the SAME requests: greedy token
    parity (dense and block-table paged) plus decode / prefill tok/s per
    backend. The backend flag lives on the (frozen) config, so each backend
    memoizes its own compiled step pair; off-TPU the pallas kernels run in
    interpret mode — the parity assert is the point there, the tok/s split
    only becomes meaningful on TPU."""
    if cfg.uses_moe:
        # dropless capacity so the engine-vs-batcher dispatch shapes can't
        # change expert drops (same convention as bench_prefill)
        cfg = dataclasses.replace(cfg, capacity_factor=float(cfg.num_experts))
    max_seq = prompt_len + max_new + 4
    spec = PagingSpec.sized(
        block_size, max_seq, pool_tokens=num_slots * max_seq
    )
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(0, cfg.vocab_size, (prompt_len,)).astype(np.int32)
        for _ in range(num_slots)
    ]

    def run(backend, paging):
        model = TransformerLM(dataclasses.replace(cfg, attn_backend=backend))
        stats = {}
        for attempt in ("warmup", "timed"):
            batcher = ContinuousBatcher(
                model, params, num_slots=num_slots, max_seq=max_seq,
                prefill_chunk=chunk, paging=paging,
            )
            for i, p in enumerate(prompts):
                batcher.submit(Request(uid=i, tokens=p, max_new=max_new,
                                       task_id=i % cfg.num_tasks))
            t0 = time.perf_counter()
            batcher._admit()  # all slots admitted in one chunked round
            stats["prefill_s"] = time.perf_counter() - t0
            batcher._finish_ready()
            t0 = time.perf_counter()
            done = batcher.run()
            stats["decode_s"] = time.perf_counter() - t0
            stats["outputs"] = {r.uid: r.out for r in done}
        stats["prefill_tok_per_s"] = num_slots * prompt_len / stats["prefill_s"]
        # prefill emits each request's first token; the rest are decode ticks
        stats["decode_tok_per_s"] = (
            num_slots * (max_new - 1) / stats["decode_s"]
        )
        return stats

    print(f"\nattention backends: jnp vs pallas, {num_slots} slots x "
          f"{prompt_len} prompt + {max_new} new, dense + paged "
          f"(block_size {block_size})")
    report = {}
    for backend in ("jnp", "pallas"):
        dense = run(backend, None)
        paged = run(backend, spec)
        report[backend] = {
            "decode_tok_per_s": dense["decode_tok_per_s"],
            "prefill_tok_per_s": dense["prefill_tok_per_s"],
            "paged_decode_tok_per_s": paged["decode_tok_per_s"],
            "paged_prefill_tok_per_s": paged["prefill_tok_per_s"],
        }
        print(f"  {backend:>6}: decode {dense['decode_tok_per_s']:>8.1f} tok/s "
              f"(paged {paged['decode_tok_per_s']:.1f}), "
              f"prefill {dense['prefill_tok_per_s']:>8.1f} tok/s "
              f"(paged {paged['prefill_tok_per_s']:.1f})")
        report[backend]["_outputs"] = {
            "dense": dense["outputs"], "paged": paged["outputs"],
        }
    # token parity: pallas == jnp, dense and paged
    for layout in ("dense", "paged"):
        assert (
            report["jnp"]["_outputs"][layout]
            == report["pallas"]["_outputs"][layout]
        ), f"pallas backend diverged from jnp ({layout})"
    assert report["jnp"]["_outputs"]["dense"] == report["jnp"]["_outputs"]["paged"]
    for backend in report:
        del report[backend]["_outputs"]
    print("OK: pallas backend == jnp backend token-for-token "
          "(dense and paged)")
    return report


def _pct(xs, q):
    return float(np.percentile(np.asarray(xs, np.float64), q))


def bench_latency(model, params, cfg, num_slots=2, max_new=6, seed=0):
    """Poisson-arrival tail latency: FIFO-unchunked vs SJF + chunk budget.

    A virtual-clock discrete-event trace: requests arrive at pre-drawn
    exponential interarrival times (rate calibrated to ~1x the measured
    service rate, so queues actually form), the clock advances ONLY by the
    measured wall time of each ``step()``, and every token is timestamped
    when its dispatch completes. Per-request time-to-first-token (arrival
    -> first token) and inter-token latency are reduced to p50/p99.

    The head-of-line scenario the scheduler exists for: one long prompt in
    every four requests. Unchunked FIFO prefills a long prompt as one
    multi-dispatch lump inside a single step — queued shorts AND the other
    slot's decode both stall for the whole lump. SJF + chunk budget admits
    shorts first and bounds per-tick prefill work, so the p99 TTFT must
    drop while total throughput stays comparable (same total work, same
    slab shapes per dispatch)."""
    max_seq = 32
    long_len, short_len, chunk = 16, 3, 4
    n_req = 20
    rng = np.random.default_rng(seed)
    lens = [long_len if i % 4 == 0 else short_len for i in range(n_req)]
    prompts = [
        rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32) for n in lens
    ]

    def mk(policy, budget):
        return ContinuousBatcher(
            model, params, num_slots=num_slots, max_seq=max_seq,
            prefill_chunk=chunk, policy=policy, chunk_budget=budget,
        )

    # warmup: compiles both step configurations and measures the mean tick
    # wall time that calibrates the arrival rate
    step_s = None
    for policy, budget in (("fifo", None), ("sjf", 2 * short_len)):
        b = mk(policy, budget)
        for i, p in enumerate(prompts):
            b.submit(Request(uid=i, tokens=p, max_new=max_new))
        t0 = time.perf_counter()
        b.run()
        if step_s is None:
            step_s = (time.perf_counter() - t0) / b.ticks
    # offered load ~ service rate: each request needs ~max_new ticks of one
    # of num_slots slots
    mean_gap = step_s * max_new / num_slots
    arrivals = np.cumsum(rng.exponential(mean_gap, n_req))

    def trace(policy, budget):
        b = mk(policy, budget)
        reqs = [
            Request(uid=i, tokens=p, max_new=max_new)
            for i, p in enumerate(prompts)
        ]
        now, next_i = 0.0, 0
        tok_t = [[] for _ in range(n_req)]
        while next_i < n_req or b.queue or any(
            r is not None for r in b.active
        ):
            while next_i < n_req and arrivals[next_i] <= now:
                b.submit(reqs[next_i])
                next_i += 1
            if not b.queue and not any(r is not None for r in b.active):
                now = float(arrivals[next_i])  # idle: jump to next arrival
                continue
            t0 = time.perf_counter()
            b.step()
            now += time.perf_counter() - t0
            for i, r in enumerate(reqs):
                tok_t[i] += [now] * (len(r.out) - len(tok_t[i]))
        assert all(r.done for r in reqs)
        ttft = [tok_t[i][0] - arrivals[i] for i in range(n_req)]
        itl = [b - a for ts in tok_t for a, b in zip(ts, ts[1:]) if b > a]
        total = sum(len(ts) for ts in tok_t)
        return {
            "ttft_p50_s": _pct(ttft, 50), "ttft_p99_s": _pct(ttft, 99),
            "itl_p50_s": _pct(itl, 50), "itl_p99_s": _pct(itl, 99),
            "tok_per_s": total / now, "makespan_s": now,
        }

    fifo = trace("fifo", None)
    chunked = trace("sjf", 2 * short_len)
    print(f"\nPoisson-arrival latency: {n_req} requests "
          f"(1 in 4 prompts {long_len} tokens, rest {short_len}), "
          f"{num_slots} slots, mean interarrival {mean_gap * 1e3:.1f} ms")
    for name, r in (("fifo unchunked", fifo), ("sjf chunked", chunked)):
        print(f"  {name:>15}: TTFT p50 {r['ttft_p50_s']*1e3:7.1f} ms  "
              f"p99 {r['ttft_p99_s']*1e3:7.1f} ms | ITL p50 "
              f"{r['itl_p50_s']*1e3:6.1f} ms  p99 {r['itl_p99_s']*1e3:6.1f} ms"
              f" | {r['tok_per_s']:.1f} tok/s")
    ttft_ratio = chunked["ttft_p99_s"] / fifo["ttft_p99_s"]
    thpt_ratio = chunked["tok_per_s"] / fifo["tok_per_s"]
    # the structural claim: bounding per-tick prefill work cuts the TTFT
    # tail; total throughput stays comparable (identical total token work,
    # identical per-dispatch slab shapes — only lump sizes differ)
    assert chunked["ttft_p99_s"] <= fifo["ttft_p99_s"], (
        f"chunked interleaving did not improve p99 TTFT: "
        f"{chunked['ttft_p99_s']:.4f}s vs {fifo['ttft_p99_s']:.4f}s"
    )
    assert thpt_ratio >= 0.5, (
        f"chunked throughput collapsed: {thpt_ratio:.2f}x of fifo"
    )
    print(f"OK: sjf+chunked p99 TTFT = {ttft_ratio:.2f}x fifo at "
          f"{thpt_ratio:.2f}x throughput")
    return {
        "fifo_unchunked": fifo,
        "sjf_chunked": chunked,
        "ttft_p99_ratio": ttft_ratio,
        "tok_per_s_ratio": thpt_ratio,
    }


def bench_multitask(attn_backend="jnp", num_slots=4, prompt_len=6,
                    max_new=8):
    """Graph-mixed per-task adapter serving over a mixed-task batch.

    Three runs of the SAME requests on the multitask_lm smoke arch (one
    task id per slot, round-robin over the task graph):

      * baseline  — no adapter store attached,
      * zero store — a TaskAdapterStore holding all-zero deltas; must be
        token-for-token identical to the baseline (zero low-rank factors
        add exact +0.0, so attaching the store costs no correctness),
      * mixed     — randomized per-task deltas, graph-mixed via the bsr
        weighting (one fused kernel call per refresh), with the online
        delayed-update loop live (the store re-mixes after every finished
        request mid-run).

    Asserts decode dispatches stay O(1) per tick in ALL three runs — the
    multi-LoRA gather rides inside the one batched decode dispatch, task
    ids are data, not trace constants — and that the mixed-task run keeps
    >= 0.15x the no-adapter throughput (per-dispatch overhead dominates
    the CPU smoke regime; the bound catches accidental retrace-per-tick
    or per-task python loops, not kernel arithmetic)."""
    from repro.core import band_graph
    from repro.serve import TaskAdapterStore

    cfg = get("multitask_lm", smoke=True)
    if attn_backend != "jnp":
        cfg = dataclasses.replace(cfg, attn_backend=attn_backend)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    max_seq = prompt_len + max_new + 4
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(0, cfg.vocab_size, (prompt_len,)).astype(np.int32)
        for _ in range(num_slots)
    ]
    task_ids = [i % cfg.num_tasks for i in range(num_slots)]

    def run(adapters):
        stats = {}
        for attempt in ("warmup", "timed"):
            batcher = ContinuousBatcher(
                model, params, num_slots=num_slots, max_seq=max_seq,
                adapters=adapters,
            )
            for i, p in enumerate(prompts):
                batcher.submit(Request(uid=i, tokens=p, max_new=max_new,
                                       task_id=task_ids[i]))
            t0 = time.perf_counter()
            done = batcher.run()
            stats["seconds"] = time.perf_counter() - t0
            stats["outputs"] = {r.uid: r.out for r in done}
            stats["ticks"] = batcher.ticks
            stats["decode_dispatches"] = batcher.decode_dispatches
        stats["tok_per_s"] = (
            sum(len(o) for o in stats["outputs"].values()) / stats["seconds"]
        )
        return stats

    graph = band_graph(cfg.num_tasks, 2)
    zero_store = TaskAdapterStore(model, graph, mixing="bsr")
    mixed_store = TaskAdapterStore(model, graph, mixing="bsr", lr=0.01)
    mixed_store.randomize(scale=0.5)

    print(f"\nmultitask adapter serving: multitask_lm (smoke), {num_slots} "
          f"slots over {cfg.num_tasks} tasks (rank {cfg.adapter_rank} "
          f"adapters, bsr graph mixing), attn_backend={cfg.attn_backend}")
    baseline = run(None)
    zero = run(zero_store)
    mixed = run(mixed_store)
    for name, r in (("no adapters", baseline), ("zero store", zero),
                    ("mixed tasks", mixed)):
        assert r["decode_dispatches"] == r["ticks"], (name, r)
        print(f"  {name:>12}: {r['tok_per_s']:>8.1f} tok/s, "
              f"{r['decode_dispatches']} decode dispatches / "
              f"{r['ticks']} ticks")
    assert zero["outputs"] == baseline["outputs"], (
        "a zero adapter store changed served tokens"
    )
    assert mixed["outputs"] != baseline["outputs"], (
        "randomized per-task adapters did not change served tokens"
    )
    assert mixed_store.updates > 0, "online update loop never ran"
    ratio = mixed["tok_per_s"] / baseline["tok_per_s"]
    assert ratio >= 0.15, (
        f"multitask serving overhead collapsed throughput: {ratio:.2f}x"
    )
    print(f"OK: zero store == no-adapter baseline token-for-token; mixed "
          f"per-task adapters at {ratio:.2f}x baseline tok/s, O(1) "
          f"dispatches/tick, {mixed_store.updates} online store updates "
          f"mid-run")
    return {
        "num_tasks": cfg.num_tasks,
        "adapter_rank": cfg.adapter_rank,
        "baseline_tok_per_s": baseline["tok_per_s"],
        "zero_store_tok_per_s": zero["tok_per_s"],
        "mixed_tok_per_s": mixed["tok_per_s"],
        "overhead_ratio": ratio,
        "store_updates": mixed_store.updates,
    }


def bench_prefix_cache(cfg, params, num_slots=8, shared_len=100,
                       suffix_len=4, max_new=4, block_size=8, chunk=8):
    """Prefix-shared copy-on-write KV blocks: >= 2x prefill tok/s and
    >= 2x slots-per-KV-byte on a shared-system-prompt workload, exact
    greedy parity with the no-sharing baseline under BOTH backends.

    Workload: a warmer request registers its (shared_len + suffix_len)
    prompt in the radix cache, then num_slots requests arrive sharing the
    same shared_len-token system prompt with distinct suffixes.
    ``shared_len`` is deliberately NOT block-aligned, so every request
    copy-on-writes the partially shared tail block (cow_copies ==
    num_slots) — the benchmark exercises the whole admission path, not
    just whole-block aliasing.

    The two >= 2x claims are measured head-to-head at equal service:

      * prefill tok/s — prompt tokens SERVED per second of admission
        (``_admit`` wall time, which includes the trie walk and the COW
        dispatches). The cache serves shared_len of every prompt from
        registered blocks, so only the suffix computes.
      * slots_per_kv_byte — the no-sharing pool must hold
        num_slots x blocks_per_request blocks for the same 8 concurrent
        slots; the sharing pool holds one copy of the shared chain plus
        the per-request fresh tail, a > 2x smaller block pool for the
        SAME concurrent slot count.
    """
    if cfg.uses_moe:
        # dropless capacity: dispatch shapes must not change expert drops
        cfg = dataclasses.replace(cfg, capacity_factor=float(cfg.num_experts))
    prompt_len = shared_len + suffix_len
    per_req = -(-(prompt_len + max_new) // block_size)
    max_seq = per_req * block_size
    full = shared_len // block_size  # whole blocks of the shared prefix
    fresh = per_req - full  # per-request: COW'd tail + private blocks
    # baseline pool: every slot owns its full chain, nothing shared
    base_spec = PagingSpec(block_size, 1 + num_slots * per_req, per_req)
    # sharing pool: the warmer's registered chain + per-request fresh tail
    pref_spec = PagingSpec(
        block_size, 1 + per_req + num_slots * fresh, per_req
    )
    rng = np.random.default_rng(0)
    shared = rng.integers(0, cfg.vocab_size, (shared_len,)).astype(np.int32)
    warmer = np.concatenate(
        [shared, rng.integers(0, cfg.vocab_size, (suffix_len,)).astype(np.int32)]
    )
    prompts = [
        np.concatenate([
            shared,
            rng.integers(0, cfg.vocab_size, (suffix_len,)).astype(np.int32),
        ])
        for _ in range(num_slots)
    ]

    def run(backend, spec, prefix):
        model = TransformerLM(dataclasses.replace(cfg, attn_backend=backend))
        stats = {}
        for attempt in ("warmup", "timed", "timed"):
            b = ContinuousBatcher(
                model, params, num_slots=num_slots, max_seq=max_seq,
                prefill_chunk=chunk, paging=spec, prefix_cache=prefix,
            )
            if prefix:
                b.submit(Request(uid=999, tokens=warmer, max_new=max_new,
                                 task_id=0))
                warm_done = b.run()
                assert len(warm_done) == 1 and not warm_done[0].truncated
            for i, p in enumerate(prompts):
                b.submit(Request(uid=i, tokens=p, max_new=max_new,
                                 task_id=0))
            t0 = time.perf_counter()
            b._admit()  # all slots admitted in one round
            dt = time.perf_counter() - t0
            stats["prefill_s"] = min(stats.get("prefill_s", dt), dt)
            b._finish_ready()
            # run() reports every request finished on this batcher — drop
            # the warmer so both configs compare the same 8 requests
            done = [r for r in b.run() if r.uid != 999]
            assert len(done) == num_slots
            assert not any(r.truncated for r in done)
            stats["outputs"] = {r.uid: r.out for r in done}
            if prefix:
                # every request COW'd the partially shared tail block and
                # served the whole shared prefix from the cache
                assert b.cow_copies == num_slots, b.cow_copies
                assert b.prefix.hit_tokens == num_slots * shared_len, (
                    b.prefix.hit_tokens
                )
                stats["hit_ratio"] = b.prefix.hit_ratio
                stats["cow_copies"] = b.cow_copies
                stats["prefill_tokens"] = b.prefill_tokens
        # prompt tokens SERVED per second of admission wall time
        stats["prefill_tok_per_s"] = (
            num_slots * prompt_len / stats["prefill_s"]
        )
        return stats

    model = TransformerLM(cfg)
    base_bytes = _cache_nbytes(model.init_cache(num_slots, max_seq, base_spec))
    pref_bytes = _cache_nbytes(model.init_cache(num_slots, max_seq, pref_spec))
    bytes_ratio = base_bytes / pref_bytes
    print(f"\nprefix cache: {num_slots} slots sharing a {shared_len}-token "
          f"prefix (+{suffix_len} suffix, {max_new} new, block_size "
          f"{block_size}); no-sharing pool {base_spec.num_blocks} blocks "
          f"({base_bytes / 1e3:.0f} kB) vs sharing pool "
          f"{pref_spec.num_blocks} blocks ({pref_bytes / 1e3:.0f} kB)")
    report = {
        "num_slots": num_slots,
        "shared_len": shared_len,
        "suffix_len": suffix_len,
        "max_new": max_new,
        "block_size": block_size,
        "baseline_kv_bytes": base_bytes,
        "prefix_kv_bytes": pref_bytes,
        "baseline_slots_per_kv_byte": num_slots / base_bytes,
        "prefix_slots_per_kv_byte": num_slots / pref_bytes,
        "slots_per_kv_byte_ratio": bytes_ratio,
    }
    for backend in ("jnp", "pallas"):
        base = run(backend, base_spec, False)
        pref = run(backend, pref_spec, True)
        speedup = pref["prefill_tok_per_s"] / base["prefill_tok_per_s"]
        assert pref["outputs"] == base["outputs"], (
            f"prefix sharing diverged from the no-sharing baseline "
            f"({backend})"
        )
        report[backend] = {
            "baseline_prefill_tok_per_s": base["prefill_tok_per_s"],
            "prefix_prefill_tok_per_s": pref["prefill_tok_per_s"],
            "prefill_speedup": speedup,
            "hit_ratio": pref["hit_ratio"],
            "cow_copies": pref["cow_copies"],
            "prefill_tokens": pref["prefill_tokens"],
            "_outputs": pref["outputs"],
        }
        print(f"  {backend:>6}: prefill {base['prefill_tok_per_s']:>8.1f} "
              f"-> {pref['prefill_tok_per_s']:>8.1f} tok/s "
              f"({speedup:.1f}x), hit ratio {pref['hit_ratio']:.2f}, "
              f"{pref['cow_copies']} COW copies, parity OK")
    assert report["jnp"]["_outputs"] == report["pallas"]["_outputs"], (
        "pallas backend diverged from jnp under prefix sharing"
    )
    for backend in ("jnp", "pallas"):
        del report[backend]["_outputs"]
        assert report[backend]["prefill_speedup"] >= 2.0, (
            f"prefix cache prefill speedup below 2x under {backend}: "
            f"{report[backend]['prefill_speedup']:.2f}x"
        )
    assert bytes_ratio >= 2.0, (
        f"prefix pool not 2x smaller per slot: {bytes_ratio:.2f}x"
    )
    print(f"OK: {report['jnp']['prefill_speedup']:.1f}x (jnp) / "
          f"{report['pallas']['prefill_speedup']:.1f}x (pallas) prefill "
          f"tok/s and {bytes_ratio:.1f}x slots-per-KV-byte at exact greedy "
          f"parity, both backends")
    return report


def bench_degradation(model, params, cfg, block_size=8):
    """Graceful degradation under block pressure: preemptive swap-out vs
    refusal-only admission.

    A deterministic tick-level trace (no wall-clock in the metrics, so the
    numbers are stable across machines): two long low-urgency hogs
    (priority 10, 16 new tokens) fill a pool sized so that NO short fits
    while both run; four high-urgency shorts (priority 0) then arrive at
    once. Refusal-only admission makes the shorts wait for a hog to
    drain; ``preempt=True`` swaps a hog's blocks to host (one donated
    gather), serves the shorts, and restores the hog through one donated
    scatter.

    Asserts the contract, not the speed: >= 1 swap-out fired, every
    restore matched its swap, BOTH modes serve every request
    token-for-token identically (the snapshot round-trip is exact), the
    shorts' p99 time-to-first-token in TICKS strictly improves, and the
    makespan inflation stays bounded (< 2x — preemption costs two extra
    dispatches per victim, not a re-prefill)."""
    max_seq = 32
    hog_prompt, hog_new = 8, 16
    short_prompt, short_new = 6, 6
    n_hogs, n_shorts = 2, 4
    num_slots = 4
    # pool = exactly the two hogs' chains: blocks_for(8+16)=3 each
    per_hog = -(-(hog_prompt + hog_new) // block_size)
    spec = PagingSpec.sized(
        block_size, max_seq, pool_tokens=n_hogs * per_hog * block_size
    )
    rng = np.random.default_rng(0)
    hogs = [
        rng.integers(0, cfg.vocab_size, (hog_prompt,)).astype(np.int32)
        for _ in range(n_hogs)
    ]
    shorts = [
        rng.integers(0, cfg.vocab_size, (short_prompt,)).astype(np.int32)
        for _ in range(n_shorts)
    ]

    def run(preempt):
        stats = {}
        for attempt in ("warmup", "timed"):
            b = ContinuousBatcher(
                model, params, num_slots=num_slots, max_seq=max_seq,
                prefill_chunk=8, paging=spec, policy="priority",
                preempt=preempt,
            )
            reqs = [
                Request(uid=i, tokens=p, max_new=hog_new, priority=10)
                for i, p in enumerate(hogs)
            ]
            for r in reqs:
                b.submit(r)
            b.step()
            b.step()  # hogs are decoding and own the whole pool
            short_reqs = [
                Request(uid=100 + i, tokens=p, max_new=short_new, priority=0)
                for i, p in enumerate(shorts)
            ]
            for r in short_reqs:
                b.submit(r)
            reqs += short_reqs
            steps, first = 2, {}
            t0 = time.perf_counter()
            while b.queue or any(r is not None for r in b.active):
                b.step()
                steps += 1
                for r in short_reqs:
                    if r.out and r.uid not in first:
                        first[r.uid] = steps - 2  # ticks since arrival
            dt = time.perf_counter() - t0
            assert all(r.done for r in reqs)
            ttft = [first[r.uid] for r in short_reqs]
            total = sum(len(r.out) for r in reqs)
            stats = {
                "ttft_ticks_p50": _pct(ttft, 50),
                "ttft_ticks_p99": _pct(ttft, 99),
                "makespan_ticks": steps,
                "tok_per_s": total / dt,
                "swap_outs": b.swap_outs,
                "swap_ins": b.swap_ins,
                "outputs": {r.uid: r.out for r in reqs},
            }
        return stats

    print(f"\ngraceful degradation: {n_hogs} hogs (priority 10, "
          f"{hog_new} new) fill a {spec.num_blocks - 1}-block pool; "
          f"{n_shorts} shorts (priority 0) arrive under full pressure")
    refusal = run(False)
    preempt = run(True)
    for name, r in (("refusal-only", refusal), ("preempt+swap", preempt)):
        print(f"  {name:>12}: shorts TTFT p50 {r['ttft_ticks_p50']:5.1f} "
              f"p99 {r['ttft_ticks_p99']:5.1f} ticks | makespan "
              f"{r['makespan_ticks']} ticks | {r['tok_per_s']:.1f} tok/s | "
              f"{r['swap_outs']} swap-outs")
    assert refusal["swap_outs"] == 0
    assert preempt["swap_outs"] >= 1, "block pressure never preempted"
    assert preempt["swap_ins"] == preempt["swap_outs"], (
        "a swapped-out victim was never restored"
    )
    # the snapshot/restore round-trip is exact: BOTH modes (and therefore
    # the roomy-pool serve) emit identical tokens for every request
    assert preempt["outputs"] == refusal["outputs"], (
        "preemptive swap-out changed served tokens"
    )
    assert preempt["ttft_ticks_p99"] < refusal["ttft_ticks_p99"], (
        f"preemption did not improve shorts' p99 TTFT: "
        f"{preempt['ttft_ticks_p99']} vs {refusal['ttft_ticks_p99']} ticks"
    )
    makespan_ratio = preempt["makespan_ticks"] / refusal["makespan_ticks"]
    assert makespan_ratio < 2.0, (
        f"preemption inflated the makespan {makespan_ratio:.2f}x"
    )
    ttft_ratio = preempt["ttft_ticks_p99"] / refusal["ttft_ticks_p99"]
    print(f"OK: preemption cut shorts' p99 TTFT to {ttft_ratio:.2f}x "
          f"refusal-only at {makespan_ratio:.2f}x makespan, "
          f"{preempt['swap_outs']} swap-outs each restored exactly, "
          f"token parity both modes")
    report = {
        "pool_blocks": spec.num_blocks - 1,
        "hogs": n_hogs, "shorts": n_shorts,
        "ttft_p99_ratio": ttft_ratio,
        "makespan_ratio": makespan_ratio,
    }
    for name, r in (("refusal", refusal), ("preempt", preempt)):
        report[name] = {
            k: r[k] for k in ("ttft_ticks_p50", "ttft_ticks_p99",
                              "makespan_ticks", "tok_per_s", "swap_outs")
        }
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo_1b")
    ap.add_argument("--slots", type=int, nargs="+", default=[1, 2, 4, 8])
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--skip-paged", action="store_true",
                    help="skip the paged-vs-dense memory/parity section")
    ap.add_argument("--skip-prefill", action="store_true",
                    help="skip the parallel-vs-scan prefill section")
    ap.add_argument("--skip-backends", action="store_true",
                    help="skip the jnp-vs-pallas attention-backend section")
    ap.add_argument("--skip-latency", action="store_true",
                    help="skip the Poisson-arrival tail-latency section")
    ap.add_argument("--skip-multitask", action="store_true",
                    help="skip the graph-mixed adapter serving section")
    ap.add_argument("--skip-prefix", action="store_true",
                    help="skip the prefix-cache / copy-on-write section")
    ap.add_argument("--skip-degradation", action="store_true",
                    help="skip the preemptive swap-out degradation section")
    ap.add_argument("--attn-backend", default="jnp",
                    choices=("jnp", "pallas"),
                    help="attention backend for ALL sections (the backends "
                    "section always compares both)")
    ap.add_argument("--json", nargs="?", const="BENCH_serve.json",
                    default=None, metavar="PATH",
                    help="write the perf report to PATH "
                    "(default BENCH_serve.json) for trajectory diffing")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get(args.arch, smoke=True)
    if args.attn_backend != "jnp":
        cfg = dataclasses.replace(cfg, attn_backend=args.attn_backend)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    max_seq = args.prompt_len + args.max_new + 8

    print(f"arch={args.arch} (smoke) backend={jax.default_backend()} "
          f"attn_backend={cfg.attn_backend} "
          f"prompt={args.prompt_len} max_new={args.max_new}")
    print(f"{'slots':>6} {'tok/s':>10} {'ticks':>6} {'decode_disp':>12} "
          f"{'disp/tick':>10} {'prefill_disp':>13}")
    rows = []
    for n in args.slots:
        r = bench_slots(model, params, cfg, n, args.prompt_len,
                        args.max_new, max_seq)
        rows.append(r)
        print(f"{r['num_slots']:>6} {r['tok_per_s']:>10.1f} {r['ticks']:>6} "
              f"{r['decode_dispatches']:>12} {r['dispatches_per_tick']:>10.2f} "
              f"{r['prefill_dispatches']:>13}")

    # ---- property 1: decode dispatches are O(1) in slot count ----
    for r in rows:
        assert r["dispatches_per_tick"] == 1.0, r
    base_disp = rows[0]["decode_dispatches"]
    for r in rows[1:]:
        assert r["decode_dispatches"] == base_disp, (
            f"decode dispatches grew with slot count: {rows}"
        )
    print(f"OK: decode dispatches constant at {base_disp} across "
          f"slot counts {args.slots}")

    # ---- property 2: token-for-token greedy parity with ServeEngine ----
    rng = np.random.default_rng(0)
    check = rows[-1]
    prompts = [
        rng.integers(0, cfg.vocab_size, (args.prompt_len,)).astype(np.int32)
        for _ in range(check["num_slots"])
    ]
    engine = ServeEngine(model, params, max_seq=max_seq)
    for uid, p in enumerate(prompts):
        ref = engine.generate(
            {
                "tokens": jnp.asarray(p)[None],
                "task_ids": jnp.full((1,), uid % cfg.num_tasks, jnp.int32),
            },
            num_tokens=args.max_new,
        )[0].tolist()
        assert check["outputs"][uid] == ref, (uid, check["outputs"][uid], ref)
    print(f"OK: batcher == ServeEngine.generate token-for-token "
          f"({check['num_slots']} slots x {args.max_new} tokens, greedy)")

    # ---- throughput scaling report ----
    per_slot = [r["tok_per_s"] / r["num_slots"] for r in rows]
    scale = rows[-1]["tok_per_s"] / rows[0]["tok_per_s"]
    print(f"throughput scaling {rows[0]['num_slots']}->"
          f"{rows[-1]['num_slots']} slots: {scale:.2f}x "
          f"(per-slot tok/s: {', '.join(f'{p:.1f}' for p in per_slot)})")

    report = {
        "arch": args.arch,
        "platform": jax.default_backend(),
        "attn_backend": cfg.attn_backend,
        "prompt_len": args.prompt_len,
        "max_new": args.max_new,
        "decode": [
            {k: r[k] for k in ("num_slots", "tokens", "tok_per_s", "ticks",
                               "decode_dispatches", "prefill_dispatches")}
            for r in rows
        ],
    }

    # ---- property 3: paged cache = more slots at equal KV memory ----
    if not args.skip_paged:
        report["paged"] = bench_paged(model, cfg)

    # ---- property 4: parallel prefill == scan oracle, same dispatches ----
    if not args.skip_prefill:
        report["prefill"] = bench_prefill(model, params, cfg)

    # ---- property 5: pallas backend == jnp backend, with tok/s split ----
    if not args.skip_backends:
        report["backends"] = bench_backends(cfg, params)

    # ---- property 6: chunked interleaving cuts the TTFT tail ----
    if not args.skip_latency:
        report["latency"] = bench_latency(model, params, cfg)

    # ---- property 7: graph-mixed per-task adapters serve at O(1) ----
    if not args.skip_multitask:
        report["multitask"] = bench_multitask(
            attn_backend=cfg.attn_backend
        )

    # ---- property 8: prefix-shared COW blocks: 2x prefill + 2x memory ----
    if not args.skip_prefix:
        report["prefix_cache"] = bench_prefix_cache(cfg, params)

    # ---- property 9: graceful degradation under block pressure ----
    if not args.skip_degradation:
        report["degradation"] = bench_degradation(model, params, cfg)

    if args.json:
        # append to the perf trajectory: BENCH_serve.json holds
        # {"history": [entry, ...]} ordered oldest-first, one timestamped
        # entry per run. A pre-history single-object file migrates in
        # place as the first entry.
        history = []
        if os.path.exists(args.json):
            with open(args.json) as f:
                prev = json.load(f)
            history = (
                prev["history"]
                if isinstance(prev, dict) and "history" in prev
                else [prev]
            )
        report["timestamp"] = datetime.now(timezone.utc).isoformat(
            timespec="seconds"
        )
        history.append(report)
        with open(args.json, "w") as f:
            json.dump({"history": history}, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"\nwrote perf report to {args.json} "
              f"({len(history)} history entries)")


if __name__ == "__main__":
    main()
