"""One run of one cell: set up, measure a window, check it, print one line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (``setup_s``, from process start to the window's opening): the
weights and any adapter store are made on the device from the seed; the
batcher is built; one request warms up the cell's only step shape (and one
store update its mixing path); a closed loop primes its clients until every
bound slot is decoding. The window then drives ``ContinuousBatcher.submit``
/ ``.step`` for ``--seconds``: an open loop submits each request when it is
due and times it from then; a closed loop sends a client's next request as
soon as its last one finished. After the window, an open loop keeps stepping
(sending nothing new) until every request due in the window has its first
token, at most ``DRAIN_S``.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from the profiler's trace of the window and from
the harness's own record of every step. Both check the served tokens
against the plain reference (``bench.lib.check``) and print each number
compared beside its limit.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import shutil
import sys
import time
from contextlib import nullcontext

import jax
import numpy as np

from bench.lib import check, costs, gen, program, spec, trace, weights
from bench.lib.peaks import peaks

# seconds an open loop may keep stepping after the window for the first
# tokens of requests due in it
DRAIN_S = 60.0


@dataclasses.dataclass
class Obs:
    """What the harness saw of one request."""
    req: object  # the program's Request
    due: float | None  # absolute perf_counter time it was due
    submit: float | None = None
    bound: float | None = None  # start of the first step that held a slot
    tokens: list = dataclasses.field(default_factory=list)  # (t, step, ver)
    segments: list = dataclasses.field(default_factory=list)  # prompt chunks
    done_prompt: int = 0


@dataclasses.dataclass
class Tick:
    """One step of the batcher, as the harness saw it."""
    dispatched: int  # jitted step dispatches in this step (0 or 1)
    live: int  # slots bound after the step
    blocks: int  # KV blocks in use after the step
    chunks: list  # (start, n) positions computed per slot
    emitted: int  # tokens emitted
    updates: int  # store updates made during the step
    # what each of the program's counters (``program.counters``) rose by
    counters: dict = dataclasses.field(default_factory=dict)


class Recorder:
    """Every step the window makes, and every token it emits."""

    def __init__(self):
        self.step = 0
        self.version = 0
        self.obs: dict[int, Obs] = {}
        self.ticks: list[Tick] = []
        self._emitted: list[int] = []

    def on_token(self, req, tok) -> None:
        o = self.obs.get(req.uid)
        if o is not None:
            o.tokens.append((time.perf_counter(), self.step, self.version))
        self._emitted.append(req.uid)

    def run_step(self, sys_, span) -> Tick:
        b, store = sys_.batcher, sys_.store
        self.version = store.updates if store is not None else 0
        dispatches, n_finished = b.ticks, len(b.finished)
        before = program.counters(b)
        self._emitted = []
        t0 = time.perf_counter()
        with span("bench.step"):
            b.step()
        chunks = []
        for _, req in b.slots.live_items():
            o = self.obs.get(req.uid)
            if o is not None and o.bound is None:
                o.bound = t0
        # positions computed: each prompt's newly prefilled run, and one
        # decode position per emitted token but a request's first (which
        # comes out of the step that finished its prompt)
        for req in ([r for _, r in b.slots.live_items()]
                    + b.finished[n_finished:]):
            o = self.obs.get(req.uid)
            if o is not None and req.prompt_done > o.done_prompt:
                chunks.append((o.done_prompt, req.prompt_done - o.done_prompt))
                o.segments.append((o.done_prompt, req.prompt_done,
                                   self.version))
                o.done_prompt = req.prompt_done
        for uid in self._emitted:
            o = self.obs.get(uid)
            if o is not None and len(o.tokens) > 1:
                chunks.append((len(o.req.tokens) + len(o.tokens) - 2, 1))
        tick = Tick(
            b.ticks - dispatches,
            int(b.slots.live().sum()),
            b.allocator.used_blocks,
            chunks, len(self._emitted),
            (store.updates - self.version) if store is not None else 0,
            {k: v - before.get(k, 0)
             for k, v in program.counters(b).items()},
        )
        self.ticks.append(tick)
        self.step += 1
        return tick


def _span(name):
    return jax.profiler.TraceAnnotation(name)


def _no_span(name):
    return nullcontext()


def parse(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def chip_check(chips: int):
    """The devices, or SystemExit when there is no TPU or too few chips."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX reports platform "
                         f"{devices[0].platform!r}; this benchmark never "
                         "runs on another device")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devices)}")
    return devices


def warm_up(sys_) -> None:
    """Compile the one step shape the cell uses, and the store's update."""
    from repro.serve import Request

    b = sys_.batcher
    b.submit(Request(uid=-1, tokens=np.ones(2, np.int32), max_new=2))
    while b.queue or b.slots.any_live():
        b.step()
    if sys_.store is not None:
        sys_.store.update()
    jax.block_until_ready(b.caches)


def submit(sys_, rec: Recorder, p: gen.Planned, due_abs, span) -> None:
    from repro.serve import Request

    req = Request(uid=p.uid, tokens=p.prompt, max_new=p.max_new,
                  task_id=p.task)
    o = Obs(req, due_abs)
    rec.obs[p.uid] = o
    with span("bench.submit"):
        sys_.batcher.submit(req)
    o.submit = time.perf_counter()


def main(argv, t_process: float) -> int:
    args = parse(argv)
    bench = spec.load()
    cell = spec.cell(bench, args.workload)
    cfg = spec.config(bench, cell["config"])
    mix = spec.traffic(cell["traffic"])
    devices = chip_check(cell["chips"])
    peak = peaks(devices[0].device_kind)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    result = run(bench, cell, cfg, mix, args.seed, args.seconds,
                 bool(args.trace), t_process, devices, peak,
                 check.limits(cell["name"]))
    for name, c in result["checks"].items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr,
              flush=True)
    print(json.dumps(result), flush=True)
    return 0


def run(bench, cell, cfg, mix, seed, seconds, tracing, t_process, devices,
        peak, limits, control=False, say=None) -> dict:
    """One run of ``cell``; returns the result line as a dict."""
    dev = devices[0]
    span = _span if tracing else _no_span
    say = say or (lambda msg: print(msg, flush=True))

    # ---------------------------------------------------------- set-up
    tasks = program.num_tasks(cfg)
    w = weights.model_weights(cfg, seed, tasks)
    raw = weights.adapter_raw(cfg, seed) if cfg.get("adapters") else None
    rec = Recorder()
    sys_ = program.build(cfg, w, raw, rec.on_token)
    del raw  # the store holds its own copy; the reference remakes it
    warm_up(sys_)
    b = sys_.batcher
    vocab = cfg["vocab_size"]
    closed = mix["loop"] == "closed"
    if closed:
        rounds = [gen.closed_round(mix, seed, 0, vocab)]
        nxt = [1] * mix["clients"]
        seen_finished = len(b.finished)
        for p in rounds[0]:
            submit(sys_, rec, p, None, span)
        # prime: until every bound slot decodes (requests the pool cannot
        # hold yet wait in the queue, as they would in the window)
        rec.run_step(sys_, _no_span)
        while any(r.prefill_remaining > 0 for _, r in b.slots.live_items()):
            rec.run_step(sys_, _no_span)
    else:
        plan = gen.open_loop(mix, seconds, seed, vocab)
    jax.block_until_ready(b.caches)
    rec.ticks.clear()
    if tracing:
        trace_dir = os.path.join(spec.ROOT, ".bench_trace", str(os.getpid()))
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    t_open = time.perf_counter()
    setup_s = t_open - t_process
    # a closed loop's window works on the primed requests still running
    in_window = ({u for u, o in rec.obs.items() if not o.req.done}
                 if closed else set())

    # ---------------------------------------------------------- window
    late = []
    i = 0
    with span("bench.window"):
        while True:
            now = time.perf_counter()
            # an open loop sends every request due before the close, also
            # those that came due during the window's last step
            while not closed and i < len(plan) and plan[i].due <= now - t_open:
                submit(sys_, rec, plan[i], t_open + plan[i].due, span)
                late.append(rec.obs[plan[i].uid].submit
                            - (t_open + plan[i].due))
                in_window.add(plan[i].uid)
                i += 1
            if now - t_open >= seconds:
                break
            if closed:
                for req in b.finished[seen_finished:]:
                    if req.uid < 0:
                        continue
                    c = req.uid % mix["clients"]
                    while len(rounds) <= nxt[c]:
                        rounds.append(gen.closed_round(mix, seed,
                                                       len(rounds), vocab))
                    p = rounds[nxt[c]][c]
                    nxt[c] += 1
                    submit(sys_, rec, p, time.perf_counter(), span)
                    in_window.add(p.uid)
                seen_finished = len(b.finished)
            if b.queue or b.slots.any_live():
                rec.run_step(sys_, span)
            else:
                # an open loop waits for its next request; a closed loop
                # always has one in flight
                nxt_due = (plan[i].due if not closed and i < len(plan)
                           else seconds)
                with span("bench.wait"):
                    time.sleep(max(0.0, t_open + min(nxt_due, seconds)
                                   - time.perf_counter()))
    t_close = time.perf_counter()
    queued_at_close = len(b.queue)
    window_ticks = list(rec.ticks)
    if tracing:
        jax.profiler.stop_trace()
    # an open loop's requests due in the window all get their first token
    drain_end = t_close + DRAIN_S
    while not closed and time.perf_counter() < drain_end and any(
            not rec.obs[u].tokens for u in in_window):
        if not (b.queue or b.slots.any_live()):
            break
        rec.run_step(sys_, _no_span)
    memory_peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    if late:
        say(f"generator: {len(late)} requests sent, late by mean "
            f"{1e3 * float(np.mean(late)):.3f} ms, max "
            f"{1e3 * float(np.max(late)):.3f} ms")
    say(f"setup_s {setup_s:.3f}; window {t_close - t_open:.3f} s, "
        f"{len(window_ticks)} steps, store updates "
        f"{sys_.store.updates if sys_.store is not None else 0}, "
        f"{queued_at_close} requests queued at the close")

    # ---------------------------------------------------------- results
    finished = [rec.obs[u] for u in sorted(in_window) if rec.obs[u].req.done]
    failed = [u for u in in_window
              if rec.obs[u].req.failed or rec.obs[u].req.timed_out
              or (not closed and not rec.obs[u].tokens)]
    invariants_ok = 1
    try:
        b.check_invariants()
    except RuntimeError as e:
        say(f"check_invariants: {e}")
        invariants_ok = 0
    num_slots, chunk = b.num_slots, b.prefill_chunk
    pool_blocks = sys_.paging.num_blocks - 1
    del sys_, b  # the program's state is freed before the reference runs
    gc.collect()

    verdict = check.run(cfg, limits, seed, finished, w, say, control)
    del w
    checks = dict(verdict.numbers)
    checks["failed_requests"] = {"value": len(failed), "limit": 0}
    checks["invariants_ok"] = {"value": invariants_ok, "limit": 1}
    correct = verdict.ok and not failed and invariants_ok == 1

    result = {
        "correct": bool(correct),
        "attempted": len(in_window),
        "failed": len(failed),
        "metrics": {},
        "device": {
            "platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices), "memory_peak_bytes": memory_peak,
        },
    }
    ctx = Context(
        cfg=cfg, mix=mix, peak=peak, shape=costs.Shape.of(cfg),
        num_slots=num_slots, chunk=chunk, pool_blocks=pool_blocks,
        t_open=t_open, t_close=t_close, ticks=window_ticks,
        obs={u: rec.obs[u] for u in in_window},
        trace=None,
    )
    if tracing:
        t = time.perf_counter()
        ctx.trace = trace.reduce(trace.find(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        say(f"trace: {len(ctx.trace.ops)} device operations, "
            f"{len(ctx.trace.program_spans)} program spans, reduced in "
            f"{time.perf_counter() - t:.3f} s")
        result["device"]["busy_s"] = ctx.trace.busy_s
        result["device"]["window_s"] = ctx.trace.window_s
        for m in spec.per_layer(bench, cell["name"]):
            v = spec.reader(m["name"])(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = ctx.trace.breakdown()
    else:
        values = end_to_end(ctx, setup_s)
        for m in spec.end_to_end(bench, cell["name"]):
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    result["checks"] = checks
    return result


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader may read."""
    cfg: dict
    mix: dict
    peak: dict
    shape: costs.Shape
    num_slots: int
    chunk: int
    pool_blocks: int
    t_open: float
    t_close: float
    ticks: list  # Tick of every step in the window
    obs: dict  # uid -> Obs of every request the window worked on
    trace: object  # trace.Trace of the window (traced runs), else None


def end_to_end(ctx: Context, setup_s: float) -> dict:
    """Every end-to-end metric a cell may report, over all the work of the
    window: time to first token from due time over every request due in
    it; every gap between two tokens both emitted in it; every token
    emitted in it."""
    t0, t1 = ctx.t_open, ctx.t_close
    ttft, gaps, emitted = [], [], 0
    for o in ctx.obs.values():
        if o.due is not None and o.due >= t0 and o.tokens:
            ttft.append(o.tokens[0][0] - o.due)
        times = [t for t, _, _ in o.tokens if t0 <= t <= t1]
        emitted += len(times)
        gaps.extend(np.diff(times).tolist())
    return {
        "setup_s": setup_s,
        "ttft_p90_ms": 1e3 * float(np.percentile(ttft, 90)) if ttft else None,
        "itl_p95_ms": 1e3 * float(np.percentile(gaps, 95)) if gaps else None,
        "itl_mean_ms": 1e3 * float(np.sum(gaps) / len(gaps)) if gaps else None,
        "output_tok_s": emitted / (t1 - t0),
    }
