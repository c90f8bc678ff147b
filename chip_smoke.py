"""Chip smoke: drive the serving path once on a TPU, at published widths.

    python chip_smoke.py            # one chip: phases A and B
    python chip_smoke.py --chips 4  # four chips: the task-per-chip mixing
                                    # collectives only (phase C)

Phase A serves ``olmo_1b`` (bf16, random weights from ``--seed``) through
``ContinuousBatcher``: 8 slots, ``max_seq`` 2048, paged KV (block 16), the
chunked regime (budget 512), 8 greedy requests of 32 new tokens each — first
on the jnp attention backend, then on the Pallas kernels. It checks that
every request finishes cleanly, that ``check_invariants()`` holds at drain,
that the Pallas step really contains compiled kernels (``tpu_custom_call``),
and that the two backends' first-prefill logits agree within
``LOGITS_RTOL`` (relative L2, bf16 on both sides).

Phase B serves ``multitask_lm`` (256 tasks, rank-8 adapters, f32 at
``highest`` matmul precision) with a
``TaskAdapterStore`` over a band task graph: a zero store must give exactly
the tokens of no store, a randomized store must give different tokens for
different tasks, and the store's ``update()`` — one graph-mixing step, run
between ticks — must be a compiled ``graph_mix`` kernel.

Phase C (``--chips 4``, one task per chip) checks the paper's distributed
mixing: ``mix_ring`` on a band graph and ``mix_all_gather`` on a non-band
graph against ``mu.T @ theta`` on one device, with each task's vector as
wide as one ``multitask_lm`` adapter-store row; and ``bol_sharded`` against
single-device ``bol``.

Exits non-zero before any phase when JAX finds no TPU, and at the first
failed check. Seconds printed here are smoke timings, not benchmarks. The
last line of standard output is ``{"ok": true, "device": {...}}`` only when
every check passed.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import get
from repro.core import MultiTaskProblem, SQUARED, TaskGraph, band_graph, bol
from repro.core.distributed import (
    mix_all_gather,
    mix_ring,
    mixing_spec_for_band_graph,
)
from repro.core.runners import bol_sharded
from repro.data.synthetic import generate_clustered_tasks
from repro.kernels.graph_mix import graph_mix_tree
from repro.launch.compile_cache import use_compile_cache
from repro.models import TransformerLM
from repro.serve import ContinuousBatcher, PagingSpec, Request, TaskAdapterStore
from repro.serve.step import make_serve_step

# bf16 jnp vs bf16 Pallas logits after the first prefill chunk:
# ||pallas - jnp||_2 / ||jnp||_2. The jnp path rounds attention scores to
# bf16 before its f32 softmax; the kernel keeps them in f32 — both are
# bf16 approximations of the same f32 math, a few bf16 ulps (2^-8) apart
# per layer.
LOGITS_RTOL = 0.05
# one multitask_lm adapter-store row: 12 layers x {attn, mlp} x (a + b)
# low-rank factors of 1024 x 8
ROW_WIDTH = 12 * 2 * 2 * 1024 * 8


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"CHECK FAILED: {what}")


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def tree_bytes(tree) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(tree))


def init_params(model: TransformerLM, seed: int):
    t0 = time.perf_counter()
    params = jax.block_until_ready(jax.jit(model.init)(jax.random.PRNGKey(seed)))
    return params, time.perf_counter() - t0


def make_prompts(rng, n: int, lo: int, hi: int, vocab: int) -> list:
    lens = rng.integers(lo, hi + 1, n)
    return [rng.integers(0, vocab, int(s)).astype(np.int32) for s in lens]


def first_chunk_args(model, params, prompts, spec, max_seq, chunk,
                     task_ids=None, adapters=None):
    """Arguments of the batcher's prefill step for every slot's first
    ``chunk`` prompt tokens, each slot on its own fresh blocks."""
    b = len(prompts)
    task_ids = np.zeros(b, np.int32) if task_ids is None else task_ids
    per_slot = spec.blocks_for(chunk)
    tables = np.zeros((b, spec.max_blocks_per_slot), np.int32)
    tables[:, :per_slot] = 1 + np.arange(b * per_slot).reshape(b, per_slot)
    return (
        params,
        jnp.asarray(np.stack([p[:chunk] for p in prompts])),
        jnp.asarray(task_ids, jnp.int32),
        model.init_cache(b, max_seq, spec),
        jnp.zeros((b,), jnp.int32),
        jnp.ones((b, chunk), bool),
        jnp.ones((b,), bool),
        {},
        jnp.asarray(tables),
        adapters,
    )


def serve(model, params, prompts, task_ids, *, spec, max_seq, chunk, budget,
          max_new, adapters=None, between_ticks=None):
    """Run one batcher to drain; returns (requests by uid, batcher, secs)."""
    batcher = ContinuousBatcher(
        model, params, num_slots=len(prompts), max_seq=max_seq,
        prefill_chunk=chunk, paging=spec, chunk_budget=budget,
        adapters=adapters,
    )
    for i, (p, t) in enumerate(zip(prompts, task_ids)):
        batcher.submit(Request(uid=i, tokens=p, max_new=max_new, task_id=t))
    t0 = time.perf_counter()
    if between_ticks is not None:
        batcher.step()
        between_ticks()
    done = batcher.run()
    secs = time.perf_counter() - t0
    return {r.uid: r for r in done}, batcher, secs


def check_served(phase, reqs, batcher, n, max_new, vocab) -> int:
    check(len(reqs) == n, f"{phase}: {len(reqs)} of {n} requests finished")
    for r in reqs.values():
        check(r.done and not r.failed and not r.timed_out,
              f"{phase}: request {r.uid} done={r.done} failed={r.failed} "
              f"error={r.error}")
        check(len(r.out) == max_new,
              f"{phase}: request {r.uid} emitted {len(r.out)} tokens")
        check(all(0 <= int(t) < vocab for t in r.out),
              f"{phase}: request {r.uid} emitted a token outside [0, {vocab})")
    inv = batcher.check_invariants()
    check(inv["live_slots"] == 0 and inv["queued"] == 0,
          f"{phase}: batcher not drained: {inv}")
    return sum(len(r.out) for r in reqs.values())


# ---------------------------------------------------------------- phase A
def phase_a(cfg, seed, *, num_slots=8, max_seq=2048, block_size=16,
            chunk=64, budget=512, max_new=32, prompt_lens=(64, 1024)):
    """olmo_1b on both attention backends through ContinuousBatcher."""
    t_phase = time.perf_counter()
    say("A", f"{cfg.name}: layers {cfg.num_layers}, d_model {cfg.d_model}, "
             f"heads {cfg.num_heads}/{cfg.num_kv_heads} x {cfg.head_dim}, "
             f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, bf16")
    base = TransformerLM(cfg, dtype=jnp.bfloat16)
    params, init_s = init_params(base, seed)
    say("A", f"params {tree_bytes(params)} bytes, init {init_s:.2f} s")
    rng = np.random.default_rng(seed)
    prompts = make_prompts(rng, num_slots, *prompt_lens, cfg.vocab_size)
    say("A", f"{num_slots} slots, max_seq {max_seq}, paged block "
             f"{block_size}, chunk {chunk}, budget {budget}, prompt lengths "
             f"{[len(p) for p in prompts]}, {max_new} new tokens each")
    spec = PagingSpec.sized(block_size, max_seq, num_slots * max_seq)
    logits, served = {}, {}
    for backend in ("jnp", "pallas"):
        model = TransformerLM(
            dataclasses.replace(cfg, attn_backend=backend), dtype=jnp.bfloat16
        )
        _, prefill_fn = make_serve_step(model, max_seq, spec)
        args = first_chunk_args(model, params, prompts, spec, max_seq, chunk)
        t0 = time.perf_counter()
        compiled = prefill_fn.lower(*args).compile()
        compile_s = time.perf_counter() - t0
        kernels = "tpu_custom_call" in compiled.as_text()
        last, caches, _ = compiled(*args)
        logits[backend] = np.asarray(last, np.float32)
        del caches, args
        check(np.isfinite(logits[backend]).all(),
              f"A/{backend}: non-finite first-prefill logits")
        if backend == "pallas":
            check(kernels, "A/pallas: no tpu_custom_call in the compiled step")
        reqs, batcher, secs = serve(
            model, params, prompts, [0] * num_slots, spec=spec,
            max_seq=max_seq, chunk=chunk, budget=budget, max_new=max_new,
        )
        tokens = check_served(f"A/{backend}", reqs, batcher, num_slots,
                              max_new, cfg.vocab_size)
        served[backend] = [reqs[i].out for i in range(num_slots)]
        say("A", f"{backend}: step compile {compile_s:.2f} s, "
                 f"tpu_custom_call {kernels}, {tokens} tokens in "
                 f"{batcher.ticks} ticks, serve wall {secs:.2f} s (smoke "
                 f"timing, not a benchmark), peak_bytes_in_use {peak_bytes()}")
        del batcher, reqs
    ref, got = logits["jnp"], logits["pallas"]
    rel = float(np.linalg.norm(got - ref) / np.linalg.norm(ref))
    greedy = float(np.mean(ref.argmax(-1) == got.argmax(-1)))
    same = float(np.mean([a == b for a, b in zip(served["jnp"],
                                                  served["pallas"])]))
    say("A", f"first-prefill logits jnp vs pallas: relative L2 {rel:.3e} "
             f"(limit {LOGITS_RTOL}), max |diff| "
             f"{float(np.abs(got - ref).max()):.3e}, greedy agreement "
             f"{greedy:.3f}; requests with identical served tokens {same:.3f}")
    check(rel <= LOGITS_RTOL,
          f"A: jnp/pallas logits differ by {rel:.3e} > {LOGITS_RTOL}")
    say("A", f"ok, phase wall {time.perf_counter() - t_phase:.2f} s")


# ---------------------------------------------------------------- phase B
def phase_b(cfg, seed, *, num_slots=8, max_seq=2048, block_size=16,
            chunk=64, budget=512, max_new=16, prompt_lens=(64, 512)):
    """multitask_lm with a graph-mixed adapter store through the batcher.

    At float32 reference numerics (``highest`` matmul precision):
    "zero store == no store" is a bitwise claim about two different compiled
    programs. At the default precision a TPU runs f32 matmuls as bf16
    passes, and which operands get rounded depends on the fusion, which the
    adapter ops change: on a v5e the first-prefill logits of the two
    programs differed by 2.5e-2 at the default precision and by 0 at
    ``highest``. With random weights greedy tokens are near-ties, so such
    differences flip served tokens."""
    t_phase = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        say("B", f"{cfg.name}: layers {cfg.num_layers}, d_model {cfg.d_model}, "
                 f"heads {cfg.num_heads}/{cfg.num_kv_heads} x {cfg.head_dim}, "
                 f"vocab {cfg.vocab_size}, {cfg.num_tasks} tasks, adapter rank "
                 f"{cfg.adapter_rank}, f32, highest matmul precision")
        model = TransformerLM(cfg)
        params, init_s = init_params(model, seed)
        say("B", f"params {tree_bytes(params)} bytes, init {init_s:.2f} s")
        rng = np.random.default_rng(seed + 1)
        # pairs of slots share a prompt under two distant tasks
        prompts = make_prompts(rng, num_slots // 2, *prompt_lens, cfg.vocab_size)
        prompts = [p for p in prompts for _ in range(2)]
        half = cfg.num_tasks // 2
        task_ids = [(i // 2) + (i % 2) * half for i in range(num_slots)]
        spec = PagingSpec.sized(block_size, max_seq, num_slots * max_seq)
        kw = dict(spec=spec, max_seq=max_seq, chunk=chunk, budget=budget,
                  max_new=max_new)
        graph = band_graph(cfg.num_tasks, 2)

        plain, batcher, secs = serve(model, params, prompts, task_ids, **kw)
        tokens = check_served("B/none", plain, batcher, num_slots, max_new,
                              cfg.vocab_size)
        say("B", f"no store: {tokens} tokens, serve wall {secs:.2f} s (smoke "
                 "timing, not a benchmark)")
        del batcher

        zero = TaskAdapterStore(model, graph, mixing="bsr")
        say("B", f"store {tree_bytes(zero.raw)} bytes ({cfg.num_tasks} tasks)")
        _, prefill_fn = make_serve_step(model, max_seq, spec)
        first = []
        for adapters in (None, zero.serving):
            last, caches, _ = prefill_fn(*first_chunk_args(
                model, params, prompts, spec, max_seq, chunk,
                np.asarray(task_ids, np.int32), adapters,
            ))
            first.append(np.asarray(last, np.float32))
            del caches
        say("B", "first-prefill logits, zero store vs no store: max |diff| "
                 f"{float(np.abs(first[1] - first[0]).max()):.3e}")
        reqs, batcher, secs = serve(model, params, prompts, task_ids,
                                    adapters=zero, **kw)
        check_served("B/zero", reqs, batcher, num_slots, max_new, cfg.vocab_size)
        check(all(reqs[i].out == plain[i].out for i in range(num_slots)),
              "B: a zero adapter store changed the served tokens")
        say("B", f"zero store == no store, serve wall {secs:.2f} s")
        del batcher, zero

        # update_every past the request count: the batcher's finish hook never
        # updates, so the one update() between ticks below is the only one
        store = TaskAdapterStore(model, graph, mixing="bsr", seed=seed,
                                 update_every=num_slots + 1)
        store.randomize(scale=0.5)
        mix = jax.jit(graph_mix_tree)
        t0 = time.perf_counter()
        compiled = mix.lower(store.mu, store.raw).compile()
        compile_s = time.perf_counter() - t0
        kernels = "tpu_custom_call" in compiled.as_text()
        check(kernels, "B: no tpu_custom_call in the compiled store update")
        reqs, batcher, secs = serve(model, params, prompts, task_ids,
                                    adapters=store, between_ticks=store.update,
                                    **kw)
        tokens = check_served("B/mixed", reqs, batcher, num_slots, max_new,
                              cfg.vocab_size)
        check(store.updates == 1, f"B: {store.updates} store updates, want 1")
        differ = [reqs[i].out != reqs[i + 1].out for i in range(0, num_slots, 2)]
        check(all(differ),
              f"B: same prompt under two tasks served identical tokens {differ}")
        say("B", f"randomized store: tasks differ on every shared prompt, "
                 f"1 update between ticks, mix compile {compile_s:.2f} s, "
                 f"tpu_custom_call {kernels}, {tokens} tokens, serve wall "
                 f"{secs:.2f} s, peak_bytes_in_use {peak_bytes()}")
        say("B", f"ok, phase wall {time.perf_counter() - t_phase:.2f} s")


# ---------------------------------------------------------------- phase C
def phase_c(devices, seed, *, width=ROW_WIDTH, eta=0.5, tau=2.0, alpha=0.05):
    """One task per chip: graph mixing over collectives, and sharded BOL."""
    t_phase = time.perf_counter()
    m = len(devices)
    mesh = jax.make_mesh((m,), ("task",), axis_types=(AxisType.Auto,),
                         devices=devices)
    row = NamedSharding(mesh, P("task", None))
    one = devices[0]
    rng = np.random.default_rng(seed)
    theta_np = rng.standard_normal((m, width)).astype(np.float32)
    theta = jax.device_put(theta_np, row)
    shards = theta.addressable_shards
    placed = {s.device for s in shards}
    check(len(shards) == m and len(placed) == m,
          f"C: {len(shards)} shards on {len(placed)} distinct devices")
    check(all(s.data.shape == (1, width) for s in shards),
          "C: a shard does not hold exactly one task row")
    say("C", f"{m} tasks x {width} floats, one task per device: "
             f"{[str(s.device) for s in shards]}")

    def reference(mu):
        return np.asarray(jax.jit(lambda a, b: a.T @ b)(
            jax.device_put(mu, one), jax.device_put(theta_np, one)))

    ring_graph = band_graph(m, 1)
    spec = mixing_spec_for_band_graph(ring_graph, eta, tau, alpha)
    check(spec is not None, "C: the band graph has no ring mixing spec")
    self_w, nbr = spec
    ring = jax.jit(jax.shard_map(
        lambda th: mix_ring(th, self_w, nbr, "task", m),
        mesh=mesh, in_specs=P("task", None), out_specs=P("task", None),
    ))
    adj = rng.uniform(0.1, 1.0, (m, m))
    adj = np.triu(adj, 1) + np.triu(adj, 1).T
    dense_graph = TaskGraph(adj)
    check(mixing_spec_for_band_graph(dense_graph, eta, tau, alpha) is None,
          "C: the all-gather graph is a band graph")
    mu_dense = np.asarray(dense_graph.bsr_mixing(eta, tau, alpha), np.float32)
    gather = jax.jit(jax.shard_map(
        lambda th, mu_col: mix_all_gather(th, mu_col[:, 0], "task"),
        mesh=mesh, in_specs=(P("task", None), P(None, "task")),
        out_specs=P("task", None),
    ))
    with jax.default_matmul_precision("highest"):
        for name, fn, fargs, mu in (
            ("mix_ring", ring, (theta,),
             np.asarray(ring_graph.bol_mixing(eta, tau, alpha), np.float32)),
            ("mix_all_gather", gather,
             (theta, jax.device_put(mu_dense, NamedSharding(mesh, P(None, "task")))),
             mu_dense),
        ):
            t0 = time.perf_counter()
            compiled = fn.lower(*fargs).compile()
            compile_s = time.perf_counter() - t0
            out = compiled(*fargs)
            devs = {s.device for s in out.addressable_shards}
            check(len(devs) == m, f"C/{name}: output on {len(devs)} devices")
            err = float(np.abs(np.asarray(out) - reference(mu)).max())
            say("C", f"{name}: max |err| vs mu.T @ theta on one device "
                     f"{err:.3e}, compile {compile_s:.2f} s")
            check(err <= 1e-4, f"C/{name}: max |err| {err:.3e} > 1e-4")

        tasks = generate_clustered_tasks(rng, m=m, d=6, num_clusters=2, knn=2)
        x, y = map(jnp.asarray, tasks.sample(rng, 40))
        problem = MultiTaskProblem(ring_graph, SQUARED, 0.5, 1.5)
        want = np.asarray(bol(problem, x, y, num_iters=60, accelerated=False).w)
        for use_ring in (True, False):
            got = bol_sharded(problem, x, y, 60, mesh, use_ring=use_ring)
            err = float(np.abs(np.asarray(got) - want).max())
            say("C", f"bol_sharded(use_ring={use_ring}) vs bol: max |err| "
                     f"{err:.3e}")
            check(err <= 1e-4, f"C/bol_sharded: max |err| {err:.3e} > 1e-4")
    say("C", f"ok, phase wall {time.perf_counter() - t_phase:.2f} s "
             "(smoke timing, not a benchmark)")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip mixing phase")
    args = ap.parse_args()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX reports platform {dev.platform!r}")
    if len(devices) < args.chips:
        raise SystemExit(f"--chips {args.chips} but JAX sees {len(devices)}")
    print(f"cache {use_compile_cache()}; device {dev.device_kind} x "
          f"{len(devices)}; jax {jax.__version__}", flush=True)
    if args.chips == 4:
        phase_c(devices[:4], args.seed)
    else:
        phase_a(get("olmo_1b"), args.seed)
        phase_b(get("multitask_lm"), args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))


if __name__ == "__main__":
    main()
