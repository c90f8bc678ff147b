"""Benchmark runner — one function per paper table/figure.

Prints ``name,seconds,derived`` CSV summary lines (detailed per-benchmark
CSVs land in reports/).

  fig2_erm           Figure 2  — ERM convergence, all methods, C sweep
  fig3_stochastic    Figure 3  — stochastic minibatch sweep (fresh samples)
  table1             Table 1   — communication/sample complexity accounting
  delay              Theorem 7 — bounded-staleness convergence
  kernels            micro     — Pallas kernels vs jnp oracle (interpret)

Full paper-scale runs: pass --full (m=100, d=100, n=500 as in Appendix I);
the default is a reduced-size pass that exercises every code path quickly.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def _timed(name, fn):
    t0 = time.perf_counter()
    derived = fn()
    dt = time.perf_counter() - t0
    print(f"SUMMARY,{name},{dt:.2f}s,{derived}")
    return derived


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes (slower)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    if args.full:
        size = ["--m", "100", "--d", "100", "--n", "500"]
        fig2_extra = ["--iters", "300"]
        fig3_extra = ["--budget", "10000"]
    else:
        size = ["--m", "40", "--d", "40", "--n", "150"]
        fig2_extra = ["--iters", "200", "--clusters", "1", "5", "50"]
        fig3_extra = ["--budget", "3000", "--batches", "50", "150", "500"]

    def bench_fig2():
        from benchmarks import fig2_erm

        rows = fig2_erm.main(size + fig2_extra)
        return f"methods={len(set(r[0] for r in rows))}"

    def bench_fig3():
        from benchmarks import fig3_stochastic

        rows = fig3_stochastic.main(size + fig3_extra)
        return f"points={len(rows)}"

    def bench_table1():
        from benchmarks import table1_complexity

        rows = table1_complexity.main(size)
        return f"rows={len(rows)}"

    def bench_delay():
        from benchmarks import delay_bench

        rows = delay_bench.main(
            [] if args.full else ["--m", "12", "--d", "12", "--n", "60",
                                  "--iters", "200"]
        )
        return f"gammas={len(rows)}"

    def bench_ablation():
        from benchmarks import ablation_mtl_lm

        rows = ablation_mtl_lm.main(
            ["--steps", "200" if args.full else "40"]
        )
        by = {r[0]: r[1] for r in rows}
        return f"local={by['local']:.3f},graph={by['graph']:.3f},consensus={by['consensus']:.3f}"

    def bench_kernels():
        import numpy as np
        import jax.numpy as jnp

        from repro.kernels.graph_mix.kernel import graph_mix_pallas
        from repro.kernels.graph_mix.ref import graph_mix_reference

        rng = np.random.default_rng(0)
        mu = jnp.asarray(rng.standard_normal((32, 32)), jnp.float32)
        th = jnp.asarray(rng.standard_normal((32, 4096)), jnp.float32)
        got = graph_mix_pallas(mu, th, interpret=True)
        want = graph_mix_reference(mu, th)
        err = float(jnp.max(jnp.abs(got - want)))
        return f"graph_mix_max_err={err:.2e}"

    benches = {
        "fig2_erm": bench_fig2,
        "fig3_stochastic": bench_fig3,
        "table1": bench_table1,
        "delay": bench_delay,
        "ablation_mtl_lm": bench_ablation,
        "kernels": bench_kernels,
    }
    print("name,seconds,derived")
    for name, fn in benches.items():
        if args.only and name != args.only:
            continue
        _timed(name, fn)


if __name__ == "__main__":
    main()
