"""Readings a cell's limits and rate are set from, many seeds in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 3 --seconds 20
        Runs the cell once per seed with the control on: each run reports
        the program's widest logit gap and the control's (the reference in
        float8 over the same tokens), and is judged on the control's, so
        ``correct`` comes out false. ``PERF.md`` sets each limit between.
    python3 bench/calibrate.py --workload <cell> --rates 1.6 2.4 3.2 ...
        Runs an open-loop cell once per offered rate (control off) and
        reports what the knee is read from: time to first token and the
        gaps between tokens, and (on standard error) the requests still
        queued at the window's close.

``bench/run.py`` never calls this; it writes one JSON line per run to
standard output and, with ``--out``, all of them to a file.
"""
import argparse
import json
import os
import sys
import time

T_PROCESS = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
# libtpu would log to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402

from bench.lib import check, harness, spec  # noqa: E402
from bench.lib.peaks import peaks  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", default=[1])
    ap.add_argument("--rates", type=float, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out")
    args = ap.parse_args()
    bench = spec.load()
    cell = spec.cell(bench, args.workload)
    cfg = spec.config(bench, cell["config"])
    mix = spec.traffic(cell["traffic"])
    devices = harness.chip_check(cell["chips"])
    peak = peaks(devices[0].device_kind)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    runs = []
    plan = ([(s, r) for r in args.rates for s in args.seeds] if args.rates
            else [(s, None) for s in args.seeds])
    for seed, rate in plan:
        m = dict(mix, rate=rate) if rate is not None else mix
        t = time.perf_counter()
        r = harness.run(bench, cell, cfg, m, seed, args.seconds,
                        bool(args.trace), t, devices, peak,
                        check.limits(cell["name"]),
                        control=rate is None,
                        say=lambda msg: print(msg, file=sys.stderr,
                                              flush=True))
        row = {"seed": seed, "rate": rate, "correct": r["correct"],
               "attempted": r["attempted"], "failed": r["failed"],
               "checks": r["checks"], "metrics": r["metrics"],
               "memory_peak_bytes": r["device"]["memory_peak_bytes"],
               "run_s": time.perf_counter() - t}
        if "breakdown" in r:
            row["device"] = r["device"]
            row["breakdown"] = r["breakdown"]
        print(json.dumps(row), flush=True)
        runs.append(row)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(runs, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
