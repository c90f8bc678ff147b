"""Run one benchmark cell once; the last line of standard output is its
result as one JSON object.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for. JAX's persistent compilation cache lives at the
fixed path ``.jax_cache`` inside the checkout, so only a cell's first run in
a checkout compiles.
"""
import os
import sys
import time

T_PROCESS = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
# libtpu would log to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench.lib.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T_PROCESS))
