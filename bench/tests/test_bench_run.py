"""``bench/run.py`` refuses to run without a TPU, and without the program."""
import os
import shutil
import subprocess
import sys

from bench.lib import spec


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "olmo_1b.long_decode", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_run_exits_nonzero_without_tpu():
    r = _run(spec.ROOT)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"correct"' not in r.stdout


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path, {"PYTHONPATH": ""})
    assert r.returncode != 0
    assert '"correct"' not in r.stdout
