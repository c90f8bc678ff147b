"""chip_smoke.py and the compile-cache helper, off the chip.

The script must refuse to run without a TPU, and without the rest of the
repo. Its phases run here at smoke widths on the CPU with the Pallas kernels
interpreted, so every check runs except the compiled-kernel ones
(``tpu_custom_call``), which only a chip can satisfy.
"""
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from repro.configs import get

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402

SMOKE_SERVING = dict(num_slots=4, max_seq=64, block_size=8, chunk=8,
                     budget=16, max_new=4)


def _run(args, cwd, env_extra=None, timeout=600):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    for k, v in (env_extra or {}).items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=timeout,
    )


def _no_result(stdout: str) -> bool:
    return '"ok"' not in stdout


def test_refuses_without_tpu():
    r = _run([SCRIPT], cwd=ROOT)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert _no_result(r.stdout)


def test_refuses_without_the_repo(tmp_path):
    shutil.copy(SCRIPT, tmp_path)
    r = _run([str(tmp_path / "chip_smoke.py")], cwd=tmp_path)
    assert r.returncode != 0
    assert _no_result(r.stdout)


@pytest.fixture
def cpu_checks(monkeypatch):
    """Every check of the script, except that interpreted kernels cannot
    show a tpu_custom_call."""
    real = chip_smoke.check

    def check(ok, what):
        if "tpu_custom_call" not in what:
            real(ok, what)

    monkeypatch.setattr(chip_smoke, "check", check)


def test_phase_a_serves_both_backends(cpu_checks, capsys):
    chip_smoke.phase_a(get("olmo_1b", smoke=True), 0, prompt_lens=(8, 40),
                       **SMOKE_SERVING)
    assert "[A] ok" in capsys.readouterr().out


def test_phase_b_serves_adapter_store(cpu_checks, capsys):
    chip_smoke.phase_b(get("multitask_lm", smoke=True), 0,
                       prompt_lens=(8, 30), **SMOKE_SERVING)
    assert "[B] ok" in capsys.readouterr().out


def test_phase_c_on_four_host_devices():
    code = textwrap.dedent("""
        import sys, jax
        sys.path.insert(0, %r)
        import chip_smoke
        chip_smoke.phase_c(jax.devices()[:4], 0, width=4096)
    """ % ROOT)
    r = _run(["-c", code], cwd=ROOT, env_extra={
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "PYTHONPATH": os.path.join(ROOT, "src"),
    })
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "[C] ok" in r.stdout


CACHE_PROBE = textwrap.dedent("""
    import os, jax, jax.numpy as jnp
    from repro.launch.compile_cache import REPO_CACHE, use_compile_cache
    d = use_compile_cache()
    print("dir", d)
    print("config", jax.config.jax_compilation_cache_dir)
    print("repo", REPO_CACHE)
    if os.environ.get("COMPILE"):
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: x * 3 + 1)(jnp.ones(7)).block_until_ready()
""")


def _probe(env_extra):
    r = _run(["-c", CACHE_PROBE], cwd=ROOT, env_extra={
        "PYTHONPATH": os.path.join(ROOT, "src"), **env_extra,
    })
    assert r.returncode == 0, r.stderr
    return dict(line.split(" ", 1) for line in r.stdout.splitlines())


def _listing(path):
    return sorted(os.listdir(path)) if os.path.isdir(path) else None


def test_compile_cache_env_dir_is_the_only_dir(tmp_path):
    cache = tmp_path / "cache"
    repo_cache = os.path.join(ROOT, ".jax_cache")
    before = _listing(repo_cache)
    out = _probe({"JAX_COMPILATION_CACHE_DIR": str(cache), "COMPILE": "1"})
    assert out["dir"] == out["config"] == str(cache)
    assert any(cache.iterdir()), "nothing was written to the cache dir"
    assert _listing(repo_cache) == before, "the checkout's cache was written"


def test_compile_cache_defaults_to_checkout():
    out = _probe({"JAX_COMPILATION_CACHE_DIR": None})
    assert out["dir"] == out["config"] == out["repo"]
    assert out["repo"] == os.path.join(ROOT, ".jax_cache")
