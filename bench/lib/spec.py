"""``BENCHMARK.json`` and the files it names, found by name.

A cell's configuration is ``bench/configs/<config>.json``, its traffic mix
``bench/traffic/<traffic>.json``, its architecture's module
``bench/arch/<model_type>.py`` and each per-layer metric's reader
``bench/metrics/<metric>.py``. A later change adds a configuration, an
architecture, a mix or a metric by adding such files and entries, never by
editing these.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import re
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(bench: dict, name: str, root: Path = ROOT) -> dict:
    """The configuration ``name``: its file, as declared, must be
    ``bench/configs/<name>.json``."""
    path = BENCH / "configs" / f"{name}.json"
    declared = [c for c in bench["configs"] if c["name"] == name]
    if not declared:
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")
    if (root / declared[0]["file"]).resolve() != path.resolve():
        raise ValueError(f"configuration {name!r} declares file "
                         f"{declared[0]['file']!r}, expected {path}")
    return json.loads(path.read_text())


def traffic(name: str) -> dict:
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def end_to_end(bench: dict, workload: str) -> list[dict]:
    return [m for m in bench["end_to_end"] if applies(m, workload)]


def per_layer(bench: dict, workload: str) -> list[dict]:
    return [m for m in bench["per_layer"] if applies(m, workload)]


def reader(name: str):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"per-layer metric {name!r} has no reader "
                                f"at {path}")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def architecture(model_type: str):
    """The module ``bench/arch/<model_type>.py``: for one architecture, the
    program's config (``arch``), the weights drawn from a key
    (``weights``), their place in the program's tree (``params``), the
    adapter store's raw factors and layout (``adapter_raw``,
    ``store_raw``) and the shape the cost counts read (``shape``)."""
    if not re.fullmatch(r"[A-Za-z0-9_]+", model_type):
        raise ValueError(f"model_type {model_type!r} is not a module name")
    name = f"bench.arch.{model_type}"
    path = BENCH / "arch" / f"{model_type}.py"
    if name not in sys.modules and not path.is_file():
        raise FileNotFoundError(f"model_type {model_type!r} has no "
                                f"architecture module at {path}")
    return importlib.import_module(name)
