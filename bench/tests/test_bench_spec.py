"""Every cell of BENCHMARK.json resolves to its files by name, and the file
keeps to the benchmark's rules."""
import re

import pytest

from bench.lib import check, spec

BENCH = spec.load()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves_by_name(cell):
    w = spec.cell(BENCH, cell)
    cfg = spec.config(BENCH, w["config"])
    assert cfg["reference"] and cfg["deployment"]["num_slots"] > 0
    assert spec.architecture(cfg["model_type"]).shape(cfg).attn_layers > 0
    assert spec.traffic(w["traffic"])["loop"] in ("open", "closed")
    assert check.limits(cell)["widest_logit_gap"] > 0
    for m in spec.per_layer(BENCH, cell):
        assert callable(spec.reader(m["name"]))
    reported = {m["name"] for m in spec.end_to_end(BENCH, cell)}
    assert "setup_s" in reported and len(reported) >= 2
    assert spec.per_layer(BENCH, cell)
    for m in spec.per_layer(BENCH, cell):
        assert m["moves"] in reported, (cell, m["name"])


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        spec.cell(BENCH, "no_such_cell")


def test_names_units_and_layers():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names) and len(set(names)) == len(names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert all(len(x) <= 200 and "\n" not in x for x in layers)


def test_configs_used_and_files_unique():
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
