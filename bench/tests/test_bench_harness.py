"""The harness end to end on the CPU at a tiny size, past its look for a
chip: a sound run is correct; a run whose timed path is broken underneath,
or whose program is replaced by the lower-precision control, is not."""
import time

import jax
import numpy as np
import pytest

from bench.lib import harness
from bench.lib.peaks import PEAKS
from bench.tests import tiny
from repro.serve import batching

LIMITS = {"widest_logit_gap": 0.1}


def _run(mix, adapters=True, tracing=False, control=False, seconds=1.5,
         seed=2**33 + 7):
    cell = {"name": tiny.CELL, "config": "tiny", "traffic": "tiny",
            "chips": 1}
    return harness.run(tiny.bench(), cell, tiny.config(adapters), mix, seed,
                       seconds, tracing, time.perf_counter(), jax.devices(),
                       PEAKS["TPU v5 lite"], LIMITS, control=control,
                       say=lambda msg: None)


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_sound_run_is_correct(loop):
    mix = tiny.OPEN if loop == "open" else tiny.CLOSED
    r = _run(mix, adapters=loop == "open")
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    if loop == "open":  # every request due in the window was sent
        assert r["attempted"] == round(mix["rate"] * 1.5)
    assert set(r["metrics"]) == {"ttft_p90_ms", "itl_p95_ms", "itl_mean_ms",
                                 "output_tok_s", "setup_s"}
    assert list(r)[-1] == "checks"
    assert r["checks"]["widest_logit_gap"]["value"] <= LIMITS["widest_logit_gap"]


def test_traced_run_reports_per_layer_metrics():
    r = _run(tiny.CLOSED, tracing=True)
    assert r["correct"], r["checks"]
    assert {"slab_fill", "slots_busy_share", "kv_blocks_in_use_share",
            "queue_wait_p90_ms"} <= set(r["metrics"])
    assert 0 < r["metrics"]["slab_fill"]["value"] <= 100
    assert r["device"]["window_s"] > 0 and "breakdown" in r


def test_altered_token_is_not_correct(monkeypatch):
    """A token altered where it is produced: every request's third token
    is replaced by the next id."""
    emit = batching.ContinuousBatcher._emit

    def altered(self, req, row=None, greedy=None):
        emit(self, req, row, greedy)
        if len(req.out) == 3:
            req.out[-1] = (int(req.out[-1]) + 1) % self.model.cfg.vocab_size

    monkeypatch.setattr(batching.ContinuousBatcher, "_emit", altered)
    r = _run(tiny.OPEN)
    assert not r["correct"]
    assert r["checks"]["widest_logit_gap"]["value"] > LIMITS["widest_logit_gap"]


def test_stale_adapter_store_is_not_correct(monkeypatch):
    """The store's update leaves the served copy as it was: the reference,
    mixing at every version, sees the served tokens fall off its best."""
    from repro.serve import adapters

    def frozen(self):
        self.updates += 1

    monkeypatch.setattr(adapters.TaskAdapterStore, "update", frozen)
    r = _run(tiny.OPEN, seconds=2.0)
    assert not r["correct"]


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_control_is_far_from_the_program(loop):
    """The reference in float8 (the control), put in the program's place,
    puts first tokens that lie far below the float32 best, and the run is
    not correct; the bf16 program's served tokens lie within the limit."""
    mix = tiny.OPEN if loop == "open" else tiny.CLOSED
    r = _run(mix, adapters=loop == "open", control=True)
    prog = r["checks"]["widest_logit_gap"]["value"]
    ctrl = r["checks"]["control_widest_gap"]["value"]
    assert not r["correct"], r["checks"]
    assert ctrl > 3 * prog and ctrl > LIMITS["widest_logit_gap"], (prog, ctrl)
    assert np.isfinite(prog) and prog <= LIMITS["widest_logit_gap"]


def test_main_prints_checks_then_one_result_line(monkeypatch, capsys):
    """``main`` past the look for a chip: the checks go last to standard
    error, the result is the last line of standard output."""
    import json

    from bench.lib import check, spec

    bench = tiny.bench()
    monkeypatch.setattr(spec, "load", lambda: bench)
    monkeypatch.setattr(spec, "config", lambda bench, name: tiny.config())
    monkeypatch.setattr(spec, "traffic", lambda name: tiny.OPEN)
    monkeypatch.setattr(check, "limits", lambda cell: LIMITS)
    monkeypatch.setattr(harness, "chip_check", lambda chips: jax.devices())
    monkeypatch.setattr(harness, "peaks", lambda kind: PEAKS["TPU v5 lite"])
    rc = harness.main(["--workload", tiny.CELL, "--seed", str(2**32 + 3),
                       "--seconds", "1", "--trace", "0"], time.perf_counter())
    out, err = capsys.readouterr()
    assert rc == 0
    result = json.loads(out.strip().splitlines()[-1])
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device", "checks"}
    assert result["correct"] and list(result)[-1] == "checks"
    tail = err.strip().splitlines()[-len(result["checks"]):]
    assert [line.split()[0] for line in tail] == list(result["checks"])
