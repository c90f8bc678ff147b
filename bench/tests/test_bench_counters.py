"""The program's counters reach the readers through ``Tick.counters``: per
step, what each counter rose by, summing over a run to the batcher's own
totals; and the readers of the counters and of the program's spans."""
import time
import types

import jax
import numpy as np
import pytest

from bench.lib import gen, harness, program, spec, weights
from bench.lib.harness import Recorder, Tick
from bench.lib.peaks import PEAKS
from bench.lib.trace import Event
from bench.tests import tiny

WALK = ("attn_blocks_walked", "attn_blocks_listed")


def _pallas_config():
    cfg = tiny.config(adapters=False)
    cfg["deployment"]["attn_backend"] = "pallas"
    return cfg


def test_tick_counters_sum_to_the_batchers_totals():
    """Every step of a run, recorded as the harness records the window's:
    the deltas add up to what the counters rose by in the run."""
    cfg = _pallas_config()
    seed = 2**33 + 21
    w = weights.model_weights(cfg, seed, program.num_tasks(cfg))
    rec = Recorder()
    sys_ = program.build(cfg, w, None, rec.on_token)
    harness.warm_up(sys_)
    b = sys_.batcher
    start = program.counters(b)
    assert set(start) == set(WALK)
    for p in gen.open_loop(tiny.OPEN, 1.0, seed, cfg["vocab_size"])[:6]:
        harness.submit(sys_, rec, p, None, harness._no_span)
    while b.queue or b.slots.any_live():
        rec.run_step(sys_, harness._no_span)
    end = program.counters(b)
    for k in WALK:
        assert sum(t.counters[k] for t in rec.ticks) == end[k] - start[k]
    assert 0 < end["attn_blocks_walked"] - start["attn_blocks_walked"] < (
        end["attn_blocks_listed"] - start["attn_blocks_listed"])
    assert all(t.counters[k] >= 0 for t in rec.ticks for k in WALK)


def test_counters_of_a_program_with_a_dict_or_without_any():
    assert program.counters(types.SimpleNamespace()) == {}
    b = types.SimpleNamespace(counters={"x": 3}, attn_blocks_walked=9)
    assert program.counters(b) == {"x": 3}
    b = types.SimpleNamespace(attn_blocks_walked=9)
    assert program.counters(b) == {"attn_blocks_walked": 9}


def _tick(**counters):
    return Tick(1, 1, 1, [], 1, 0, counters)


def test_walked_share_reads_the_tick_deltas():
    read = spec.reader("attn_walked_share")
    ctx = types.SimpleNamespace(ticks=[
        _tick(attn_blocks_walked=3, attn_blocks_listed=40),
        _tick(attn_blocks_walked=7, attn_blocks_listed=40),
        _tick()])
    assert read(ctx) == pytest.approx(12.5)
    assert read(types.SimpleNamespace(ticks=[_tick(), _tick()])) is None


def test_fetch_reader_takes_the_mean_fetch_span():
    read = spec.reader("logits_fetch_ms")
    spans = [Event("serve.fetch", 0, 2_000_000), Event("serve.emit", 0, 9),
             Event("serve.fetch", 5, 3_000_000)]
    trace = types.SimpleNamespace(program_spans=spans)
    assert read(types.SimpleNamespace(trace=trace)) == pytest.approx(2.5)
    trace = types.SimpleNamespace(program_spans=spans[1:2])
    assert read(types.SimpleNamespace(trace=trace)) is None


def test_traced_run_reports_the_counter_and_span_readers():
    cell = {"name": tiny.CELL, "config": "tiny", "traffic": "tiny",
            "chips": 1}
    r = harness.run(tiny.bench(), cell, _pallas_config(), tiny.CLOSED,
                    2**33 + 23, 1.0, True, time.perf_counter(),
                    jax.devices(), PEAKS["TPU v5 lite"],
                    {"widest_logit_gap": 0.1}, say=lambda msg: None)
    assert r["correct"], r["checks"]
    m = r["metrics"]
    assert 0 < m["attn_walked_share"]["value"] < 100
    assert m["logits_fetch_ms"]["value"] > 0
    assert np.isfinite(m["logits_fetch_ms"]["value"])
