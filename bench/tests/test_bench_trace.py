"""The reduction from a trace to busy time, idle gaps and what the host was
doing in them; on hand-made events and on a small trace recorded on a v5e."""
from pathlib import Path

import pytest

from bench.lib import names, trace
from bench.lib.trace import Event, Trace

DATA = Path(__file__).parent / "data"


def _trace():
    ops = [Event("fusion.1", 10, 20),
           Event("paged_prefill_attention_pallas.6", 25, 10),
           Event("fusion.2", 50, 10), Event("_graph_mix_kernel", 80, 5)]
    spans = [Event("bench.window", 0, 100), Event("bench.step", 5, 40),
             Event("bench.update", 70, 20), Event("bench.wait", 90, 10)]
    return Trace((0, 100), ops, [Event("jit_prefill_chunk(1)", 10, 50)],
                 spans, 1)


def test_busy_is_the_union_of_operations():
    t = _trace()
    # [10, 35) + [50, 60) + [80, 85)
    assert t.busy_s == pytest.approx(40e-9)
    assert t.window_s == pytest.approx(100e-9)
    assert t.idle_gaps() == [(0, 10), (35, 50), (60, 80), (85, 100)]


def test_idle_gaps_are_put_on_what_the_host_was_doing():
    b = _trace().breakdown()
    gaps = dict((k, v) for k, v in b["idle_gaps"])
    # (0,10): step covers 5 of 10; (35,50): step 10 of 15; (60,80): update
    # 10 of 20; (85,100): wait 10 of 15, update 5
    assert gaps == pytest.approx({"bench.step": 25e-9, "bench.update": 20e-9,
                                  "bench.wait": 15e-9})
    ops = dict((k, v) for k, v in b["device_ops"])
    assert ops["fusion.1"] == pytest.approx(20e-9)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_kernel_and_step_times_by_name():
    t = _trace()
    assert t.op_time(names.is_attention) == (pytest.approx(10e-9), 1)
    assert t.op_time(names.is_graph_mix) == (pytest.approx(5e-9), 1)
    assert t.module_time(names.is_step) == (pytest.approx(50e-9), 1)


def test_recorded_host_trace_reduces():
    """A trace recorded by the JAX profiler on a CPU: the window and the
    harness's spans come out on one clock, and with no TPU plane nothing
    counts as device time."""
    t = trace.reduce(str(DATA / "cpu.xplane.pb"))
    assert t.devices == 0 and t.ops == [] and t.busy_s == 0
    assert 0 < t.window_s < 1
    assert [s.name for s in t.spans] == ["bench.window", "bench.step",
                                         "bench.wait", "bench.step",
                                         "bench.wait"]
    assert all(t.window[0] <= s.start and s.end <= t.window[1]
               for s in t.spans)
    assert t.breakdown()["device_ops"] == []
