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


def test_scope_time_sums_operations_under_a_name_scope():
    ops = [Event("fusion.1", 10, 20,
                 "jit(step)/while/body/mixer/dot_general"),
           Event("fusion.2", 40, 5, "jit(step)/while/body/mlp/mul"),
           Event("fusion.3", 50, 7, "jit(step)/while/body/mixer/add"),
           Event("copy.1", 60, 3)]
    t = Trace((0, 100), ops, [], [Event("bench.window", 0, 100)], 1)
    assert t.scope_time("/mixer/") == (pytest.approx(27e-9), 2)
    assert t.scope_time("mlp") == (pytest.approx(5e-9), 1)
    assert t.scope_time("attention") == (0, 0)


def test_program_spans_leave_the_breakdown_as_it_was():
    """The program's ``serve.*`` spans are kept in a list of their own:
    the harness's spans, the idle split and the busy time read as without
    them."""
    t = _trace()
    with_program = Trace(t.window, t.ops, t.modules, t.spans, t.devices,
                         [Event("serve.step", 5, 40),
                          Event("serve.fetch", 36, 12),
                          Event("serve.fetch", 61, 18)])
    assert with_program.breakdown() == t.breakdown()
    assert with_program.spans == t.spans and t.program_spans == []
    assert [s.name for s in with_program.program_spans] == [
        "serve.step", "serve.fetch", "serve.fetch"]


def _pb(num, value):
    """One protobuf field: an int as a varint, bytes length-delimited."""
    def varint(n):
        out = b""
        while True:
            out += bytes([n & 0x7F | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out
    if isinstance(value, int):
        return varint(num << 3) + varint(value)
    return varint(num << 3 | 2) + varint(len(value)) + value


def _hlo(computations):
    """A serialized HloProto: each computation a list of (instruction name,
    op_name or None for no metadata)."""
    module = _pb(1, b"m")
    for i, insts in enumerate(computations):
        comp = _pb(1, f"c{i}".encode())
        for name, op_name in insts:
            inst = _pb(1, name.encode()) + _pb(2, b"op")
            if op_name is not None:
                inst += _pb(7, _pb(1, b"type") + _pb(2, op_name.encode()))
            comp += _pb(2, inst)
        module += _pb(3, comp)
    return _pb(1, module)


def _xspace(programs):
    """An XSpace whose metadata plane holds the given {program name: HLO}
    beside a device plane and a host plane, as a TPU trace has them."""
    plane = _pb(1, 5) + _pb(2, trace.METADATA_PLANE.encode())
    for i, (name, hlo) in enumerate(programs.items()):
        stats = (_pb(5, _pb(1, 1) + _pb(4, 7))
                 + _pb(5, _pb(1, 2) + _pb(6, hlo)))
        meta = _pb(1, i + 1) + _pb(2, name.encode()) + stats
        plane += _pb(4, _pb(1, i + 1) + _pb(2, meta))
    for k, name in ((1, b"flops"), (2, trace.HLO_STAT.encode())):
        plane += _pb(5, _pb(1, k) + _pb(2, _pb(1, k) + _pb(2, name)))
    device = _pb(2, b"/device:TPU:0") + _pb(4, _pb(1, 1) + _pb(2, _pb(
        2, b"%fusion.1 = f32[2] fusion(...)")))
    host = _pb(2, b"/host:CPU") + _pb(4, _pb(1, 1) + _pb(2, _pb(2, b"x")))
    return _pb(1, device) + _pb(1, plane) + _pb(1, host)


def test_hlo_scopes_read_each_programs_instructions():
    raw = _xspace({
        "jit_step(7)": _hlo([[("fusion.1", "jit(step)/while/mixer/dot"),
                              ("copy.2", None), ("add.3", "")],
                             [("sort.4", "jit(step)/sort")]]),
        "jit_other(9)": _hlo([[("fusion.1", "jit(other)/mlp/mul")]])})
    assert trace.hlo_scopes(raw) == {
        "jit_step(7)": {"fusion.1": "jit(step)/while/mixer/dot",
                        "sort.4": "jit(step)/sort"},
        "jit_other(9)": {"fusion.1": "jit(other)/mlp/mul"}}
    assert trace.hlo_scopes(_pb(1, _pb(2, b"/host:CPU"))) == {}


def test_operations_take_the_path_of_the_program_that_ran_them():
    paths = {"jit_step(7)": {"fusion.1": "jit(step)/mixer/dot"},
             "jit_other(9)": {"fusion.1": "jit(other)/mlp/mul"}}
    modules = [Event("jit_other(9)", 50, 30), Event("jit_step(7)", 0, 40)]
    ops = [Event("fusion.1", 55, 5), Event("fusion.1", 10, 5),
           Event("copy.2", 20, 5), Event("fusion.1", 45, 2)]
    trace._scope(ops, modules, paths)
    assert [e.scope for e in ops] == ["jit(other)/mlp/mul",
                                      "jit(step)/mixer/dot", "", ""]
