"""From the profiler's trace of a window to what the per-layer metrics read.

``jax.profiler`` writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it. The device plane (``/device:TPU:<n>``) holds one event per
executed program (line ``XLA Modules``) and per operation (line
``XLA Ops``); the plane ``METADATA_PLANE`` holds each program's HLO, from
which an operation takes the name-scope path of the JAX code it came from.
Host planes hold the harness's own spans (``bench.*``) and the program's
(``serve.*``), both written by ``jax.profiler.TraceAnnotation``, on the
same clock. The window is the ``bench.window`` span. Busy time is the
union of the device's operation intervals inside it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
PROGRAM_SPAN_PREFIX = "serve."
WINDOW = "bench.window"
# the host plane that keeps the HLO of each program run, and its stat
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"


@dataclasses.dataclass
class Event:
    name: str
    start: int  # ns
    dur: int  # ns
    scope: str = ""  # a device operation's name-scope path

    @property
    def end(self) -> int:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    window: tuple  # (start, end) ns of the bench.window span
    ops: list  # device operations inside the window, by start
    modules: list  # device programs inside the window, by start
    spans: list  # the harness's host spans inside the window
    devices: int  # device planes seen
    # the program's host spans inside the window
    program_spans: list = dataclasses.field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        return sum(b - a for a, b in busy_intervals(self.ops)) * 1e-9

    def op_time(self, match) -> tuple[float, int]:
        """(seconds, count) of the device operations whose name matches."""
        hits = [e for e in self.ops if match(e.name)]
        return sum(e.dur for e in hits) * 1e-9, len(hits)

    def scope_time(self, substr: str) -> tuple[float, int]:
        """(seconds, count) of the device operations whose name-scope path
        holds ``substr``."""
        hits = [e for e in self.ops if substr in e.scope]
        return sum(e.dur for e in hits) * 1e-9, len(hits)

    def module_time(self, match) -> tuple[float, int]:
        hits = [e for e in self.modules if match(e.name)]
        return sum(e.dur for e in hits) * 1e-9, len(hits)

    def idle_gaps(self) -> list:
        """(start, end) ns of every stretch of the window with no device
        operation running."""
        gaps, t = [], self.window[0]
        for a, b in busy_intervals(self.ops):
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if self.window[1] > t:
            gaps.append((t, self.window[1]))
        return gaps

    def host_doing(self, a: int, b: int) -> str:
        """The innermost harness span that covers most of [a, b)."""
        best, best_cover = "bench.window", 0
        for s in self.spans:
            if s.name == WINDOW:
                continue
            cover = min(b, s.end) - max(a, s.start)
            if cover > best_cover:
                best, best_cover = s.name, cover
        return best

    def breakdown(self, top: int = 10) -> dict:
        by_op = defaultdict(int)
        for e in self.ops:
            by_op[e.name] += e.dur
        by_host = defaultdict(int)
        for a, b in self.idle_gaps():
            by_host[self.host_doing(a, b)] += b - a
        rank = lambda d: [[k, v * 1e-9] for k, v in  # noqa: E731
                          sorted(d.items(), key=lambda kv: -kv[1])[:top]]
        return {"device_ops": rank(by_op), "idle_gaps": rank(by_host)}


def busy_intervals(events) -> list:
    """The union of the events' intervals, merged and sorted."""
    out = []
    for e in sorted(events, key=lambda e: e.start):
        if out and e.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e.end)
        else:
            out.append([e.start, e.end])
    return [tuple(x) for x in out]


def short(name: str) -> str:
    """An operation's name without the HLO text a TPU trace appends:
    ``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _varint(buf: bytes, pos: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def _fields(buf: bytes, pos: int, end: int):
    """(field number, value) of each field of the protobuf message in
    buf[pos:end]; a length-delimited value is its (start, end)."""
    while pos < end:
        key, pos = _varint(buf, pos)
        num, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 1:
            value, pos = None, pos + 8
        elif wire == 2:
            n, pos = _varint(buf, pos)
            value, pos = (pos, pos + n), pos + n
        elif wire == 5:
            value, pos = None, pos + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {pos}")
        yield num, value


def hlo_scopes(buf: bytes) -> dict:
    """Program name -> {instruction name -> name-scope path}, from a
    serialized XSpace (``.xplane.pb``).

    The trace keeps the HLO of every program it saw run in the plane
    ``METADATA_PLANE``: one event metadata per program, named as the
    device's ``XLA Modules`` events are, whose stat ``HLO_STAT`` holds a
    serialized HloProto. An instruction's ``OpMetadata.op_name`` is the
    path of jitted function names and ``jax.named_scope`` it came from; a
    fusion carries its root's. ``ProfileData`` does not expose event
    metadata, so the few fields needed are read here: XSpace.planes (1);
    XPlane.name (2), .event_metadata (4), .stat_metadata (5), map entries
    key (1) and value (2); XEventMetadata.name (2), .stats (5);
    XStatMetadata.name (2); XStat.metadata_id (1), .bytes_value (6);
    HloProto.hlo_module (1); HloModuleProto.computations (3);
    HloComputationProto.instructions (2); HloInstructionProto.name (1),
    .metadata (7); OpMetadata.op_name (2)."""
    def text(span):
        return buf[span[0]:span[1]].decode(errors="replace")

    def field(span, num):
        return next((v for n, v in _fields(buf, *span) if n == num), None)

    for num, span in _fields(buf, 0, len(buf)):
        if num != 1:
            continue
        ours, programs, stat_names = False, [], {}
        for n, v in _fields(buf, *span):
            if n == 2:
                ours = text(v) == METADATA_PLANE
                if not ours:
                    break
            elif n == 4:
                programs.append(field(v, 2))
            elif n == 5:
                meta = field(v, 2)
                name = field(meta, 2) if meta else None
                stat_names[field(v, 1) or 0] = text(name) if name else ""
        if not ours:
            continue
        out = {}
        for meta in filter(None, programs):
            program, hlo = "", None
            for n, v in _fields(buf, *meta):
                if n == 2:
                    program = text(v)
                elif n == 5:
                    stat = dict(_fields(buf, *v))
                    if stat_names.get(stat.get(1)) == HLO_STAT and 6 in stat:
                        hlo = stat[6]
            module = field(hlo, 1) if hlo else None
            if module is None:
                continue
            paths = out.setdefault(program, {})
            for n, comp in _fields(buf, *module):
                if n != 3:
                    continue
                for m, inst in _fields(buf, *comp):
                    if m != 2:
                        continue
                    f = dict(_fields(buf, *inst))
                    path = text(field(f[7], 2) or (0, 0)) if 7 in f else ""
                    if 1 in f and path:
                        paths[text(f[1])] = path
        return out
    return {}


def _scope(ops: list, modules: list, paths: dict) -> None:
    """Give each device operation the name-scope path of its instruction in
    the program whose execution (an ``XLA Modules`` event) holds it."""
    mods = sorted(modules, key=lambda m: m.start)
    i = 0
    for e in sorted(ops, key=lambda e: e.start):
        while i < len(mods) and mods[i].end < e.start:
            i += 1
        if i < len(mods) and mods[i].start <= e.start:
            e.scope = paths.get(mods[i].name, {}).get(e.name, "")


def find(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} traces under {trace_dir}")
    return paths[0]


def reduce(path: str) -> Trace:
    """Read an ``.xplane.pb`` into the window's events. Device events are
    taken from the first TPU plane (the chip a one-chip cell runs on)."""
    import jax

    with open(path, "rb") as f:
        raw = f.read()
    data = jax.profiler.ProfileData.from_serialized_xspace(raw)
    spans, program, ops, modules, devices = [], [], [], [], 0
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            devices += 1
            if devices > 1:
                continue
            for line in plane.lines:
                dest = {OPS_LINE: ops, MODULES_LINE: modules}.get(line.name)
                if dest is not None:
                    dest.extend(Event(short(e.name), e.start_ns,
                                      e.duration_ns) for e in line.events)
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(Event(e.name, e.start_ns, e.duration_ns))
                    elif e.name.startswith(PROGRAM_SPAN_PREFIX):
                        program.append(Event(e.name, e.start_ns,
                                             e.duration_ns))
    _scope(ops, modules, hlo_scopes(raw))
    windows = [s for s in spans if s.name == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"{len(windows)} {WINDOW} spans in {path}")
    w = (windows[0].start, windows[0].end)
    inside = lambda es: sorted(  # noqa: E731
        (e for e in es if e.start >= w[0] and e.end <= w[1]),
        key=lambda e: e.start)
    return Trace(w, inside(ops), inside(modules), inside(spans), devices,
                 inside(program))
