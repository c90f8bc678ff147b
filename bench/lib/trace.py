"""From the profiler's trace of a window to what the per-layer metrics read.

``jax.profiler`` writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it. The device plane (``/device:TPU:<n>``) holds one event per
executed program (line ``XLA Modules``) and per operation (line
``XLA Ops``); host planes hold the harness's own spans (``bench.*``,
written by ``jax.profiler.TraceAnnotation``) on the same clock. The window
is the ``bench.window`` span. Busy time is the union of the device's
operation intervals inside it.
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
WINDOW = "bench.window"


@dataclasses.dataclass
class Event:
    name: str
    start: int  # ns
    dur: int  # ns

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

    data = jax.profiler.ProfileData.from_file(path)
    spans, ops, modules, devices = [], [], [], 0
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
                spans.extend(Event(e.name, e.start_ns, e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    windows = [s for s in spans if s.name == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"{len(windows)} {WINDOW} spans in {path}")
    w = (windows[0].start, windows[0].end)
    inside = lambda es: sorted(  # noqa: E731
        (e for e in es if e.start >= w[0] and e.end <= w[1]),
        key=lambda e: e.start)
    return Trace(w, inside(ops), inside(modules), inside(spans), devices)
