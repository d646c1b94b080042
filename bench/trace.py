"""Reduction of a JAX profiler trace to the events the metric readers use.

``load_xplane`` reads the ``.xplane.pb`` the profiler writes into a flat
list of :class:`Event` (device planes and the host's TraceMe lines), and
:class:`TraceView` answers the questions the readers ask: which chips ran,
which device operations and programs ran when, how much of a window a chip
was busy, and which host span a stretch of device idleness fell in.

Events carry nanoseconds on the profiler's one clock (host and device
planes are aligned by the profiler). ``save_events``/``load_events`` keep a
small recorded trace for the tests.
"""

from __future__ import annotations

import gzip
import json
import re
from pathlib import Path
from typing import NamedTuple

ROUND_SPAN = "bench.round"
DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start: float   # ns
    dur: float     # ns

    @property
    def end(self) -> float:
        return self.start + self.dur


def load_xplane(path: str | Path) -> list[Event]:
    """Every event of the device planes and of the host CPU plane."""
    from jax.profiler import ProfileData

    events = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not (DEVICE_PLANE.match(plane.name) or plane.name == "/host:CPU"):
            continue
        for line in plane.lines:
            for e in line.events:
                events.append(Event(plane.name, line.name, e.name,
                                    float(e.start_ns), float(e.duration_ns)))
    return events


def save_events(events: list[Event], path: str | Path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump([list(e) for e in events], f)


def load_events(path: str | Path) -> list[Event]:
    with gzip.open(path, "rt") as f:
        return [Event(*e) for e in json.load(f)]


def union_ns(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``(start, end)`` intervals within [lo, hi]."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The stretches of [lo, hi] that no interval covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if e <= t:
            continue
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


_HLO = re.compile(r"^%?([^\s=]+) = (\S+?)(?:\{[^}]*\})? ([\w-]+)\(")
_HLO_TUPLE = re.compile(r"^%?([^\s=]+) = \(.*?\) ([\w-]+)\(")


def op_label(name: str) -> str:
    """A short label for a device operation. The TPU profiler names an
    operation by its whole HLO instruction (``%fusion.250 = s32[23,1]{..}
    fusion(...)``); the label keeps its name, opcode and result shape (or
    ``tuple`` where it returns several)."""
    m = _HLO.match(name)
    if m:
        return f"{m[1]} ({m[3]} {m[2][:40]})"
    m = _HLO_TUPLE.match(name)
    if m:
        return f"{m[1]} ({m[2]} tuple)"
    return name[:80]


def self_times(events: list[Event]) -> list[tuple[Event, float]]:
    """Each event with its duration less that of the events nested in it
    (a ``while`` holds its body's operations on the same line)."""
    out, stack = [], []
    for e in sorted(events, key=lambda e: (e.start, -e.dur)):
        while stack and stack[-1][0].end <= e.start:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][1] -= min(e.end, stack[-1][0].end) - e.start
        stack.append([e, e.dur])
    out.extend(tuple(x) for x in reversed(stack))
    return out


class TraceView:
    """The traced window of one run: from the start of the first whole
    round span to the end of the last."""

    def __init__(self, events: list[Event]):
        self.events = events
        rounds = sorted((e for e in events if e.name == ROUND_SPAN),
                        key=lambda e: e.start)
        self.rounds = rounds
        if rounds:
            self.lo, self.hi = rounds[0].start, rounds[-1].end
            self.host_line = (rounds[0].plane, rounds[0].line)
        else:
            self.lo = self.hi = 0.0
            self.host_line = None
        planes = {e.plane for e in events if DEVICE_PLANE.match(e.plane)}
        self.devices = sorted(planes,
                              key=lambda p: int(DEVICE_PLANE.match(p)[1]))

    @property
    def window_ns(self) -> float:
        return self.hi - self.lo

    def _line(self, device: str, line: str) -> list[Event]:
        return [e for e in self.events if e.plane == device
                and e.line == line and e.end > self.lo and e.start < self.hi]

    def ops(self, device: str) -> list[Event]:
        """Device operations of ``device`` inside the window."""
        return self._line(device, OPS_LINE)

    def modules(self, device: str) -> list[Event]:
        """Whole compiled programs run on ``device`` inside the window."""
        return self._line(device, MODULES_LINE)

    def busy_ns(self, device: str, lo: float | None = None,
                hi: float | None = None) -> float:
        lo = self.lo if lo is None else lo
        hi = self.hi if hi is None else hi
        return union_ns([(e.start, e.end) for e in self.ops(device)], lo, hi)

    def host_events(self) -> list[Event]:
        """TraceMe spans of the thread that ran the rounds."""
        if self.host_line is None:
            return []
        plane, line = self.host_line
        return [e for e in self.events if e.plane == plane
                and e.line == line]

    def host_span_at(self, t: float, spans: list[Event]) -> str:
        """The innermost host span that covers time ``t``."""
        inside = [e for e in spans if e.start <= t < e.end]
        if not inside:
            return "(no host span)"
        return min(inside, key=lambda e: e.dur).name


def breakdown(view: TraceView, top: int = 10) -> dict:
    """The device operations that took most time (their own time, less
    the operations nested in them; mean over chips), and device idle time
    by the host span it fell in (mean over chips)."""
    n = max(1, len(view.devices))
    ops: dict[str, float] = {}
    idle: dict[str, float] = {}
    host = view.host_events()
    for d in view.devices:
        dev_ops = view.ops(d)
        for e, own in self_times(dev_ops):
            k = op_label(e.name)
            ops[k] = ops.get(k, 0.0) + own / 1e9 / n
        for a, b in gaps([(e.start, e.end) for e in dev_ops],
                         view.lo, view.hi):
            k = view.host_span_at((a + b) / 2, host)
            idle[k] = idle.get(k, 0.0) + (b - a) / 1e9 / n
    order = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in order(ops)],
            "idle_gaps": [[k, v] for k, v in order(idle)]}
