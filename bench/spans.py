"""The executor's own host spans and device phases in a trace, for the
metric readers: time under named host spans per round, device idle time
under no span of the program, and device time per phase per chunk.

Device phases come from the program's scope table (``repro.obs.trace.
scope_table()``: ``{module: {instruction: phase}}``), since a device trace
names an operation by its HLO instruction alone. The names below are
``repro.obs.trace``'s (a test holds them equal), spelled out here so that a
reader still loads, and returns None, against a program without them.
"""

from __future__ import annotations

import bisect
import re

from repro.obs import trace as program_trace

from .trace import gaps, op_label, self_times, union_ns

EXEC_PREFIX = "exec."
EXEC_CALL = "exec.call"
EXEC_PREPARE = "exec.prepare"
EXEC_MEGASTEP = "exec.megastep"
EXEC_DRAIN = "exec.drain"
EXEC_REFRESH = "exec.refresh"
EXEC_SYNC = "exec.sync"
EXEC_READBACK = "exec.readback"

TXN_NEWORDER = "txn.neworder"
TXN_PAYMENT = "txn.payment"
TXN_READS = "txn.reads"
TXN_DELIVERY = "txn.delivery"
DRAIN_APPLY = "drain.apply"
DRAIN_REFRESH = "drain.refresh"
UNSCOPED = "unscoped"
# the reader's own bucket: operations of a registered program that its scope
# table does not hold (a table of another executable than the one that ran)
MISSING = "missing"
# the phase readers read nothing where more than this share of a program's
# device time falls on instructions missing from its table
MAX_MISSING = 0.02

_FINGERPRINT = re.compile(r"\(\d+\)$")


def module_name(event_name: str) -> str:
    """A program's name in the ``XLA Modules`` line, fingerprint stripped
    (``jit__megastep(8930260749794128874)`` -> ``jit__megastep``)."""
    return _FINGERPRINT.sub("", event_name)


def instruction(event_name: str) -> str:
    """An operation's HLO instruction name, as ``op_label`` reads it."""
    return op_label(event_name).split(" ", 1)[0]


def _program_spans(view) -> list:
    return [e for e in view.host_events() if e.name.startswith(EXEC_PREFIX)]


def host_ms(rec, names) -> float | None:
    """Per traced round, ms of host time under the spans ``names`` (their
    union, within the window); None where the program emits no spans."""
    t = rec.trace
    if t is None or not t.rounds:
        return None
    spans = _program_spans(t)
    if not any(e.name == EXEC_CALL for e in spans):
        return None
    iv = [(e.start, e.end) for e in spans if e.name in names]
    return union_ns(iv, t.lo, t.hi) / 1e6 / len(t.rounds)


def idle_unspanned_pct(rec) -> float | None:
    """Share, in %, of the window's device-idle time whose midpoint lies
    under no ``exec.*`` span: idle while the harness, the client or nothing
    of the program ran on the host. Idle and unspanned time are each the
    mean over chips."""
    t = rec.trace
    if t is None or not t.devices or t.window_ns <= 0:
        return None
    spans = _program_spans(t)
    if not spans:
        return None
    # the spans' union as sorted disjoint intervals, for bisection
    merged = []
    for s, e in sorted((e.start, e.end) for e in spans):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    starts = [s for s, _ in merged]
    idle = unspanned = 0.0
    for d in t.devices:
        for a, b in gaps([(e.start, e.end) for e in t.ops(d)], t.lo, t.hi):
            mid = (a + b) / 2
            k = bisect.bisect_right(starts, mid) - 1
            idle += b - a
            if k < 0 or mid >= merged[k][1]:
                unspanned += b - a
    return 100.0 * unspanned / idle if idle > 0 else None


def scope_table(rec) -> dict | None:
    """The program's scope table: the record's own (``rec.scopes``, as a
    test sets it) or the one the program computes in this process; None
    where the program has none."""
    if not hasattr(rec, "scopes"):
        table = getattr(program_trace, "scope_table", None)
        rec.scopes = table() if table is not None else None
    return rec.scopes or None


def phase_times(rec) -> dict | None:
    """``{module: {phase: ms}}``: per traced chunk, the device self time of
    each registered program's operations by phase (``unscoped`` for those
    under no phase, ``missing`` for those the table lacks), mean over
    chips."""
    if hasattr(rec, "_phase_times"):
        return rec._phase_times
    t, table = rec.trace, scope_table(rec)
    out = None
    if t is not None and table and t.devices and rec.traced_chunks:
        out = {}
        scale = 1e6 * rec.traced_chunks * len(t.devices)
        for d in t.devices:
            mods = sorted(t.modules(d), key=lambda e: e.start)
            starts = [m.start for m in mods]
            for e, own in self_times(t.ops(d)):
                k = bisect.bisect_right(starts, e.start) - 1
                if k < 0 or e.start >= mods[k].end:
                    continue
                module = module_name(mods[k].name)
                scopes = table.get(module)
                if scopes is None:
                    continue
                phase = scopes.get(instruction(e.name), MISSING)
                acc = out.setdefault(module, {})
                acc[phase] = acc.get(phase, 0.0) + own / scale
    rec._phase_times = out
    return out


def phase_ms(rec, phase: str) -> float | None:
    """Per traced chunk, ms of device self time in ``phase`` over every
    program, mean over chips; None where no program holds the phase, or
    where the table misses more than :data:`MAX_MISSING` of any program's
    time (it then describes another executable than the one that ran)."""
    times, table = phase_times(rec), scope_table(rec)
    if times is None or not any(phase in s.values() for s in table.values()):
        return None
    if any(p.get(MISSING, 0.0) > MAX_MISSING * sum(p.values())
           for p in times.values()):
        return None
    return sum(p.get(phase, 0.0) for p in times.values())
