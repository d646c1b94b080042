"""Trace reduction: interval arithmetic on hand-made events, and the
readers on a small trace recorded on the chip."""

from pathlib import Path

import pytest

from bench import spec
from bench.harness import RunRecord
from bench.peaks import peaks_for
from bench.trace import (Event, TraceView, breakdown, gaps, load_events,
                         op_label, self_times, union_ns)
from bench.traffic.generator import load_mix

RECORDED = Path(__file__).resolve().parent / "fixtures" / \
    "trace_merge_small.json.gz"
DEV = "/device:TPU:0"
HOST = ("/host:CPU", "main/1")


def ev(plane, line, name, start, dur):
    return Event(plane, line, name, float(start), float(dur))


def test_union_and_gaps():
    iv = [(0, 10), (5, 20), (30, 40), (35, 38)]
    assert union_ns(iv, 0, 50) == 30
    assert union_ns(iv, 8, 32) == 14
    assert gaps(iv, 0, 50) == [(20, 30), (40, 50)]
    assert gaps([], 0, 5) == [(0, 5)]


def test_self_times_subtract_nested_ops():
    outer = ev(DEV, "XLA Ops", "%while.3 = s32[] while(", 0, 100)
    a = ev(DEV, "XLA Ops", "%fusion.1 = f32[8]{0} fusion(", 10, 30)
    b = ev(DEV, "XLA Ops", "%scatter.2 = s32[4]{0} scatter(", 50, 20)
    own = {e.name: t for e, t in self_times([b, outer, a])}
    assert own[outer.name] == 50 and own[a.name] == 30 and own[b.name] == 20


def test_op_label():
    assert op_label("%fusion.250 = s32[23,1]{0,1:T(1,128)S(1)} fusion(s32["
                    ) == "fusion.250 (fusion s32[23,1])"
    assert op_label("plain") == "plain"


def synthetic_view():
    """Two rounds of 100 ns; the chip busy 60 ns in the first, 30 in the
    second; one megastep and one drain program per round."""
    e = [ev(*HOST, "bench.round", 0, 100), ev(*HOST, "bench.round", 100, 100),
         ev(*HOST, "PjitFunction(broadcast_in_dim)", 0, 10),
         ev(DEV, "XLA Modules", "jit__megastep(1)", 10, 50),
         ev(DEV, "XLA Modules", "jit__drain(2)", 60, 10),
         ev(DEV, "XLA Modules", "jit__megastep(1)", 120, 20),
         ev(DEV, "XLA Modules", "jit__drain(2)", 150, 10),
         ev(DEV, "XLA Ops", "%a.1 = s32[] add(", 10, 50),
         ev(DEV, "XLA Ops", "%b.1 = s32[] add(", 60, 10),
         ev(DEV, "XLA Ops", "%all-gather.1 = s32[4]{0} all-gather(", 62, 4),
         ev(DEV, "XLA Ops", "%a.1 = s32[] add(", 120, 20),
         ev(DEV, "XLA Ops", "%b.1 = s32[] add(", 150, 10)]
    return TraceView(e)


def record(view, traced_chunks=2):
    return RunRecord(config={"max_lines": 15}, mix=None, setup_s=1.0,
                     window_s=1.0, rounds=[], counter_names=(), trace=view,
                     traced_chunks=traced_chunks)


def test_readers_on_synthetic_trace():
    rec = record(synthetic_view())
    read = lambda n: spec.load_reader(n)(rec)
    assert read("device_idle_pct") == pytest.approx(100 * (1 - 90 / 200))
    # round 1: 100 - 60 busy; round 2: 100 - 30 busy
    assert read("round_host_ms") == pytest.approx((40 + 70) / 2 / 1e6)
    assert read("megastep_ms") == pytest.approx(35 / 1e6)
    assert read("drain_ms") == pytest.approx(10 / 1e6)
    assert read("admit_kernel_ms") is None          # no kernel ran
    assert read("admit_kernel_roofline") is None
    assert read("collective_ms") is None            # one chip
    b = breakdown(rec.trace)
    assert b["device_ops"][0][0] == "a.1 (add s32[])"
    names = [k for k, _ in b["idle_gaps"]]
    assert "bench.round" in names
    assert "PjitFunction(broadcast_in_dim)" in names


def test_collectives_take_the_slowest_chip():
    base = synthetic_view().events
    dev1 = [e._replace(plane="/device:TPU:1") for e in base
            if e.plane == DEV]
    extra = [ev("/device:TPU:1", "XLA Ops",
                "%all-reduce.2 = s32[4]{0} all-reduce(", 64, 6)]
    rec = record(TraceView(base + dev1 + extra))
    # chip 0: 4 ns over 2 chunks; chip 1: 10 ns
    assert spec.load_reader("collective_ms")(rec) == pytest.approx(5e-6)


def test_readers_on_recorded_trace():
    """A few rounds of the merge cell, recorded on a TPU v5e."""
    view = TraceView(load_events(RECORDED))
    assert view.devices == [DEV] and len(view.rounds) >= 2
    rec = record(view, traced_chunks=len(view.rounds))
    idle = spec.load_reader("device_idle_pct")(rec)
    assert 0.0 < idle < 100.0
    mega = spec.load_reader("megastep_ms")(rec)
    drain = spec.load_reader("drain_ms")(rec)
    host = spec.load_reader("round_host_ms")(rec)
    assert mega > drain > 0 and host > 0
    round_ms = view.window_ns / len(view.rounds) / 1e6
    assert mega + drain < round_ms
    b = breakdown(view)
    assert len(b["device_ops"]) == 10 and b["idle_gaps"]


def test_readers_on_recorded_four_chip_trace():
    """One round of the escrow cell on four TPU v5e chips (operations
    under 20 us dropped, except collectives and the admission kernel)."""
    view = TraceView(load_events(RECORDED.with_name(
        "trace_escrow4_small.json.gz")))
    assert len(view.devices) == 4 and len(view.rounds) == 1
    rec = record(view, traced_chunks=1)
    assert spec.load_reader("collective_ms")(rec) > 0
    kernel = spec.load_reader("admit_kernel_ms")(rec)
    assert 0 < kernel < spec.load_reader("megastep_ms")(rec)
    rec.mix = load_mix("zipf1")
    rec.peaks = peaks_for("TPU v5 lite")
    share = spec.load_reader("admit_kernel_roofline")(rec)
    # 256 transactions x (15 lines x 56 bytes + 20 bytes) per step
    assert share == pytest.approx(
        100 * 256 * (15 * 56 + 20) / (kernel / 1e3 * 819e9))
    assert 0 < share < 100
