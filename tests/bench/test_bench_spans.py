"""The readers of the executor's own spans and phases (``bench/spans.py``
and its nine metrics): known answers on a hand-made trace, None where the
program has no spans or no scope table, and the readers that came before
them unchanged on the two recorded chip traces."""

import gzip
import json
from pathlib import Path

import pytest

from bench import spec
from bench.harness import RunRecord
from bench.peaks import peaks_for
from bench.spans import MISSING, instruction, module_name, phase_times
from bench.trace import Event, TraceView, load_events
from bench.traffic.generator import load_mix

FIXTURES = Path(__file__).resolve().parent / "fixtures"
DEV = "/device:TPU:0"
HOST = ("/host:CPU", "python3")
HOST_METRICS = ("host_prepare_ms", "host_dispatch_ms", "host_sync_ms",
                "idle_unspanned_pct")
PHASE_METRICS = ("neworder_ms", "payment_ms", "reads_ms", "delivery_ms",
                 "refresh_ms")
SCOPES = {"jit__megastep": {"while.1": "unscoped",
                            "fusion.1": "txn.neworder",
                            "fusion.2": "txn.payment",
                            "copy.3": "unscoped",
                            "fusion.4": "txn.reads"},
          "jit__drain": {"fusion.9": "drain.apply"}}


def ev(plane, line, name, start, dur):
    return Event(plane, line, name, float(start), float(dur))


def host(name, start, end):
    return ev(*HOST, name, start, end - start)


def op(name, start, end):
    return ev(DEV, "XLA Ops", f"%{name} = s32[8]{{0}} x(", start, end - start)


def module(name, start, end):
    return ev(DEV, "XLA Modules", name, start, end - start)


def synthetic_events():
    """Two rounds of one chunk. Round 1: prepare 10, dispatch 5 + 5,
    sync 65; round 2: prepare 5, dispatch 2 + 2, sync 51, readback 5.
    The chip idles [0, 8) and [155, 200) outside every exec.* span."""
    return [
        host("bench.round", 0, 100), host("bench.round", 100, 200),
        host("exec.call", 5, 96), host("exec.prepare", 5, 15),
        host("exec.megastep", 15, 20), host("exec.drain", 20, 25),
        host("exec.sync", 25, 90),
        host("exec.call", 105, 170), host("exec.prepare", 105, 110),
        host("exec.megastep", 110, 112), host("exec.drain", 112, 114),
        host("exec.sync", 114, 165), host("exec.readback", 165, 170),
        host("PjitFunction(convert_element_type)", 8, 10),
        module("jit_convert_element_type(7)", 8, 10),
        op("convert.1", 8, 10),
        module("jit__megastep(11)", 20, 60), op("while.1", 20, 60),
        op("fusion.1", 22, 40), op("fusion.2", 40, 50),
        op("copy.3", 50, 58),
        module("jit__drain(12)", 62, 70), op("fusion.9", 62, 70),
        module("jit__megastep(11)", 120, 140), op("while.1", 120, 140),
        op("fusion.1", 121, 131), op("fusion.4", 131, 136),
        module("jit__drain(12)", 150, 155), op("fusion.9", 150, 155)]


def record(events, traced_chunks=2, scopes=SCOPES):
    rec = RunRecord(config={"max_lines": 15}, mix=None, setup_s=1.0,
                    window_s=1.0, rounds=[], counter_names=(),
                    trace=TraceView(events), traced_chunks=traced_chunks)
    rec.scopes = scopes
    return rec


def read(rec, name):
    return spec.load_reader(name)(rec)


def test_names_from_trace_events():
    assert module_name("jit__megastep(8930260749794128874)") == \
        "jit__megastep"
    assert instruction("%fusion.37 = s32[122880]{0} fusion(s32[") == \
        "fusion.37"
    assert instruction("%tuple.2 = (s32[], f32[]) tuple(") == "tuple.2"


def test_host_readers_on_synthetic_trace():
    rec = record(synthetic_events())
    assert read(rec, "host_prepare_ms") == pytest.approx((10 + 5) / 2e6)
    assert read(rec, "host_dispatch_ms") == pytest.approx((10 + 4) / 2e6)
    assert read(rec, "host_sync_ms") == pytest.approx((65 + 56) / 2e6)
    # idle: [0,8) [10,20) [60,62) [70,120) [140,150) [155,200) = 125 ns,
    # of which [0,8) and [155,200) lie under no exec.* span
    assert read(rec, "idle_unspanned_pct") == pytest.approx(100 * 53 / 125)


def test_phase_readers_on_synthetic_trace():
    rec = record(synthetic_events())
    assert read(rec, "neworder_ms") == pytest.approx((18 + 10) / 2e6)
    assert read(rec, "payment_ms") == pytest.approx(10 / 2e6)
    assert read(rec, "reads_ms") == pytest.approx(5 / 2e6)
    assert read(rec, "delivery_ms") is None      # no such scope
    assert read(rec, "refresh_ms") is None
    times = phase_times(rec)
    # while.1's own time (4, then 5) and copy.3's 8 are the unscoped rest
    assert times["jit__megastep"]["unscoped"] == pytest.approx(17 / 2e6)
    assert times["jit__drain"] == {"drain.apply": pytest.approx(13 / 2e6)}
    assert "jit_convert_element_type" not in times
    # the four phases and the unscoped rest make up the megastep's time
    mega = sum(times["jit__megastep"].values())
    assert mega == pytest.approx(read(rec, "megastep_ms"))


def test_phase_readers_refuse_a_table_of_another_program():
    """Operations the table lacks go to their own bucket, not to the
    unscoped rest; past MAX_MISSING of a program's time no phase is read."""
    scopes = {m: dict(s) for m, s in SCOPES.items()}
    del scopes["jit__megastep"]["copy.3"]       # 8 of the megastep's 68
    rec = record(synthetic_events(), scopes=scopes)
    assert phase_times(rec)["jit__megastep"][MISSING] == \
        pytest.approx(8 / 2e6)
    for name in PHASE_METRICS:
        assert read(rec, name) is None, name
    # a share under the bound leaves the readers as they were
    rec = record(synthetic_events(), scopes=scopes)
    rec._phase_times = {"jit__megastep": {"txn.neworder": 98.5,
                                          MISSING: 1.5}}
    assert read(rec, "neworder_ms") == 98.5


def test_readers_without_spans_or_table():
    """A program that emits no exec.* spans and has no scope table: every
    new reader returns None and makes up no number."""
    bare = [e for e in synthetic_events() if not e.name.startswith("exec.")]
    rec = record(bare, scopes=None)
    for name in HOST_METRICS + PHASE_METRICS:
        assert read(rec, name) is None, name


def test_phases_mean_over_chips():
    base = synthetic_events()
    # chip 1 runs the same programs, of which only fusion.1 shows
    dev1 = [e._replace(plane="/device:TPU:1") for e in base
            if e.plane == DEV and (e.line == "XLA Modules"
                                   or "fusion.1 " in e.name)]
    rec = record(base + dev1)
    assert read(rec, "neworder_ms") == pytest.approx(28 / 2e6)
    assert read(rec, "payment_ms") == pytest.approx(10 / 2 / 2e6)


PRIOR = {
    "trace_merge_small": {
        "round_host_ms": 22.644395333333335,
        "device_idle_pct": 44.991955100007765,
        "megastep_ms": 26.183354, "drain_ms": 1.5667856666666669,
        "admit_kernel_ms": None, "admit_kernel_roofline": None,
        "collective_ms": None},
    "trace_escrow4_small": {
        "round_host_ms": 44.234417, "device_idle_pct": 51.97514682217814,
        "megastep_ms": 18.88345325, "drain_ms": 22.32708525,
        "admit_kernel_ms": 0.76877075,
        "admit_kernel_roofline": 0.03496694285203083,
        "collective_ms": 0.045505},
}


@pytest.mark.parametrize("fixture", sorted(PRIOR))
def test_prior_readers_unchanged_on_recorded_traces(fixture):
    """The readers of the first benchmark read what they read before; the
    new ones read nothing from traces recorded before the spans."""
    view = TraceView(load_events(FIXTURES / f"{fixture}.json.gz"))
    chunks = 1 if len(view.devices) > 1 else len(view.rounds)
    rec = record(view.events, traced_chunks=chunks, scopes=None)
    rec.mix = load_mix("zipf1")
    rec.peaks = peaks_for("TPU v5 lite")
    for name, value in PRIOR[fixture].items():
        got = read(rec, name)
        assert got == (value if value is None else pytest.approx(value)), \
            name
    for name in HOST_METRICS + PHASE_METRICS:
        assert read(rec, name) is None, name


def recorded(name):
    """A chip trace with the executor's spans and the scope table of the
    programs that ran, cut to the instructions the trace holds."""
    with gzip.open(FIXTURES / f"scopes_{name}_spans.json.gz", "rt") as f:
        scopes = json.load(f)
    return TraceView(load_events(FIXTURES / f"trace_{name}_spans.json.gz")), \
        scopes


def test_readers_on_recorded_trace_with_spans():
    """Three rounds of the merge cell, recorded on a TPU v5e."""
    view, scopes = recorded("merge")
    assert len(view.rounds) == 3
    rec = record(view.events, traced_chunks=3, scopes=scopes)
    got = {n: read(rec, n) for n in HOST_METRICS + PHASE_METRICS}
    assert got == pytest.approx({
        "host_prepare_ms": 16.416453666666666,
        "host_dispatch_ms": 3.3034606666666666,
        "host_sync_ms": 27.837202666666666,
        "idle_unspanned_pct": 0.0,
        "neworder_ms": 5.917175333333363, "payment_ms": 0.8666146666666662,
        "reads_ms": 0.5488533333333324, "delivery_ms": 3.7245163333333333,
        "refresh_ms": None})
    # the table holds every instruction that ran, so the four phases and
    # the unscoped rest (the ORDER-LINE copies, the scan's loop) make up
    # the megastep program's time within 2%
    times = phase_times(rec)
    assert all(MISSING not in p for p in times.values())
    mega = read(rec, "megastep_ms")
    assert sum(times["jit__megastep"].values()) == pytest.approx(mega,
                                                                 rel=0.02)
    assert sum(times["jit__drain"].values()) == pytest.approx(
        read(rec, "drain_ms"), rel=0.02)
    # the host spans of a round cover it but for the harness's own work
    round_ms = view.window_ns / 3 / 1e6
    host = sum(got[n] for n in HOST_METRICS[:3])
    assert 0.9 * round_ms < host < round_ms


def test_readers_on_recorded_four_chip_trace_with_spans():
    """One round of the escrow cell on four TPU v5e chips (operations under
    20 us dropped, except collectives and the admission kernel, so the
    reads and Delivery, all short, fall out)."""
    view, scopes = recorded("escrow4")
    assert len(view.devices) == 4 and len(view.rounds) == 1
    rec = record(view.events, traced_chunks=1, scopes=scopes)
    assert read(rec, "refresh_ms") == pytest.approx(0.394482)
    assert read(rec, "neworder_ms") == pytest.approx(11.964059250000018)
    assert read(rec, "reads_ms") is None and read(rec, "delivery_ms") is None
    drain = read(rec, "drain_ms")
    assert read(rec, "refresh_ms") < drain
    assert all(MISSING not in p for p in phase_times(rec).values())
    assert sum(phase_times(rec)["jit__drain_refresh"].values()) == \
        pytest.approx(drain, rel=0.02)
    # the admission kernel keeps its name and its launches
    assert read(rec, "admit_kernel_ms") == pytest.approx(0.77445828125)
    for name in HOST_METRICS:
        assert read(rec, name) > 0, name
