"""End-to-end metric arithmetic: txn_s and the p95 of rounds."""

import numpy as np
import pytest

from bench import spec
from bench.harness import Round, RunRecord
from bench.reference.tpcc import COUNTERS


def rounds(durations, counts):
    out, t = [], 0.0
    for d in durations:
        out.append(Round(t, t + d, 0.0, np.array(counts, np.int64), 1))
        t += d
    return out


def counts(neworders=0, aborts=0, payments=0, os_=0, sl=0, deliveries=0):
    c = dict.fromkeys(COUNTERS, 0)
    c.update(neworders=neworders, aborts=aborts, payments=payments,
             order_statuses=os_, stock_levels=sl, deliveries=deliveries)
    return [c[k] for k in COUNTERS]


def rec(rs, window_s, setup_s=30.0):
    return RunRecord(config={}, mix=None, setup_s=setup_s,
                     window_s=window_s, rounds=rs, counter_names=COUNTERS)


def test_txn_s_counts_every_decision():
    c = counts(neworders=100, aborts=156, payments=256, os_=23, sl=23,
               deliveries=250)
    r = rec(rounds([0.05] * 4, c), window_s=0.25)
    # Stock-Levels are read but never answered, so they decide nothing
    per_round = 100 + 156 + 256 + 23 + 25.0
    assert spec.load_reader("txn_s")(r) == pytest.approx(
        4 * per_round / 0.25)


def test_p95_of_rounds():
    durations = [0.010 * (i + 1) for i in range(100)]   # 10 .. 1000 ms
    r = rec(rounds(durations, counts(neworders=1)), window_s=50.5)
    p95 = spec.load_reader("latency_p95_ms")(r)
    assert p95 == pytest.approx(np.percentile(np.arange(1, 101) * 10.0, 95))
    assert 950.0 <= p95 <= 960.0


def test_setup_and_empty_windows():
    r = rec([], window_s=0.0, setup_s=42.5)
    assert spec.load_reader("setup_s")(r) == 42.5
    assert spec.load_reader("txn_s")(r) is None
    assert spec.load_reader("latency_p95_ms")(r) is None
