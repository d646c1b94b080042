"""device_idle_pct: the share of the traced window (first to last whole
round span) in which no operation ran on the chip; the mean over chips."""

import numpy as np


def read(rec):
    t = rec.trace
    if t is None or not t.devices or t.window_ns <= 0:
        return None
    busy = [t.busy_ns(d) / t.window_ns for d in t.devices]
    return 100.0 * (1.0 - float(np.mean(busy)))
