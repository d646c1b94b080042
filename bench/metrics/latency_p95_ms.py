"""latency_p95_ms: the 95th percentile, over every transaction of the
window, of its round's duration (host clock, from the moment the round's
transactions sit in host memory to the moment the host holds their
verdicts). Every round carries the same transactions, so this is the 95th
percentile of the round durations (linear interpolation between ranks)."""

import numpy as np


def read(rec):
    if not rec.rounds:
        return None
    ms = [(r.end - r.start) * 1e3 for r in rec.rounds]
    return float(np.percentile(ms, 95))
