"""round_host_ms: per traced round, the round's wall time (the harness's
own ``bench.round`` span) less the time inside it in which the chip ran an
operation (mean over chips); the mean over the traced rounds. What is left
is the host loop: dispatch, transfers, waits and the counter read-back."""

import numpy as np


def read(rec):
    t = rec.trace
    if t is None or not t.devices or not t.rounds:
        return None
    host = []
    for r in t.rounds:
        busy = np.mean([t.busy_ns(d, r.start, r.end) for d in t.devices])
        host.append((r.dur - busy) / 1e6)
    return float(np.mean(host))
