"""collective_ms: device time per chunk of the cross-chip collectives
(all-gather, all-reduce and their kin) that the drain and share refresh
run over ICI; the slowest chip's."""

from bench.metrics_common import COLLECTIVE, per_chunk


def read(rec):
    t = rec.trace
    if t is None or len(t.devices) < 2:
        return None
    per_chip = []
    for d in t.devices:
        ops = [e for e in t.ops(d) if COLLECTIVE.search(e.name)]
        if ops:
            per_chip.append(per_chunk(rec, sum(e.dur for e in ops)))
    return max(per_chip) if per_chip else None
