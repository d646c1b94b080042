"""refresh_ms: device self time per chunk of the operations in the
``drain.refresh`` phase (the escrow share refresh of the drain program),
mean over chips. An operation's phase is read from the program's scope
table."""

from bench.spans import DRAIN_REFRESH, phase_ms


def read(rec):
    return phase_ms(rec, DRAIN_REFRESH)
