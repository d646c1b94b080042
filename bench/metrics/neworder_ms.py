"""neworder_ms: device self time per chunk of the operations in the
``txn.neworder`` phase (New-Order admission, effects and the ring write,
in both regimes), mean over chips. An operation's phase is read from the
program's scope table."""

from bench.spans import TXN_NEWORDER, phase_ms


def read(rec):
    return phase_ms(rec, TXN_NEWORDER)
