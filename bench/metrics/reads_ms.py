"""reads_ms: device self time per chunk of the operations in the
``txn.reads`` phase (the RAMP Order-Status and Stock-Level reads), mean
over chips. An operation's phase is read from the program's scope table."""

from bench.spans import TXN_READS, phase_ms


def read(rec):
    return phase_ms(rec, TXN_READS)
