"""delivery_ms: device self time per chunk of the operations in the
``txn.delivery`` phase (Delivery), mean over chips. An operation's phase
is read from the program's scope table."""

from bench.spans import TXN_DELIVERY, phase_ms


def read(rec):
    return phase_ms(rec, TXN_DELIVERY)
