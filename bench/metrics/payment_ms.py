"""payment_ms: device self time per chunk of the operations in the
``txn.payment`` phase (Payment), mean over chips. An operation's phase
is read from the program's scope table."""

from bench.spans import TXN_PAYMENT, phase_ms


def read(rec):
    return phase_ms(rec, TXN_PAYMENT)
