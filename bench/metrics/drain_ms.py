"""drain_ms: device time per chunk of the outbox drain and share refresh
(``_drain``, ``_drain_strict``, ``_drain_refresh``); the mean over chips of
each program run's duration."""

from bench.metrics_common import program_ms


def read(rec):
    return program_ms(rec, ("drain",))
