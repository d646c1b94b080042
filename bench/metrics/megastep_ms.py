"""megastep_ms: device time per chunk of the executor's jitted megastep
(``_megastep`` or ``_megastep_escrow``: New-Order admission and effects,
Payment, the RAMP reads and Delivery for every step of the chunk); the
mean over chips of each program run's duration."""

from bench.metrics_common import program_ms


def read(rec):
    return program_ms(rec, ("megastep",))
