"""idle_unspanned_pct: the share of the window's device-idle time (mean
over chips) whose midpoint lies under no ``exec.*`` span of the executor:
idle while the harness, the client or nothing ran on the host."""

from bench.spans import idle_unspanned_pct


def read(rec):
    return idle_unspanned_pct(rec)
