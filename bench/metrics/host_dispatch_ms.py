"""host_dispatch_ms: per traced round, host time under the executor's
``exec.megastep``, ``exec.drain`` and ``exec.refresh`` spans: each
program's dispatch, the chunk's upload included."""

from bench.spans import EXEC_DRAIN, EXEC_MEGASTEP, EXEC_REFRESH, host_ms


def read(rec):
    return host_ms(rec, (EXEC_MEGASTEP, EXEC_DRAIN, EXEC_REFRESH))
