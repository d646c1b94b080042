"""host_prepare_ms: per traced round, host time under the executor's
``exec.prepare`` spans: ``shard_state``, the ring, counter and retry
buffers built for the call."""

from bench.spans import EXEC_PREPARE, host_ms


def read(rec):
    return host_ms(rec, (EXEC_PREPARE,))
