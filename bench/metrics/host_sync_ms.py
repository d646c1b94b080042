"""host_sync_ms: per traced round, host time under the executor's
``exec.sync`` (the final ``block_until_ready``) and ``exec.readback`` (the
cold-reject and retry-ring reads) spans."""

from bench.spans import EXEC_READBACK, EXEC_SYNC, host_ms


def read(rec):
    return host_ms(rec, (EXEC_SYNC, EXEC_READBACK))
