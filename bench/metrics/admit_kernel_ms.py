"""admit_kernel_ms: device time per step of the admission kernel (the
``txn_megastep`` Pallas call, body ``_txn_megastep_body``): the kernel's
traced time over its launches, mean over chips."""

from bench.metrics_common import kernel_ms


def read(rec):
    return kernel_ms(rec)
