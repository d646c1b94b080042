"""admit_kernel_roofline: the admission kernel's share of its roofline, in
%: the bytes a step's order lines must touch, over the kernel's time per
step times the chip's HBM bandwidth. The kernel does no arithmetic worth
counting, so bandwidth is its bound.

Bytes, from the step's shapes alone and the same whatever implements
admission: per order line, its inputs (slot, quantity, validity, stock
cell, locality, remoteness: six int32), the availability cell read and
written (two int32) and the three stock counters read and written (six
int32); per transaction, its district key, gate verdict and residual
index read and its verdict and rank written (five int32)."""

from bench.metrics_common import kernel_ms

LINE_BYTES = 4 * (6 + 2 + 6)
TXN_BYTES = 4 * 5


def step_bytes(batch_per_chip: int, max_lines: int) -> int:
    return batch_per_chip * (max_lines * LINE_BYTES + TXN_BYTES)


def read(rec):
    ms = kernel_ms(rec)
    if ms is None or not ms or rec.peaks is None:
        return None
    b = step_bytes(rec.mix.neworders_per_step, rec.config["max_lines"])
    return 100.0 * b / (ms / 1e3 * rec.peaks.hbm_bytes_per_s)
