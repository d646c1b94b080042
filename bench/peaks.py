"""Published peaks of each accelerator the benchmark may run on, keyed by
the ``device_kind`` JAX reports.

TPU v5e: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/
v5e): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s
of inter-chip interconnect per chip. JAX names the chip "TPU v5 lite".

A kind missing from the table is an error, never a default.
"""

from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    bf16_flops: float     # FLOP/s
    int8_ops: float       # OP/s
    hbm_bytes: float      # bytes of HBM per chip
    hbm_bytes_per_s: float
    ici_bits_per_s: float
    source: str


_V5E = Peaks(bf16_flops=197e12, int8_ops=393e12, hbm_bytes=16e9,
             hbm_bytes_per_s=819e9, ici_bits_per_s=1600e9,
             source="Google Cloud documentation, 'TPU v5e'")

PEAKS: dict[str, Peaks] = {
    "TPU v5 lite": _V5E,
}


class UnknownDevice(KeyError):
    pass


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
