"""Helpers the metric readers share: device time of named programs and
kernels in a trace, per chunk or per launch."""

from __future__ import annotations

import re

import numpy as np

COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute")
KERNEL = re.compile(r"txn_megastep")


def per_chunk(rec, ns: float) -> float | None:
    """``ns`` of device time over the traced chunks, in ms."""
    if not rec.traced_chunks:
        return None
    return ns / 1e6 / rec.traced_chunks


def program_ms(rec, words) -> float | None:
    """Mean duration, in ms, of the runs of the compiled programs whose
    name holds one of ``words``, averaged over chips."""
    t = rec.trace
    if t is None:
        return None
    per_chip = []
    for d in t.devices:
        runs = [e.dur for e in t.modules(d)
                if any(w in e.name for w in words)]
        if runs:
            per_chip.append(float(np.mean(runs)) / 1e6)
    return float(np.mean(per_chip)) if per_chip else None


def kernel_ms(rec) -> float | None:
    """Mean duration, in ms, of the admission kernel's launches, averaged
    over chips; None where the kernel did not run."""
    t = rec.trace
    if t is None:
        return None
    per_chip = []
    for d in t.devices:
        runs = [e.dur for e in t.ops(d) if KERNEL.search(e.name)]
        if runs:
            per_chip.append(float(np.mean(runs)) / 1e6)
    return float(np.mean(per_chip)) if per_chip else None
