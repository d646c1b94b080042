"""Pallas TPU kernel: fused VersionedSlots merge (⊔) + invariant audit.

The anti-entropy hot spot of the database substrate is memory-bound: read two
versioned tables, keep the higher-version row, OR the valid masks, and check
a row-level threshold invariant — five streams in, three streams + a mask
out. Fusing the join with the invariant check halves HBM traffic vs the
two-pass jnp formulation (merge, then audit), which is exactly the kind of
bandwidth win the roofline's memory term rewards.

Grid: row blocks; each block is a [rows_per_block, width] VMEM tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _merge_kernel(av_ref, ar_ref, ap_ref, bv_ref, br_ref, bp_ref,
                  ov_ref, or_ref, op_ref, viol_ref, *, lo: float, hi: float):
    a_valid = av_ref[...]
    b_valid = bv_ref[...]
    a_ver = ar_ref[...]
    b_ver = br_ref[...]
    a_pay = ap_ref[...]
    b_pay = bp_ref[...]

    b_newer = b_ver > a_ver
    valid = a_valid | b_valid
    version = jnp.maximum(a_ver, b_ver)
    payload = jnp.where(b_newer[:, None], b_pay, a_pay)

    bad = (payload < lo) | (payload > hi)
    viol = valid & jnp.any(bad, axis=1)

    ov_ref[...] = valid
    or_ref[...] = version
    op_ref[...] = payload
    viol_ref[...] = viol


def lattice_merge_kernel(a_valid, a_ver, a_pay, b_valid, b_ver, b_pay,
                         lo: float, hi: float, *, block_rows: int = 256,
                         interpret: bool = False):
    """Row-wise join of two versioned tables + threshold audit.

    a/b_valid: [R] bool; a/b_ver: [R] int; a/b_pay: [R, W] float.
    Returns (valid, version, payload, violation_mask).
    """
    R, W = a_pay.shape
    block_rows = min(block_rows, R)
    assert R % block_rows == 0, (R, block_rows)
    n = R // block_rows

    row_spec = pl.BlockSpec((block_rows,), lambda i: (i,))
    pay_spec = pl.BlockSpec((block_rows, W), lambda i: (i, 0))

    return pl.pallas_call(
        functools.partial(_merge_kernel, lo=lo, hi=hi),
        grid=(n,),
        in_specs=[row_spec, row_spec, pay_spec, row_spec, row_spec, pay_spec],
        out_specs=[row_spec, row_spec, pay_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((R,), a_valid.dtype),
            jax.ShapeDtypeStruct((R,), a_ver.dtype),
            jax.ShapeDtypeStruct((R, W), a_pay.dtype),
            jax.ShapeDtypeStruct((R,), jnp.bool_),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(a_valid, a_ver, a_pay, b_valid, b_ver, b_pay)
