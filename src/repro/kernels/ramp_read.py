"""Pallas TPU kernel: fused RAMP read — metadata check + fracture detection
+ version-lookback select + per-query aggregation in one memory-bound pass.

The RAMP read path (txn/ramp.py) is bandwidth-bound: per query it streams the
commit-record metadata ([R] timestamps and sibling counts) and five [R, L]
line streams (stamps, committed-layer visibility, prepared-layer retention,
amounts, item ids), then reduces to the repaired selection and per-query
aggregates. Unfused, XLA materializes the need/match/fracture masks to HBM
between steps; fusing the whole decision tree into one kernel reads each
stream once and writes only the outputs — the same HBM-traffic rationale as
kernels/lattice_merge.py.

Grid: query-row blocks; each block holds [rows, L] line tiles in VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _ramp_read_kernel(req_ts_ref, nlines_ref, ol_ts_ref, ol_vis_ref,
                      ol_prep_ref, amount_ref, i_id_ref,
                      present_ref, amt_sel_ref, item_sel_ref,
                      amt_sum_ref, read_ref, rep_ref):
    req_ts = req_ts_ref[...]          # [r]
    nlines = nlines_ref[...]          # [r]
    ol_ts = ol_ts_ref[...]            # [r, L]
    vis = ol_vis_ref[...]             # [r, L]
    prep = ol_prep_ref[...]           # [r, L]
    amount = amount_ref[...]          # [r, L]
    i_id = i_id_ref[...]              # [r, L]

    line = jax.lax.broadcasted_iota(jnp.int32, ol_ts.shape, 1)
    need = line < nlines[:, None]
    match = ol_ts == req_ts[:, None]

    round1 = vis & match & need            # committed layer
    fractured = need & ~round1             # metadata says a sibling is missing
    repaired = fractured & (prep & match)  # 2nd round: local version lookback
    present = round1 | repaired

    present_ref[...] = present
    amt_sel_ref[...] = jnp.where(present, amount, 0.0)
    item_sel_ref[...] = jnp.where(present, i_id, -1)
    amt_sum_ref[...] = jnp.where(present, amount, 0.0).sum(axis=1)
    read_ref[...] = present.sum(axis=1).astype(jnp.int32)
    rep_ref[...] = repaired.sum(axis=1).astype(jnp.int32)


def ramp_read_kernel(req_ts, nlines, ol_ts, ol_vis, ol_prep, amount, i_id,
                     *, block_rows: int = 256, interpret: bool = False):
    """Fused RAMP line-set read over flattened queries.

    req_ts/nlines: [R]; ol_ts/ol_vis/ol_prep/amount/i_id: [R, L].
    Returns (present [R,L] bool, amount_sel [R,L], i_id_sel [R,L],
    amount_sum [R], lines_read [R] i32, repaired [R] i32).
    """
    R, L = ol_ts.shape
    block_rows = min(block_rows, R)
    assert R % block_rows == 0, (R, block_rows)
    n = R // block_rows

    row_spec = pl.BlockSpec((block_rows,), lambda i: (i,))
    line_spec = pl.BlockSpec((block_rows, L), lambda i: (i, 0))

    return pl.pallas_call(
        _ramp_read_kernel,
        grid=(n,),
        in_specs=[row_spec, row_spec, line_spec, line_spec, line_spec,
                  line_spec, line_spec],
        out_specs=[line_spec, line_spec, line_spec, row_spec, row_spec,
                   row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((R, L), jnp.bool_),
            jax.ShapeDtypeStruct((R, L), amount.dtype),
            jax.ShapeDtypeStruct((R, L), i_id.dtype),
            jax.ShapeDtypeStruct((R,), amount.dtype),
            jax.ShapeDtypeStruct((R,), jnp.int32),
            jax.ShapeDtypeStruct((R,), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(req_ts, nlines, ol_ts, ol_vis, ol_prep, amount, i_id)
