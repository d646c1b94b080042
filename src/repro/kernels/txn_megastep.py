"""Pallas TPU kernel: the one-kernel transaction megastep — admission +
committed effects in a single VMEM residency, RAMP stamping beside it.

PR 5 made the closed loop *effects-bound*: the two-level admission wins
2-2.4x in the micro, but the committed-effect application — the per-district
o_id rank, the district counter advance, the stock slab scatter-adds and the
order/order-line inserts — still round-trips the hot state through HBM once
per phase, erasing the win end-to-end. This kernel fuses the four phases of
the strict-stock New-Order hot path over ONE residency of the hot tiles:

  phase 1 — contention gate (kernels/escrow_admit.contention_gate, pure jnp
            outside the kernel: one segmented sum classifies every
            transaction; the monotone majority commits order-free);
  phase 2 — residual FCFS admission: the `escrow_admit` walk verbatim, with
            the availability vector resident in VMEM (dynamic trip count =
            the contended handful);
  phase 3 — committed effects, one pass over the batch in FCFS order while
            `avail` is STILL resident: the fast path's reservations settle
            in-place (so `avail` leaves the kernel fully settled, exactly
            `admit_fcfs`'s contract), each transaction picks up its
            committed per-district rank from an SMEM counter (the
            batched increment-and-get), and the three stock slabs
            (decrement / order count / remote count) accumulate in VMEM
            instead of three whole-table HBM scatter passes;
  phase 4 — RAMP stamping, vectorized jnp over the whole [B, L] window
            after the kernel (elementwise, so XLA fuses it): the write-set
            timestamp (`ol_ts`) and the line amounts from the pre-gathered
            price row.

The kernel returns effect PRODUCTS (rank, per-district counts, stock slabs,
stamps), not mutated tables: the caller (txn/tpcc.py
``_neworder_fused_effects``) lands them with dense vector adds and the
unchanged order/order-line row scatters, which keeps the kernel's working
set to the hot tiles and leaves the big append-mostly tables on their
existing one-scatter-per-row path. Bit-exactness with the sequential scan
path is the contract, phase by phase:

  * rank / d_count — integer counting in batch order, identical to the
    ``[B, B]`` committed-rank matrix of the scan path by construction;
  * stock slabs — integer segment sums; scatter-add order cannot matter.
    (s_ytd is f32 in the tables, but its addends are integers and TPC-C
    year-to-date totals sit far below 2**24, where f32 integer sums are
    exact in any association.)
  * stamps — the same elementwise formulas as the scan path.

``megastep_effect_products`` is the vectorized CPU lowering of phases 3-4
(sort-based rank + ONE stacked [N, 3] segment sum for the three slabs) —
interpret-mode Pallas pays ~100x per load/store, so off-TPU dispatch
(ops.txn_megastep) runs the gate + `residual_fcfs` + this, bit-exact with
the kernel (whose interpret-mode path the tests pin against the oracle).

Memory placement: VMEM holds avail [A] and the three stock slabs [Wl*I],
each as ``[rows, 128]`` int32 (escrow_admit.to_lanes) — 16 bytes per local
stock cell, about 16 MB at 10 local warehouses, the most one v5e core's
default scoped VMEM takes (tests/test_tpu_compile.py: 11 is refused;
streaming the slabs over a grid lifts the limit). SMEM holds the scalars:
the per-transaction residual order / verdicts / keys / ranks, the
per-district counters, and the flat [B * L] line arrays.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .escrow_admit import (HBM, LANES, SMEM, VMEM, copy_smem, fcfs_walk,
                           flat_i32, lane_add, lane_rows, to_lanes)

Array = jax.Array


class MegastepOut(NamedTuple):
    """The megastep's effect products (identical for kernel / CPU lowering /
    oracle — the caller lands them on the tables the same way either way)."""

    committed: Array   # [B] bool — FCFS admission verdicts
    avail: Array       # [A] int32 — fully settled availability vector
    rank: Array        # [B] int32 — committed rank within the (w, d) key
    d_count: Array     # [n_keys] int32 — committed txns per district key
    stock_dec: Array   # [n_cells] int32 — admitted decrement per local cell
    stock_cnt: Array   # [n_cells] int32 — admitted order lines per cell
    stock_rcnt: Array  # [n_cells] int32 — admitted remote lines per cell
    ol_ts: Array       # [B, L] int32 — RAMP write-set timestamp stamp
    amount: Array      # [B, L] f32 — order-line amounts (price x qty)


def megastep_effect_products(committed: Array, qty: Array, line_valid: Array,
                             key_local: Array, cell_local: Array,
                             local_line: Array, remote_line: Array,
                             ramp_ts: Array, price_row: Array, *,
                             n_keys: int, n_cells: int
                             ) -> tuple[Array, ...]:
    """Phases 3-4 as vectorized jnp — the CPU lowering of the kernel's
    effect walk (admission happens upstream; see ops.txn_megastep).

    * rank: sort-based committed prefix count per ``key_local`` group — a
      stable argsort + segmented exclusive cumsum replaces the scan path's
      ``[B, B]`` rank matrix (O(B log B) work instead of O(B^2));
    * d_count: one segment sum of the commit mask over district keys;
    * stock slabs: ONE stacked ``[N, 3]`` segment sum shares the admitted
      line ids across the decrement / count / remote-count slabs (one
      sort-free pass instead of three scatter-adds);
    * stamps: the scan path's elementwise formulas verbatim.

    Returns (rank, d_count, stock_dec, stock_cnt, stock_rcnt, ol_ts,
    amount) — the MegastepOut tail.
    """
    B, _ = qty.shape
    c32 = committed.astype(jnp.int32)

    # committed rank among earlier same-key txns, via one stable sort:
    # within a key group (contiguous after the sort) the rank is the
    # group-local exclusive cumsum of the commit mask
    order = jnp.argsort(key_local, stable=True)
    ks = key_local[order]
    cs = c32[order]
    excl = jnp.cumsum(cs) - cs
    start = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), ks[1:] != ks[:-1]])
    last_start = jax.lax.cummax(jnp.where(start, jnp.arange(B), 0))
    rank = jnp.zeros((B,), jnp.int32).at[order].set(
        (excl - excl[last_start]).astype(jnp.int32))

    d_count = jax.ops.segment_sum(c32, key_local, num_segments=n_keys)

    # stacked slab aggregation: admitted local lines only; masked-out lines
    # redirect to cell 0 adding 0 (exact for integer sums)
    m = committed[:, None] & local_line
    ids = jnp.where(m, cell_local, 0).reshape(-1)
    vals = jnp.stack([jnp.where(m, qty, 0).reshape(-1),
                      jnp.where(m, 1, 0).reshape(-1),
                      jnp.where(m & remote_line, 1, 0).reshape(-1)],
                     axis=1).astype(jnp.int32)
    slabs = jax.ops.segment_sum(vals, ids, num_segments=n_cells)

    ol_ts = jnp.where(line_valid, ramp_ts[:, None], -1).astype(jnp.int32)
    amount = jnp.where(line_valid,
                       price_row * qty.astype(price_row.dtype), 0.0)
    return (rank, d_count, slabs[:, 0], slabs[:, 1], slabs[:, 2], ol_ts,
            amount)


def _txn_megastep_body(n_res_ref, res_idx_ref, slot_ref, qty_ref, lv_ref,
                       fast_ref, key_ref, cell_ref, loc_ref, rem_ref,
                       avail0_hbm,
                       committed_ref, rank_ref, dcnt_ref,
                       avail_ref, dec_ref, cnt_ref, rcnt_ref):
    """Phases 2-3 over one VMEM residency of the hot tiles. ``avail_ref``
    doubles as the running reservation state across both phases;
    ``dcnt_ref`` (SMEM) doubles as the per-district increment-and-get
    counter."""
    copy_smem(fast_ref, committed_ref)
    pltpu.sync_copy(avail0_hbm, avail_ref)

    def zero_keys(k, carry):
        dcnt_ref[k] = 0
        return carry

    jax.lax.fori_loop(0, dcnt_ref.shape[0], zero_keys, 0)
    for ref in (dec_ref, cnt_ref, rcnt_ref):
        ref[...] = jnp.zeros(ref.shape, jnp.int32)
    B = committed_ref.shape[0]
    L = slot_ref.shape[0] // B

    # ---- phase 2: residual FCFS (the escrow_admit walk, verbatim) ----------
    fcfs_walk(n_res_ref, res_idx_ref, slot_ref, qty_ref, lv_ref,
              committed_ref, avail_ref)

    # ---- phase 3: committed effects, batch order, avail still resident -----
    def effect_txn(t, carry):
        c = committed_ref[t]
        fast_t = fast_ref[t]
        # per-district increment-and-get: rank is the count of committed
        # earlier same-key txns (stored for every txn, like the scan path —
        # aborted rows' o_ids are computed there too and dropped downstream)
        key = key_ref[t]
        kcnt = dcnt_ref[key]
        rank_ref[t] = kcnt
        dcnt_ref[key] = kcnt + c

        def line(j, carry):
            q = qty_ref[j]

            # settle the fast path's reservation in-place: avail leaves the
            # kernel fully settled (admit_fcfs's contract), no outside
            # scatter needed
            @pl.when((lv_ref[j] != 0) & (fast_t != 0))
            def _():
                lane_add(avail_ref, slot_ref[j], -q)

            # stock slabs: admitted local lines only
            @pl.when((c != 0) & (loc_ref[j] != 0))
            def _():
                cell = cell_ref[j]
                lane_add(dec_ref, cell, q)
                lane_add(cnt_ref, cell, 1)
                lane_add(rcnt_ref, cell, rem_ref[j])
            return carry

        jax.lax.fori_loop(t * L, t * L + L, line, 0)
        return carry

    jax.lax.fori_loop(0, B, effect_txn, 0)


@functools.partial(jax.jit, static_argnames=("n_keys", "n_cells", "interpret"))
def txn_megastep_kernel(avail0: Array, slot: Array, qty: Array,
                        line_valid: Array, fast: Array, res_idx: Array,
                        n_res: Array, key_local: Array, cell_local: Array,
                        local_line: Array, remote_line: Array,
                        ramp_ts: Array, price_row: Array, *,
                        n_keys: int, n_cells: int,
                        interpret: bool = False) -> MegastepOut:
    """The fused megastep (phases 2-3 in the kernel; the gate runs before
    it and the phase-4 stamps after it as vectorized jnp). ``avail0`` [A]
    int32; ``slot``/``qty``/``line_valid`` [B, L]; ``fast``/``res_idx``/
    ``n_res`` from the gate + residual_order; ``key_local`` [B] district
    keys in [0, n_keys); ``cell_local`` [B, L] local stock cells in
    [0, n_cells) (masked by ``local_line``); ``remote_line`` [B, L];
    ``ramp_ts`` [B] int32; ``price_row`` [B, L] f32.

    Returns :class:`MegastepOut` with ``avail`` FULLY settled (fast +
    residual reservations — bit-identical to ``admit_fcfs``'s output).

    Layout: ``avail`` and the three stock slabs ride in ``[rows, 128]``
    int32 (escrow_admit.to_lanes), so every per-line access is one
    tile-aligned row; per-transaction and per-line scalars, the verdicts,
    ranks and district counters live in SMEM.
    """
    B, L = slot.shape
    A = avail0.shape[0]
    lanes = to_lanes(avail0.astype(jnp.int32))
    slab = jax.ShapeDtypeStruct((lane_rows(n_cells), LANES), jnp.int32)
    committed, rank, d_count, avail, dec, cnt, rcnt = pl.pallas_call(
        _txn_megastep_body,
        in_specs=[SMEM] * 10 + [HBM],
        out_specs=[SMEM] * 3 + [VMEM] * 4,
        out_shape=[jax.ShapeDtypeStruct((B,), jnp.int32),
                   jax.ShapeDtypeStruct((B,), jnp.int32),
                   jax.ShapeDtypeStruct((n_keys,), jnp.int32),
                   jax.ShapeDtypeStruct(lanes.shape, jnp.int32),
                   slab, slab, slab],
        interpret=interpret,
    )(n_res, res_idx, flat_i32(slot), flat_i32(qty), flat_i32(line_valid),
      flat_i32(fast), key_local.astype(jnp.int32), flat_i32(cell_local),
      flat_i32(local_line), flat_i32(remote_line), lanes)
    cells = lambda x: x.reshape(-1)[:n_cells]
    # ---- phase 4: RAMP stamps, vectorized over the whole window ------------
    ol_ts = jnp.where(line_valid, ramp_ts[:, None], -1).astype(jnp.int32)
    amount = jnp.where(line_valid,
                       price_row * qty.astype(price_row.dtype), 0.0)
    return MegastepOut(committed != 0, avail.reshape(-1)[:A], rank,
                       d_count, cells(dec), cells(cnt), cells(rcnt), ol_ts,
                       amount)
