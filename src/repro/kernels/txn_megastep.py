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
            tiles, streamed to HBM over the grid, instead of three
            whole-table HBM scatter passes;
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

Memory placement: VMEM holds avail [A] whole, as ``[rows, 128]`` int32
(escrow_admit.to_lanes) — 4 bytes per local stock cell and hot slot — and
one ``[TILE_ROWS, 128]`` tile of each of the three stock slabs. The slabs
stream over a grid of tiles: grid step 0 runs phases 2-3 (admission, ranks,
avail settling); every step zeroes its slab tiles and walks the admitted
local lines whose cell falls in the tile, in cell order (the caller's sort
of the lines, ``perm``, and the tile bounds into it), while the pipeline
writes the previous tile back to HBM. So VMEM holds the slabs' two
buffered tiles however many warehouses the chip holds, and the kernel's
ceiling is avail's residency: ``MAX_VMEM_BYTES`` takes 293 local
warehouses (tests/test_tpu_compile.py compiles the megastep there and
checks that one warehouse more is refused). SMEM holds the scalars: the
per-transaction residual order / verdicts / keys / ranks, the
per-district counters, the flat [B * L] line arrays and the line order.
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


TILE_ROWS = 1024          # slab rows a grid step: 131,072 cells, 512 KiB
MAX_VMEM_BYTES = 120 * 2**20   # of one v5e TensorCore's 128 MiB VMEM
_SLACK = 4 * 2**20              # Mosaic's own scratch beside the residents


def megastep_vmem_bytes(n_avail: int, tile_rows: int = TILE_ROWS) -> int:
    """VMEM the megastep asks for: avail whole, two buffers of each of the
    three slab tiles, and slack for the compiler's own scratch."""
    return (lane_rows(n_avail) + 6 * tile_rows) * LANES * 4 + _SLACK


def slab_tiling(n_cells: int) -> tuple[int, int]:
    """(rows a tile, tiles) of the ``[rows, 128]`` slabs over ``n_cells``
    local stock cells; a problem smaller than a tile takes one tile."""
    rows = min(TILE_ROWS, lane_rows(n_cells))
    return rows, pl.cdiv(lane_rows(n_cells), rows)


def local_line_order(cell_local: Array, local_line: Array, n_tiles: int,
                     tile_cells: int) -> tuple[Array, Array]:
    """The slab walk's line order: flat line indices sorted by local stock
    cell, non-local lines last, and the ``[n_tiles + 1]`` bounds of each
    tile's run in that order."""
    key = jnp.where(local_line, cell_local,
                    jnp.iinfo(jnp.int32).max).reshape(-1)
    perm = jnp.argsort(key).astype(jnp.int32)
    bounds = jnp.searchsorted(key[perm],
                              jnp.arange(n_tiles + 1) * tile_cells)
    return perm, bounds.astype(jnp.int32)


def _txn_megastep_body(n_res_ref, res_idx_ref, slot_ref, qty_ref, lv_ref,
                       fast_ref, key_ref, cell_ref, rem_ref, perm_ref,
                       bound_ref, avail0_hbm,
                       committed_ref, rank_ref, dcnt_ref,
                       avail_ref, dec_ref, cnt_ref, rcnt_ref):
    """Grid step 0: phases 2-3 over the VMEM-resident ``avail_ref``, which
    doubles as the running reservation state; ``dcnt_ref`` (SMEM) doubles
    as the per-district increment-and-get counter. Every step: this tile
    of the three stock slabs."""
    B = committed_ref.shape[0]
    L = slot_ref.shape[0] // B
    tile = pl.program_id(0)

    @pl.when(tile == 0)
    def _():
        copy_smem(fast_ref, committed_ref)
        pltpu.sync_copy(avail0_hbm, avail_ref)

        def zero_keys(k, carry):
            dcnt_ref[k] = 0
            return carry

        jax.lax.fori_loop(0, dcnt_ref.shape[0], zero_keys, 0)

        # ---- phase 2: residual FCFS (the escrow_admit walk, verbatim) ------
        fcfs_walk(n_res_ref, res_idx_ref, slot_ref, qty_ref, lv_ref,
                  committed_ref, avail_ref)

        # ---- phase 3: ranks and avail settling, batch order ----------------
        def effect_txn(t, carry):
            c = committed_ref[t]
            fast_t = fast_ref[t]
            # per-district increment-and-get: rank is the count of committed
            # earlier same-key txns (stored for every txn, like the scan
            # path — aborted rows' o_ids are computed there too and dropped
            # downstream)
            key = key_ref[t]
            kcnt = dcnt_ref[key]
            rank_ref[t] = kcnt
            dcnt_ref[key] = kcnt + c

            # settle the fast path's reservations in-place: avail leaves
            # the kernel fully settled (admit_fcfs's contract), no outside
            # scatter needed
            @pl.when(fast_t != 0)
            def _():
                def line(j, carry):
                    @pl.when(lv_ref[j] != 0)
                    def _():
                        lane_add(avail_ref, slot_ref[j], -qty_ref[j])
                    return carry

                jax.lax.fori_loop(t * L, t * L + L, line, 0)
            return carry

        jax.lax.fori_loop(0, B, effect_txn, 0)

    # ---- phase 3, stock slabs: this tile's admitted local lines ------------
    for ref in (dec_ref, cnt_ref, rcnt_ref):
        ref[...] = jnp.zeros(ref.shape, jnp.int32)
    base = tile * (dec_ref.shape[0] * LANES)

    def slab_line(p, carry):
        j = perm_ref[p]

        @pl.when(committed_ref[j // L] != 0)
        def _():
            cell = cell_ref[j] - base
            lane_add(dec_ref, cell, qty_ref[j])
            lane_add(cnt_ref, cell, 1)
            lane_add(rcnt_ref, cell, rem_ref[j])
        return carry

    jax.lax.fori_loop(bound_ref[tile], bound_ref[tile + 1], slab_line, 0)


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

    Layout: ``avail`` rides whole in ``[rows, 128]`` int32
    (escrow_admit.to_lanes), so every per-line access is one tile-aligned
    row; the stock slabs stream in ``[TILE_ROWS, 128]`` tiles over the
    grid; per-transaction and per-line scalars, the verdicts, ranks,
    district counters and the line order live in SMEM.
    """
    B, L = slot.shape
    A = avail0.shape[0]
    lanes = to_lanes(avail0.astype(jnp.int32))
    rows, n_tiles = slab_tiling(n_cells)
    vmem = megastep_vmem_bytes(A, rows)
    if vmem > MAX_VMEM_BYTES:
        raise ValueError(
            f"txn_megastep needs {vmem} bytes of VMEM for {A} avail cells; "
            f"the budget is {MAX_VMEM_BYTES}")
    perm, bounds = local_line_order(cell_local, local_line, n_tiles,
                                    rows * LANES)
    slab = jax.ShapeDtypeStruct((rows * n_tiles, LANES), jnp.int32)
    tile = pl.BlockSpec((rows, LANES), lambda i: (i, 0))
    committed, rank, d_count, avail, dec, cnt, rcnt = pl.pallas_call(
        _txn_megastep_body,
        grid=(n_tiles,),
        in_specs=[SMEM] * 11 + [HBM],
        out_specs=[SMEM] * 3 + [VMEM] + [tile] * 3,
        out_shape=[jax.ShapeDtypeStruct((B,), jnp.int32),
                   jax.ShapeDtypeStruct((B,), jnp.int32),
                   jax.ShapeDtypeStruct((n_keys,), jnp.int32),
                   jax.ShapeDtypeStruct(lanes.shape, jnp.int32),
                   slab, slab, slab],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem),
        interpret=interpret,
        name="txn_megastep_kernel",
    )(n_res, res_idx, flat_i32(slot), flat_i32(qty), flat_i32(line_valid),
      flat_i32(fast), key_local.astype(jnp.int32), flat_i32(cell_local),
      flat_i32(remote_line), perm, bounds, lanes)
    cells = lambda x: x.reshape(-1)[:n_cells]
    # ---- phase 4: RAMP stamps, vectorized over the whole window ------------
    ol_ts = jnp.where(line_valid, ramp_ts[:, None], -1).astype(jnp.int32)
    amount = jnp.where(line_valid,
                       price_row * qty.astype(price_row.dtype), 0.0)
    return MegastepOut(committed != 0, avail.reshape(-1)[:A], rank,
                       d_count, cells(dec), cells(cnt), cells(rcnt), ol_ts,
                       amount)
