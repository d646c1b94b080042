"""Pallas TPU kernel: residual FCFS escrow admission over a VMEM-resident
availability vector — Level 2 of the two-level admission pipeline.

Escrow admission (txn/tpcc.py ``admit_fcfs``) is first-come-first-served in
batch order: transaction ``t`` commits iff every valid line's quantity —
including duplicate-cell demand within ``t`` itself — fits the cell's
remaining headroom after all earlier committed transactions. The sequential
baseline is a B-step ``lax.scan`` where EVERY step pays a whole-``avail``
gather + scatter through HBM plus an ``[L, L]`` duplicate-demand matrix.

The two-level pipeline exploits that admission is monotone wherever demand
fits supply ("Keeping CALM": monotone => coordination- and order-free):

* **Level 1 — contention gate** (:func:`contention_gate`, pure jnp, O(log B)
  depth): one segmented sum computes each cell's TOTAL batch demand. Cells
  with ``demand <= headroom`` are *uncontended*: any admission order leaves
  every check on them true, so transactions touching only such cells commit
  unconditionally, bit-identically to FCFS (proof in the docstring).
* **Level 2 — this kernel**: only the *residual* transactions (those with at
  least one line on an oversubscribed cell) still need FCFS order. The
  kernel copies ``avail`` into VMEM once, then walks the residual
  transactions with a dynamic trip count — per line, one in-VMEM row
  read-modify-write and a running tentative reservation (subtract, test
  ``>= 0``, roll back on abort) replaces both the per-step HBM round-trip
  and the ``[L, L]`` tril matrix of the scan baseline.

At TPC-C skew the residual set is the oversubscribed handful, so the
sequential depth collapses from B to ~contended-transaction count, and the
whole batch costs one avail copy instead of B gather/scatter round trips.

Layout (what Mosaic, the chip's compiler, accepts): ``avail`` rides in VMEM
as ``[rows, 128]`` int32 (:func:`to_lanes`), so reaching cell ``s`` is one
tile-aligned row (``s // 128``) masked to lane ``s % 128``; a dynamic
single-element slice of a 1-D VMEM array is not tile-legal. The
per-transaction and per-line scalars (residual order, slots, quantities,
int32 line masks) and the verdicts live in SMEM. ``avail`` is ``[A]`` with
A = K + W_local * I + 1 (hot cells ++ local cold stock ++ remote sentinel):
about 4 MB at TPC-C spec scale with 10 local warehouses.
tests/test_tpu_compile.py compiles the kernel for a v5e chip at that size.

On CPU (tests, CI) the kernel runs in ``interpret`` mode, bit-exact against
the ``kernels/ref.py`` oracle; the engine's CPU path takes the equivalent
jnp lowering :func:`residual_fcfs` instead (kernels/ops.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def contention_gate(avail0: Array, slot: Array, qty: Array,
                    line_valid: Array) -> tuple[Array, Array, Array]:
    """Level 1: classify transactions by per-cell total demand vs headroom.

    Returns ``(fast, demand, uncontended)`` — ``fast`` [B] marks
    transactions whose every valid line lands on an uncontended cell
    (``demand <= avail0`` there); they commit without any ordering.

    Why ``fast`` is bit-identical to FCFS (the proof the fast path rests
    on):

    1. On an uncontended cell, every FCFS check passes: any prefix of the
       batch's reservations on the cell — plus the checking line's own
       demand and its intra-transaction duplicates — is a subset sum of the
       cell's total demand, which fits the headroom by definition. So a
       transaction touching only uncontended cells is committed by FCFS
       regardless of its position in the batch.
    2. A fast transaction's reservations land only on uncontended cells,
       where checks pass no matter what; removing or reordering them cannot
       change any other transaction's outcome.
    3. Contrapositive of the ``fast`` definition: every line on a
       *contended* cell belongs to a residual transaction — so replaying
       ONLY the residual transactions, in batch order, against the original
       ``avail0`` reproduces the exact FCFS reservation history on every
       contended cell, and therefore the exact commit verdicts.

    Hence ``committed == fast | residual_fcfs`` cell-for-cell and bit-for-
    bit (property-tested against the oracle in tests/test_escrow_admission).
    """
    A = avail0.shape[0]
    q = jnp.where(line_valid, qty, 0).astype(jnp.int32)
    demand = jax.ops.segment_sum(
        q.reshape(-1), jnp.where(line_valid, slot, 0).reshape(-1),
        num_segments=A)
    uncontended = demand <= avail0
    fast = (uncontended[slot] | ~line_valid).all(axis=1)
    return fast, demand, uncontended


def residual_order(fast: Array) -> tuple[Array, Array]:
    """Compact residual transaction indices to the front, preserving batch
    (= FCFS) order. Returns (res_idx [B] int32, n_res [1] int32) — the
    kernel's dynamic trip count."""
    res = ~fast
    res_idx = jnp.argsort(jnp.where(res, 0, 1), stable=True).astype(jnp.int32)
    return res_idx, res.sum().astype(jnp.int32)[None]


def residual_fcfs(avail0: Array, slot: Array, qty: Array, line_valid: Array,
                  fast: Array, res_idx: Array, n_res: Array
                  ) -> tuple[Array, Array]:
    """The kernel's algorithm as plain jnp — a ``fori_loop`` with a dynamic
    trip count over the residual transactions only.

    This is the CPU lowering of Level 2 (ops.escrow_admit dispatches here
    off-TPU): interpret-mode Pallas pays ~100x per load/store, but the
    algorithmic win — sequential depth = residual count, not B — is
    backend-independent, so the fallback keeps it while remaining bit-exact
    with both the kernel and the scan baseline. Returns (committed, avail)
    with the same contract as :func:`escrow_admit_kernel` (avail carries
    residual reservations only).
    """
    L = slot.shape[1]
    dup_lower = jnp.tril(jnp.ones((L, L), jnp.bool_), k=-1)

    def txn(i, carry):
        avail, committed = carry
        t = res_idx[i]
        slots, q, lv = slot[t], qty[t], line_valid[t]
        same = slots[None, :] == slots[:, None]
        prior = jnp.where(same & dup_lower & lv[None, :],
                          q[None, :], 0).sum(axis=1)
        have = avail[slots]
        ok = jnp.all(jnp.where(lv, prior + q <= have, True))
        avail = avail.at[slots].add(jnp.where(lv & ok, -q, 0))
        committed = committed.at[t].set(ok)
        return avail, committed

    avail, committed = jax.lax.fori_loop(0, n_res[0], txn, (avail0, fast))
    return committed, avail


LANES = 128
SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)
VMEM = pl.BlockSpec(memory_space=pltpu.VMEM)
HBM = pl.BlockSpec(memory_space=pl.ANY)


def flat_i32(x: Array) -> Array:
    """Any [B] / [B, L] operand as the flat int32 array SMEM takes."""
    return x.astype(jnp.int32).reshape(-1)


def lane_rows(n: int) -> int:
    """Rows of the ``[rows, 128]`` int32 layout holding ``n`` cells: whole
    (8, 128) tiles, so every row slice is tile-aligned for Mosaic."""
    return max(8, pl.cdiv(pl.cdiv(n, LANES), 8) * 8)


def to_lanes(x: Array) -> Array:
    """``[n]`` -> zero-padded ``[lane_rows(n), 128]`` (flat cell s lives at
    row s // 128, lane s % 128)."""
    n = x.shape[0]
    return jnp.pad(x, (0, lane_rows(n) * LANES - n)).reshape(-1, LANES)


def lane_add(ref, s, delta):
    """``ref.flat[s] += delta`` on a ``[rows, 128]`` VMEM ref; returns the
    cell's new value (int32). One read-modify-write of row ``s // 128``
    masked to lane ``s % 128``: a dynamic single-row access is tile-legal
    where a dynamic single-element slice of a 1-D array is not."""
    row = pl.ds(s // LANES, 1)
    hit = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1) == s % LANES
    new = ref[row, :] + jnp.where(hit, delta, 0)
    ref[row, :] = new
    return jnp.sum(jnp.where(hit, new, 0))


def fcfs_walk(n_res_ref, res_idx_ref, slot_ref, qty_ref, lv_ref,
              committed_ref, avail_ref):
    """Level 2: FCFS over the residual transactions against the
    VMEM-resident ``avail_ref`` (shared by the admission kernel and the
    megastep). Per-transaction scalars come from SMEM (flat ``[B * L]``
    line arrays, int32 masks); ``committed_ref`` (SMEM) takes each
    residual transaction's verdict."""
    L = slot_ref.shape[0] // committed_ref.shape[0]

    def txn(i, carry):
        t = res_idx_ref[i]

        # tentative reservation walk: subtracting line l before checking
        # line l+1 makes intra-transaction duplicate demand accumulate
        # naturally — no [L, L] tril matrix needed
        def reserve(j, ok):
            v = lv_ref[j]
            new = lane_add(avail_ref, slot_ref[j],
                           jnp.where(v != 0, -qty_ref[j], 0))
            return ok & jnp.where((new >= 0) | (v == 0), 1, 0)

        ok = jax.lax.fori_loop(t * L, t * L + L, reserve, jnp.int32(1))

        # atomic abort: roll every valid line's reservation back
        def release(j, carry):
            lane_add(avail_ref, slot_ref[j],
                     jnp.where(lv_ref[j] != 0, qty_ref[j], 0))
            return carry

        @pl.when(ok == 0)
        def _():
            jax.lax.fori_loop(t * L, t * L + L, release, 0)

        committed_ref[t] = ok
        return carry

    jax.lax.fori_loop(0, n_res_ref[0], txn, 0)


def copy_smem(src_ref, dst_ref):
    """Element-wise SMEM copy (SMEM holds scalars, not vectors)."""
    def body(i, carry):
        dst_ref[i] = src_ref[i]
        return carry

    jax.lax.fori_loop(0, dst_ref.shape[0], body, 0)


def _escrow_admit_body(n_res_ref, res_idx_ref, slot_ref, qty_ref, lv_ref,
                       fast_ref, avail0_hbm, committed_ref, avail_ref):
    """committed <- fast; avail <- avail0 (one DMA into the VMEM output,
    which doubles as the running reservation state); then FCFS over the
    residual transactions."""
    copy_smem(fast_ref, committed_ref)
    pltpu.sync_copy(avail0_hbm, avail_ref)
    fcfs_walk(n_res_ref, res_idx_ref, slot_ref, qty_ref, lv_ref,
              committed_ref, avail_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def escrow_admit_kernel(avail0: Array, slot: Array, qty: Array,
                        line_valid: Array, fast: Array, res_idx: Array,
                        n_res: Array, *, interpret: bool = False
                        ) -> tuple[Array, Array]:
    """Residual FCFS admission (Level 2). ``avail0`` [A] int32; ``slot`` /
    ``qty`` / ``line_valid`` [B, L]; ``fast`` [B] bool from the gate;
    ``res_idx`` / ``n_res`` from :func:`residual_order`.

    Returns ``(committed [B] bool, avail [A])`` where ``avail`` reflects the
    RESIDUAL transactions' reservations only (fast-path demand is settled by
    one vectorized scatter outside — see ops.escrow_admit).

    Layout: ``avail`` rides in ``[rows, 128]`` (:func:`to_lanes`) so each
    per-line access is one tile-aligned row; the per-line scalars live in
    SMEM as flat ``[B * L]`` int32 arrays.
    """
    B = slot.shape[0]
    A = avail0.shape[0]
    lanes = to_lanes(avail0.astype(jnp.int32))
    committed, avail = pl.pallas_call(
        _escrow_admit_body,
        in_specs=[SMEM] * 6 + [HBM],
        out_specs=[SMEM, VMEM],
        out_shape=[jax.ShapeDtypeStruct((B,), jnp.int32),
                   jax.ShapeDtypeStruct(lanes.shape, jnp.int32)],
        interpret=interpret,
        name="escrow_admit_kernel",
    )(n_res, res_idx, flat_i32(slot), flat_i32(qty), flat_i32(line_valid),
      flat_i32(fast), lanes)
    return committed != 0, avail.reshape(-1)[:A]
