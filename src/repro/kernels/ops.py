"""Jit'd public wrappers for the Pallas kernels.

On TPU the kernels compile natively; on CPU (this container) they execute in
``interpret=True`` mode, which runs the kernel body in Python for
correctness. ``FORCE_INTERPRET`` can pin interpret mode for tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention_kernel
from .lattice_merge import lattice_merge_kernel
from .rwkv6_scan import rwkv6_scan_kernel

FORCE_INTERPRET: bool | None = None


def _interpret() -> bool:
    if FORCE_INTERPRET is not None:
        return FORCE_INTERPRET
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128):
    """GQA flash attention. q: [B,S,H,hd]; k/v: [B,S,KV,hd]."""
    S = q.shape[1]
    bq = min(block_q, S)
    bk = min(block_k, S)
    while S % bq:
        bq //= 2
    while S % bk:
        bk //= 2
    return flash_attention_kernel(q, k, v, causal=causal, block_q=max(bq, 1),
                                  block_k=max(bk, 1), interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("chunk",))
def rwkv6_scan(r, k, v, w, u, s0, *, chunk: int = 64):
    """Chunked RWKV-6 WKV scan. Returns (out, final_state)."""
    T = r.shape[1]
    c = min(chunk, T)
    while T % c:
        c //= 2
    return rwkv6_scan_kernel(r, k, v, w, u, s0, chunk=max(c, 1),
                             interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("lo", "hi", "block_rows"))
def lattice_merge(a_valid, a_ver, a_pay, b_valid, b_ver, b_pay,
                  lo: float = -jnp.inf, hi: float = jnp.inf,
                  block_rows: int = 256):
    """Fused versioned-table join + threshold audit."""
    R = a_valid.shape[0]
    br = min(block_rows, R)
    while R % br:
        br //= 2
    return lattice_merge_kernel(a_valid, a_ver, a_pay, b_valid, b_ver, b_pay,
                                lo, hi, block_rows=max(br, 1),
                                interpret=_interpret())


def escrow_admit(avail0, slot, qty, line_valid):
    """Two-level escrow admission: contention gate (Level 1, vectorized jnp)
    + residual FCFS in the VMEM-resident Pallas kernel (Level 2). Bit-exact
    with the sequential-scan semantics (ref.escrow_admit_ref, property-
    tested in tests/test_escrow_admission.py).

    avail0 [A] int32; slot/qty/line_valid [B, L].
    Returns (committed [B] bool, avail [A] int32 after all reservations).

    NOT jit-wrapped here: the caller (txn/tpcc.py admit_fcfs) always sits
    inside a jitted megastep/engine step.

    Backend dispatch for Level 2: on TPU the Pallas kernel runs natively
    (avail resident in VMEM); off-TPU the same algorithm runs as the jitted
    ``residual_fcfs`` fori_loop — interpret-mode Pallas pays ~100x per
    load/store, which would bury the gate's win, while the fallback keeps
    the collapsed sequential depth AND stays bit-exact with the kernel
    (whose interpret-mode path the kernel tests pin against the oracle).
    """
    from .escrow_admit import (contention_gate, escrow_admit_kernel,
                               residual_fcfs, residual_order)

    fast, demand, _ = contention_gate(avail0, slot, qty, line_valid)

    def everyone_fast(_):
        # no contended cell anywhere: every transaction commits, and the
        # admitted demand IS the gate's per-cell total — one vector subtract
        # replaces both the residual pass and the settle scatter
        return jnp.ones_like(fast), avail0 - demand

    def with_residue(_):
        res_idx, n_res = residual_order(fast)
        if _interpret():
            committed, avail = residual_fcfs(avail0, slot, qty, line_valid,
                                             fast, res_idx, n_res)
        else:
            committed, avail = escrow_admit_kernel(
                avail0, slot, qty, line_valid, fast, res_idx, n_res)
        # settle the fast path's reservations with ONE vectorized scatter
        # (Level 2's avail carries residual reservations only); fast txns
        # always commit (gate proof)
        adm = line_valid & fast[:, None]
        avail = avail.at[jnp.where(adm, slot, 0)].add(
            -jnp.where(adm, qty, 0).astype(jnp.int32))
        return committed, avail

    return jax.lax.cond(fast.all(), everyone_fast, with_residue, None)


def txn_megastep(avail0, slot, qty, line_valid, key_local, cell_local,
                 local_line, remote_line, ramp_ts, price_row, *,
                 n_keys: int, n_cells: int):
    """One-kernel transaction megastep: gate (Level 1, vectorized jnp) +
    residual FCFS + committed effects + RAMP stamps with avail resident in
    VMEM across all phases and the stock slabs streamed over tiles
    (kernels/txn_megastep.py). Bit-exact
    with the scan path's phase sequence (ref.txn_megastep_ref, property-
    tested in tests/test_megastep_kernel.py).

    Returns a MegastepOut: (committed, fully settled avail, rank, d_count,
    stock slabs, ol_ts, amount) — see txn_megastep.py for shapes.

    NOT jit-wrapped here, like escrow_admit: the caller (txn/tpcc.py
    ``_neworder_fused_effects``) always sits inside a jitted
    megastep/engine step.

    Backend dispatch mirrors escrow_admit: on TPU one Pallas program runs
    phases 2-3 (avail settles IN-kernel, so no outside scatter) and the
    phase-4 stamps follow it as jnp; off-TPU the
    admission runs through ``escrow_admit`` (gate + jitted residual_fcfs)
    and phases 3-4 through the vectorized ``megastep_effect_products``
    lowering — same products, bit for bit.
    """
    from .escrow_admit import contention_gate, residual_order
    from .txn_megastep import (MegastepOut, megastep_effect_products,
                               txn_megastep_kernel)

    if _interpret():
        committed, avail = escrow_admit(avail0, slot, qty, line_valid)
        return MegastepOut(committed, avail, *megastep_effect_products(
            committed, qty, line_valid, key_local, cell_local, local_line,
            remote_line, ramp_ts, price_row, n_keys=n_keys,
            n_cells=n_cells))
    fast, _, _ = contention_gate(avail0, slot, qty, line_valid)
    res_idx, n_res = residual_order(fast)
    return txn_megastep_kernel(
        avail0, slot, qty, line_valid, fast, res_idx, n_res, key_local,
        cell_local, local_line, remote_line, ramp_ts, price_row,
        n_keys=n_keys, n_cells=n_cells)


@functools.partial(jax.jit, static_argnames=("block_rows",))
def ramp_read_select(req_ts, nlines, ol_ts, ol_vis, ol_prep, amount, i_id,
                     block_rows: int = 256):
    """Fused RAMP read: fracture detection + lookback select + aggregation."""
    from .ramp_read import ramp_read_kernel

    R = req_ts.shape[0]
    br = min(block_rows, R)
    while R % br:
        br //= 2
    return ramp_read_kernel(req_ts, nlines, ol_ts, ol_vis, ol_prep, amount,
                            i_id, block_rows=max(br, 1),
                            interpret=_interpret())
