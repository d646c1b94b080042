"""One-kernel transaction megastep (admission + effects + RAMP stamps).

Core level: for ARBITRARY megastep problems — duplicate cells within one
transaction, invalid lines, zero-headroom cells, sentinel cold-line cells,
remote/local line mixes — four implementations must be BIT-identical:

  * the definitional oracle (kernels/ref.py ``txn_megastep_ref``: scan-path
    admission + the ``[B, B]`` rank matrix + plain scatter-adds),
  * the Pallas kernel itself in interpret mode (the TPU code path executed
    on CPU — the same contract as escrow_admit / ramp_read),
  * the vectorized CPU lowering (``escrow_admit`` + the sort-based
    ``megastep_effect_products``),
  * whatever the public ``ops.txn_megastep`` dispatcher picks.

Transaction level: ``effects="fused"`` through the public New-Order entry
points (dense and sparse escrow layouts) lands bit-identical state / spent /
outbox / totals / committed as ``effects="scan"`` on the same batch, for
every admission mode, in plentiful AND starved stock regimes (aborts
present).

Engine level: ``Engine(effects="fused")`` closed loops land on bit-identical
final state / escrow counters / stats as ``effects="scan"`` across both
layouts and the fused / dispatch / legacy drivers, and the fused final
states audit clean (strict stock, conservation).

Plus the measured admission cut-over (ROADMAP item 2): the one-shot backend
autotune memoizes per (backend, batch shape), and switching it off restores
the documented constant threshold.

The problem generator is shared between a deterministic seeded sweep
(always runs) and a hypothesis-driven search (runs where hypothesis is
installed — CI installs it via the ``test`` extra).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # container without hypothesis: deterministic sweep only
    HAVE_HYPOTHESIS = False

from repro.kernels import ref
from repro.kernels.escrow_admit import contention_gate, residual_order
from repro.kernels.ops import escrow_admit, txn_megastep
from repro.kernels.txn_megastep import (TILE_ROWS, MegastepOut,
                                        megastep_effect_products, slab_tiling,
                                        txn_megastep_kernel)
from repro.txn import tpcc
from repro.txn.audit import assert_audit
from repro.txn.drivers import run_escrow_loop
from repro.txn.engine import single_host_engine
from repro.txn.tpcc import (TPCCScale, init_state, make_escrow_shares,
                            select_hot_cells)


# ---------------------------------------------------------------------------
# Core level: kernel == CPU lowering == dispatcher == oracle
# ---------------------------------------------------------------------------


def _mega_problem(seed: int, B: int = 16, L: int = 6, A: int = 48,
                  n_keys: int = 12, n_cells: int = 40, lo: int = 0,
                  hi: int = 40, dup_heavy: bool = False):
    """A random megastep problem: an admission problem (same shape space as
    the escrow_admit tests) plus district keys, local stock cells, a
    local/remote line split, RAMP timestamps and a price row."""
    rng = np.random.default_rng(seed)
    avail0 = jnp.asarray(rng.integers(lo, hi + 1, A), jnp.int32)
    cells = max(2, A // 4) if dup_heavy else A
    slot = jnp.asarray(rng.integers(0, cells, (B, L)), jnp.int32)
    qty = jnp.asarray(rng.integers(1, 11, (B, L)), jnp.int32)
    lv = jnp.asarray(rng.random((B, L)) < 0.85)
    key = jnp.asarray(rng.integers(0, n_keys, B), jnp.int32)
    loc = jnp.asarray(rng.random((B, L)) < 0.7) & lv
    cell = jnp.where(
        loc, jnp.asarray(rng.integers(0, n_cells, (B, L)), jnp.int32), 0)
    rem = jnp.asarray(rng.random((B, L)) < 0.3) & lv
    ts = jnp.asarray(rng.integers(0, 1 << 20, B), jnp.int32)
    price = jnp.asarray(rng.integers(1, 100, (B, L)), jnp.float32)
    return (avail0, slot, qty, lv, key, cell, loc, rem, ts, price), dict(
        n_keys=n_keys, n_cells=n_cells)


def _assert_mega_equal(args, kw):
    """All four implementations against the oracle, field by field."""
    avail0, slot, qty, lv = args[:4]
    ref_out = MegastepOut(*ref.txn_megastep_ref(*args, **kw))

    fast, _, _ = contention_gate(avail0, slot, qty, lv)
    res_idx, n_res = residual_order(fast)
    k_out = txn_megastep_kernel(avail0, slot, qty, lv, fast, res_idx, n_res,
                                *args[4:], **kw, interpret=True)

    c, a = escrow_admit(avail0, slot, qty, lv)
    low_out = MegastepOut(c, a, *megastep_effect_products(
        c, qty, lv, *args[4:], **kw))

    ops_out = txn_megastep(*args, **kw)

    for tag, got in (("kernel", k_out), ("lowering", low_out),
                     ("ops", ops_out)):
        for name, x, y in zip(MegastepOut._fields, ref_out, got):
            np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y), err_msg=f"{tag}: {name}")
    return ref_out


def test_megastep_equivalence_seeded_sweep():
    """Deterministic sweep across contention levels — scarce headroom (big
    residual sets exercise the in-kernel FCFS walk), plump headroom (pure
    fast path, in-kernel settle), duplicate-heavy rows, and bigger mixed
    problems."""
    for seed in range(20):
        kind = seed % 4
        if kind == 0:      # scarce: almost everything residual
            args, kw = _mega_problem(seed, hi=12)
        elif kind == 1:    # plump: almost everything fast
            args, kw = _mega_problem(seed, lo=300, hi=500)
        elif kind == 2:    # duplicate-heavy rows on a small cell domain
            args, kw = _mega_problem(seed, dup_heavy=True, hi=50)
        else:              # mixed, bigger batch
            args, kw = _mega_problem(seed, B=32, L=8, A=80, n_keys=6,
                                     n_cells=24, hi=60)
        _assert_mega_equal(args, kw)


def test_megastep_slabs_stream_over_tiles():
    """The stock slabs stream over a grid of ``TILE_ROWS``-row tiles: a
    problem over 2.5 tiles of local cells takes three, and the kernel
    still equals the oracle field by field, scarce and plump."""
    tile_cells = TILE_ROWS * 128
    for seed, hi in enumerate([12, 300]):
        args, kw = _mega_problem(seed, n_cells=5 * tile_cells // 2, hi=hi)
        assert slab_tiling(kw["n_cells"])[1] == 3
        _assert_mega_equal(args, kw)


def test_megastep_rank_and_counter_semantics():
    """The increment-and-get contract: rank counts committed EARLIER
    same-key transactions (stored for aborted rows too, like the scan
    path's rank matrix), aborted rows never advance the district counter,
    and the stock slabs only see admitted local lines."""
    avail0 = jnp.asarray([10, 0, 1 << 30], jnp.int32)
    #          txn: fits | zero-headroom abort | fits | sentinel ride
    slot = jnp.asarray([[0], [1], [0], [2]], jnp.int32)
    qty = jnp.asarray([[4], [1], [5], [9]], jnp.int32)
    lv = jnp.ones((4, 1), jnp.bool_)
    key = jnp.asarray([0, 0, 0, 1], jnp.int32)           # 3 share a district
    loc = jnp.asarray([[True], [True], [False], [True]])
    cell = jnp.where(loc, jnp.asarray([[2], [2], [0], [3]], jnp.int32), 0)
    rem = jnp.asarray([[False], [True], [False], [True]])
    ts = jnp.asarray([7, 7, 7, 9], jnp.int32)
    price = jnp.full((4, 1), 2.0, jnp.float32)
    out = _assert_mega_equal(
        (avail0, slot, qty, lv, key, cell, loc, rem, ts, price),
        dict(n_keys=2, n_cells=4))
    assert np.asarray(out.committed).tolist() == [True, False, True, True]
    # txn 1 aborts but still reads rank 1 (one committed predecessor on key
    # 0); txn 2 also gets rank 1 — the abort did not advance the counter
    assert np.asarray(out.rank).tolist() == [0, 1, 1, 0]
    assert np.asarray(out.d_count).tolist() == [2, 1]
    # slabs: txn 0 (local, 4 units) and txn 3 (local remote-sourced, 9)
    # land; txn 1's abort and txn 2's non-local line do not
    assert np.asarray(out.stock_dec).tolist() == [0, 0, 4, 9]
    assert np.asarray(out.stock_cnt).tolist() == [0, 0, 1, 1]
    assert np.asarray(out.stock_rcnt).tolist() == [0, 0, 0, 1]
    assert np.asarray(out.amount)[:, 0].tolist() == [8.0, 2.0, 10.0, 18.0]
    assert np.asarray(out.ol_ts)[:, 0].tolist() == [7, 7, 7, 9]


def test_megastep_invalid_lines_are_inert():
    """Invalid lines neither reserve nor stamp: ol_ts carries the -1
    sentinel, amount is 0, and the slabs ignore them even when their cell
    ids alias live cells."""
    args, kw = _mega_problem(3, B=12, L=5, hi=30)
    lv = args[3].at[:, 2].set(False)                 # kill a whole column
    loc = args[6] & lv
    args = args[:3] + (lv, args[4], args[5], loc) + args[7:]
    out = _assert_mega_equal(args, kw)
    assert np.asarray(out.ol_ts)[:, 2].tolist() == [-1] * 12
    assert np.asarray(out.amount)[:, 2].tolist() == [0.0] * 12


if HAVE_HYPOTHESIS:
    @settings(max_examples=40, deadline=None)
    @given(seed=st.integers(0, 100_000),
           B=st.integers(1, 20), L=st.integers(1, 6),
           A=st.integers(2, 48), n_keys=st.integers(1, 10),
           n_cells=st.integers(1, 32),
           hi=st.sampled_from([5, 20, 60, 400]), dup=st.booleans())
    def test_megastep_equivalence_hypothesis(seed, B, L, A, n_keys, n_cells,
                                             hi, dup):
        """Hypothesis search: kernel == lowering == dispatcher == oracle on
        arbitrary interleavings of duplicate / invalid / zero-headroom /
        contended / remote demand."""
        _assert_mega_equal(*_mega_problem(seed, B=B, L=L, A=A,
                                          n_keys=n_keys, n_cells=n_cells,
                                          hi=hi, dup_heavy=dup))


# ---------------------------------------------------------------------------
# Transaction level: effects="fused" == effects="scan" at the public entries
# ---------------------------------------------------------------------------


TXN_SCALE = TPCCScale(n_warehouses=2, districts=4, customers=16,
                      n_items=64, order_capacity=512, max_lines=8)


def _assert_txn_outputs_equal(a, b, tag):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for i, (x, y) in enumerate(zip(la, lb)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"{tag}: leaf {i}")


@pytest.mark.parametrize("stock", ["plentiful", "starved"])
def test_fused_entry_dense_bitexact_with_scan(stock):
    """apply_neworder_escrow(effects="fused") vs "scan" on the identical
    batch: full state, spent delta, outbox, totals, committed — every
    admission mode, with and without aborts."""
    rng = np.random.default_rng(0)
    B, W = 48, TXN_SCALE.n_warehouses
    batch = tpcc.generate_neworder(rng, TXN_SCALE, B, remote_frac=0.2,
                                   item_skew=1.0)
    state = init_state(TXN_SCALE)
    if stock == "plentiful":
        state = state._replace(s_quantity=state.s_quantity * 50)
    shares = make_escrow_shares(state.s_quantity, 2)[0]
    spent0 = jnp.zeros_like(shares)
    base = jax.jit(lambda st: tpcc.apply_neworder_escrow(
        st, shares, spent0, batch, TXN_SCALE, w_lo=0, w_hi=W,
        admission="scan", effects="scan"))(state)
    committed = np.asarray(base[4])
    if stock == "starved":
        assert not committed.all()       # the regime actually aborts
    for adm in ("scan", "kernel"):
        fused = jax.jit(lambda st, adm=adm: tpcc.apply_neworder_escrow(
            st, shares, spent0, batch, TXN_SCALE, w_lo=0, w_hi=W,
            admission=adm, effects="fused"))(state)
        _assert_txn_outputs_equal(base, fused, f"dense/{stock}/adm={adm}")


@pytest.mark.parametrize("stock", ["plentiful", "starved"])
def test_fused_entry_sparse_bitexact_with_scan(stock):
    """The same contract over the two-tier layout: hot shares + local cold
    stock + the cold-line sentinel all flow through the one fused
    admission domain."""
    rng = np.random.default_rng(1)
    B, W = 48, TXN_SCALE.n_warehouses
    batch = tpcc.generate_neworder(rng, TXN_SCALE, B, remote_frac=0.3,
                                   item_skew=1.2)
    state = init_state(TXN_SCALE)
    if stock == "plentiful":
        state = state._replace(s_quantity=state.s_quantity * 50)
    hot_keys = jnp.asarray(select_hot_cells(TXN_SCALE, 8))
    headroom = state.s_quantity.reshape(-1)[hot_keys]
    base = jax.jit(lambda st: tpcc.apply_neworder_escrow_sparse(
        st, hot_keys, headroom, jnp.zeros_like(headroom), batch, TXN_SCALE,
        w_lo=0, w_hi=W, admission="scan", effects="scan"))(state)
    if stock == "starved":
        assert not np.asarray(base[4]).all()
    for adm in ("scan", "kernel"):
        fused = jax.jit(
            lambda st, adm=adm: tpcc.apply_neworder_escrow_sparse(
                st, hot_keys, headroom, jnp.zeros_like(headroom), batch,
                TXN_SCALE, w_lo=0, w_hi=W, admission=adm,
                effects="fused"))(state)
        _assert_txn_outputs_equal(base, fused, f"sparse/{stock}/adm={adm}")


# ---------------------------------------------------------------------------
# Engine level
# ---------------------------------------------------------------------------


SCALE = TPCCScale(n_warehouses=2, districts=2, customers=8, n_items=32,
                  order_capacity=256, max_lines=15)


def _tree_equal(a, b):
    eq = jax.tree.map(lambda x, y: bool((x == y).all()), a, b)
    return [f for f, ok in zip(a._fields, eq) if not ok]


@pytest.mark.parametrize("layout", ["sparse", "dense"])
@pytest.mark.parametrize("driver", ["fused", "dispatch", "legacy"])
def test_engine_fused_effects_bitexact_with_scan(layout, driver):
    """The engine-level anchor: effects="fused" and effects="scan" land on
    bit-identical final state, escrow counters, and stats on the identical
    adversarial stream (hot/cold/remote mixes, skewed demand, aborts
    present), for both layouts and all three drivers — and the fused final
    state audits clean under the strict-stock conditions."""
    kw = dict(batch_per_shard=8, n_batches=6, remote_frac=0.3,
              merge_every=2, refresh_every=2, seed=5, mix=False,
              fused=(driver == "fused"), legacy=(driver == "legacy"),
              item_skew=1.1)
    finals = {}
    q0 = None
    for eff in ("scan", "fused"):
        eng = single_host_engine(SCALE, stock_invariant="strict",
                                 escrow_layout=layout, hot_items=4,
                                 admission="kernel", effects=eff)
        s = eng.shard_state(init_state(SCALE))
        q0 = s.s_quantity.copy()
        finals[eff] = run_escrow_loop(eng, s, **kw)
    s1, e1, m1 = finals["scan"]
    s2, e2, m2 = finals["fused"]
    assert _tree_equal(s1, s2) == []
    assert _tree_equal(e1, e2) == []
    assert (m1.neworders, m1.aborts, m1.cold_rejects) == \
        (m2.neworders, m2.aborts, m2.cold_rejects)
    assert m1.aborts > 0     # adversarial: the FCFS residue actually fired
    assert_audit(s2, escrow=e2, initial_stock=q0, strict_stock=True)


def test_engine_effects_knob_validation():
    assert tpcc.resolve_effects("fused") == "fused"
    assert tpcc.resolve_effects("scan") == "scan"
    with pytest.raises(ValueError, match="unknown effects"):
        tpcc.resolve_effects("warp")
    with pytest.raises(ValueError, match="unknown effects"):
        single_host_engine(SCALE, stock_invariant="strict", effects="warp")


# ---------------------------------------------------------------------------
# The measured admission cut-over (satellite)
# ---------------------------------------------------------------------------


def test_autotune_cutover_memoizes():
    """The one-shot backend probe: first call measures (a tiny shape keeps
    it cheap), the winner lands in the process cache, repeat calls are pure
    lookups, and the decision is one of the two real strategies."""
    key = (jax.default_backend(), 8, 3)
    saved = dict(tpcc._CUTOVER_CACHE)
    try:
        tpcc._CUTOVER_CACHE.clear()
        m1 = tpcc.resolve_admission_cutover(8, 3, cells=64, trials=1)
        assert key in tpcc._CUTOVER_CACHE
        assert m1 in ("scan", "kernel")
        tpcc._CUTOVER_CACHE[key] = "scan"     # prove repeat calls hit cache
        assert tpcc.resolve_admission_cutover(8, 3, cells=64) == "scan"
    finally:
        tpcc._CUTOVER_CACHE.clear()
        tpcc._CUTOVER_CACHE.update(saved)


def test_resolve_admission_fallback_without_autotune():
    """``ADMISSION_AUTOTUNE = False`` (and the no-line-width call shape)
    restores the documented constant threshold exactly."""
    saved = tpcc.ADMISSION_AUTOTUNE
    try:
        tpcc.ADMISSION_AUTOTUNE = False
        t = tpcc.AUTO_KERNEL_MIN_BATCH
        assert tpcc.resolve_admission("auto", t, 15) == "kernel"
        assert tpcc.resolve_admission("auto", t - 1, 15) == "scan"
    finally:
        tpcc.ADMISSION_AUTOTUNE = saved
    # without a line width "auto" cannot shape a probe: constant fallback
    assert tpcc.resolve_admission("auto", t) == "kernel"
    assert tpcc.resolve_admission("auto", t - 1) == "scan"
    # explicit modes bypass the autotune entirely
    assert tpcc.resolve_admission("scan", 4096, 15) == "scan"
    assert tpcc.resolve_admission("kernel", 1, 15) == "kernel"
