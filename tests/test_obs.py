"""Observability-plane tests (repro/obs).

The three claims the plane stands on:

* the metrics lattices obey the lattice laws (Definition 3) — property-tested
  with hypothesis, including the histogram-of-union law the
  ``HistogramLattice`` docstring promises;
* metrics are WRITE-ONLY: a metrics-on closed loop produces bit-identical
  TPCC state to metrics-off, in both the merge and the escrow regime, and
  the recorded totals cross-check against the run's MixStats;
* the coordination ledger holds hot phases to the zero-collective budget.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lattice as lat
from repro.obs import ObsSession, metrics as obsm
from repro.obs.ledger import CoordinationLedger, build_ledger
from repro.txn.drivers import run_loop
from repro.txn.engine import single_host_engine
from repro.txn.tpcc import TPCCScale, init_state


def _tree_eq(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


# ---------------------------------------------------------------------------
# Lattice laws. The property tests use hypothesis when available (same idiom
# as test_lattice.py); the deterministic law checks below always run, so the
# obs plane's core claims hold even in a hypothesis-less environment.
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised in minimal containers
    HAS_HYPOTHESIS = False

if HAS_HYPOTHESIS:

    def _counters(num_replicas=3, value_shape=(2,)):
        n = num_replicas * int(np.prod(value_shape))
        return st.lists(st.integers(0, 50), min_size=n, max_size=n).map(
            lambda xs: lat.CounterLattice(jnp.asarray(
                np.array(xs, np.int32).reshape(num_replicas, *value_shape))))

    @settings(max_examples=50, deadline=None)
    @given(_counters(), _counters(), _counters())
    def test_counter_lattice_laws(a, b, c):
        j = lat.CounterLattice.join
        assert _tree_eq(j(a, b), j(b, a))
        assert _tree_eq(j(a, j(b, c)), j(j(a, b), c))
        assert _tree_eq(j(a, a), a)
        bottom = lat.CounterLattice.make(3, (2,))
        assert _tree_eq(j(a, bottom), a)  # identity

    def _hists(num_replicas=2, n_bins=8):
        n = num_replicas * n_bins
        return st.lists(st.integers(0, 50), min_size=n, max_size=n).map(
            lambda xs: lat.HistogramLattice.make(num_replicas, n_bins)
            ._replace(counts=jnp.asarray(
                np.array(xs, np.int32).reshape(num_replicas, n_bins))))

    @settings(max_examples=50, deadline=None)
    @given(_hists(), _hists(), _hists())
    def test_histogram_lattice_laws(a, b, c):
        j = lat.HistogramLattice.join
        assert _tree_eq(j(a, b), j(b, a))
        assert _tree_eq(j(a, j(b, c)), j(j(a, b), c))
        assert _tree_eq(j(a, a), a)
        bottom = lat.HistogramLattice.make(2, 8)
        assert _tree_eq(j(a, bottom), a)  # identity

    _obs_values = st.lists(
        st.floats(0, 1e4, allow_nan=False, allow_subnormal=False, width=32),
        min_size=1, max_size=12)

    @settings(max_examples=50, deadline=None)
    @given(_obs_values, _obs_values)
    def test_histogram_of_union_property(xs, ys):
        _check_histogram_of_union(xs, ys)


def _check_histogram_of_union(xs, ys):
    """join(hist(A), hist(B)) == hist(A ∪ B) when A and B were observed on
    disjoint replica lanes — the law the HistogramLattice docstring promises,
    and the reason merging snapshots across replicas never double-counts."""
    h0 = lat.HistogramLattice.make(2, 8)
    a = h0.observe(0, jnp.asarray(xs))
    b = h0.observe(1, jnp.asarray(ys))
    union = h0.observe(0, jnp.asarray(xs)).observe(1, jnp.asarray(ys))
    merged = lat.HistogramLattice.join(a, b)
    assert _tree_eq(merged, union)
    # and the merged value() is the histogram of all observations
    assert int(merged.value().sum()) == len(xs) + len(ys)


def test_histogram_of_union_examples():
    _check_histogram_of_union([1.0], [1.0])            # same bin, both lanes
    _check_histogram_of_union([0.0, 3.0, 7.5], [2.0])  # bin boundaries
    _check_histogram_of_union([1e4] * 5, [0.5, 300.0])  # open top bin


def test_counter_value_reflects_all_replicas():
    c0 = lat.CounterLattice.make(2, (4,))
    a = c0.bump(0, jnp.asarray([1, 1, 3]))      # replica 0: dup idx accumulate
    b = c0.bump(1, jnp.asarray([0]), amount=5)  # replica 1's local copy
    merged = lat.CounterLattice.join(a, b)
    assert merged.value().tolist() == [5, 2, 0, 1]


def test_registered_joins_pass_lattice_laws():
    counters = [lat.CounterLattice.make(2).bump(0, amount=k) for k in (1, 5, 2)]
    lat.check_lattice_laws(lat.CounterLattice.join, counters, eq=_tree_eq)
    hists = [lat.HistogramLattice.make(2, 8).observe(0, jnp.asarray([v]))
             for v in (1.0, 7.0, 300.0)]
    lat.check_lattice_laws(lat.HistogramLattice.join, hists, eq=_tree_eq)


def test_obs_metrics_pytree_join_is_lattice():
    def sample(seed):
        rng = np.random.default_rng(seed)
        m = obsm.make_obs_metrics(2, n_items=8)
        return obsm.ObsMetrics(
            latency=m.latency._replace(counts=jnp.asarray(
                rng.integers(0, 9, m.latency.counts.shape, dtype=np.int32))),
            aborts=lat.CounterLattice(jnp.asarray(
                rng.integers(0, 9, (2,), dtype=np.int32))),
            cold_rejects=lat.CounterLattice(jnp.asarray(
                rng.integers(0, 9, (2,), dtype=np.int32))),
            item_access=lat.CounterLattice(jnp.asarray(
                rng.integers(0, 9, (2, 8), dtype=np.int32))))
    lat.check_lattice_laws(obsm.obs_metrics_join,
                           [sample(s) for s in range(3)], eq=_tree_eq)


# ---------------------------------------------------------------------------
# Recorders: the deferred per-chunk folds count exactly what ran
# ---------------------------------------------------------------------------


class _FakeNewOrders:
    """Just the four fields record_chunk reads, stacked [T, B, ...]."""

    def __init__(self, i_id, n_lines, supply_w, w):
        self.i_id, self.n_lines, self.supply_w, self.w = i_id, n_lines, supply_w, w


def _fake_chunk(T=3, B=4, L=5, n_items=32, seed=0):
    rng = np.random.default_rng(seed)
    i_id = jnp.asarray(rng.integers(0, n_items, (T, B, L), dtype=np.int32))
    n_lines = jnp.asarray(rng.integers(1, L + 1, (T, B), dtype=np.int32))
    w = jnp.zeros((T, B), jnp.int32)
    supply_w = jnp.asarray(rng.integers(0, 2, (T, B, L), dtype=np.int32))
    return _FakeNewOrders(i_id, n_lines, supply_w, w)


def test_record_chunk_totals_merge_regime():
    T, B, n_items = 3, 4, 32
    no = _fake_chunk(T, B, n_items=n_items)
    m = obsm.record_chunk(obsm.make_obs_metrics(1, n_items), no, ok=None)
    lat_counts = np.asarray(m.latency.counts)[0]
    # every New-Order commits in the merge regime: one observation per txn
    assert int(lat_counts[obsm.TXN_TYPES.index("neworder")].sum()) == T * B
    # other txn types untouched by record_chunk
    assert int(lat_counts.sum()) == T * B
    # attempted item demand counts every VALID line, committed or not
    assert int(m.item_access.value().sum()) == int(no.n_lines.sum())


def test_record_chunk_latency_proxy_bins():
    # all-local chunk: every txn's visibility proxy is 1 step -> bin 0
    no = _fake_chunk()
    no.supply_w = jnp.zeros_like(no.supply_w)  # every line home-local
    m = obsm.record_chunk(obsm.make_obs_metrics(1, 32), no, ok=None)
    row = np.asarray(m.latency.counts)[0, obsm.TXN_TYPES.index("neworder")]
    assert row[0] == no.n_lines.size and row[1:].sum() == 0
    # all-remote chunk: step t commits at the chunk drain, proxy 1 + T - t > 1
    no2 = _fake_chunk()
    no2.supply_w = jnp.ones_like(no2.supply_w)
    m2 = obsm.record_chunk(obsm.make_obs_metrics(1, 32), no2, ok=None)
    row2 = np.asarray(m2.latency.counts)[0, obsm.TXN_TYPES.index("neworder")]
    assert row2[0] == 0 and row2.sum() == no2.n_lines.size


def test_record_chunk_commit_mask_weights():
    T, B = 3, 4
    no = _fake_chunk(T, B)
    ok = jnp.asarray(np.random.default_rng(1).integers(0, 2, (T, B)),
                     jnp.bool_)
    m = obsm.record_chunk(obsm.make_obs_metrics(1, 32), no, ok=ok)
    lat_counts = np.asarray(m.latency.counts)[0]
    # the latency histogram is committed-weighted...
    assert int(lat_counts.sum()) == int(ok.sum())
    # ...but item demand still counts aborted attempts (contention signal)
    assert int(m.item_access.value().sum()) == int(no.n_lines.sum())


def test_fold_counters_lands_in_bin_zero():
    m = obsm.make_obs_metrics(1, 8)
    one = lambda v: jnp.asarray([v], jnp.int32)
    m = obsm.fold_counters(m, one(5), one(3), one(2), one(1), one(7))
    lat_counts = np.asarray(m.latency.counts)[0]
    for name, want in (("payment", 5), ("order_status", 3),
                       ("stock_level", 2), ("delivery", 1)):
        row = lat_counts[obsm.TXN_TYPES.index(name)]
        assert row[0] == want and row.sum() == want  # local => proxy bin 0
    assert np.asarray(m.aborts.slots).tolist() == [7]


def test_histogram_quantile_upper_edge():
    h = lat.HistogramLattice.make(1, 8)  # interior edges [2, 4, ..., 128]
    counts = np.zeros(8, np.int64)
    counts[0], counts[3] = 10, 1
    assert obsm.histogram_quantile(h.edges, counts, 0.50) == 2.0
    assert obsm.histogram_quantile(h.edges, counts, 0.99) == 16.0
    assert obsm.histogram_quantile(h.edges, np.zeros(8), 0.5) == 0.0


# ---------------------------------------------------------------------------
# Engine level: metrics are write-only (bit-exactness) + totals cross-check
# ---------------------------------------------------------------------------

_RUN_KW = dict(batch_per_shard=8, n_batches=12, merge_every=4,
               remote_frac=0.2, payments=True, reads=True, deliveries=True,
               seed=3)


def _run_pair(stock_invariant):
    kw = {} if stock_invariant is None else dict(
        stock_invariant=stock_invariant)
    eng = single_host_engine(TPCCScale(n_warehouses=4), **kw)

    def fresh():  # per-run state: the executor donates its input buffers
        base = init_state(eng.scale)
        if stock_invariant == "strict":
            base = base._replace(s_quantity=base.s_quantity * 20)
        return eng.shard_state(base)

    s_off, _, st_off = run_loop(eng, fresh(), **_RUN_KW)
    obs = ObsSession(metrics=True)
    s_on, _, st_on = run_loop(eng, fresh(), obs=obs, **_RUN_KW)
    return eng, (s_off, st_off), (s_on, st_on), obs


@pytest.mark.slow
def test_metrics_on_is_bit_exact_merge_regime():
    _, (s_off, st_off), (s_on, st_on), obs = _run_pair(None)
    assert _tree_eq(s_on, s_off)
    assert st_on.committed == st_off.committed
    snap = obs.snapshot()
    # histogram totals are the run's committed counts, per transaction type
    assert snap["latency"]["neworder"]["count"] == st_on.neworders
    assert snap["latency"]["payment"]["count"] == st_on.payments
    assert snap["latency"]["order_status"]["count"] == st_on.order_statuses
    assert snap["latency"]["stock_level"]["count"] == st_on.stock_levels
    assert snap["latency"]["delivery"]["count"] == st_on.deliveries
    assert sum(snap["counters"]["aborts_per_replica"]) == 0
    assert snap["item_access"]["total_line_demand"] > 0


@pytest.mark.slow
def test_metrics_on_is_bit_exact_escrow_regime():
    _, (s_off, st_off), (s_on, st_on), obs = _run_pair("strict")
    assert _tree_eq(s_on, s_off)
    assert (st_on.committed, st_on.aborts, st_on.cold_rejects) == \
           (st_off.committed, st_off.aborts, st_off.cold_rejects)
    snap = obs.snapshot()
    # committed-weighted histogram == committed New-Orders; the per-replica
    # abort/cold-reject counters sum to the stats the drain reported
    assert snap["latency"]["neworder"]["count"] == st_on.neworders
    assert sum(snap["counters"]["aborts_per_replica"]) == st_on.aborts
    assert sum(snap["counters"]["cold_rejects_per_replica"]) == \
        st_on.cold_rejects


# ---------------------------------------------------------------------------
# Coordination ledger: the zero hot budget
# ---------------------------------------------------------------------------

_CLEAN_HLO = "  %add.1 = f32[8]{0} add(%a.0, %b.0)\n"
_DIRTY_HLO = ("  %ar.1 = f32[128]{0} all-reduce(%x.0), "
              "replica_groups={{0,1}}\n")


def test_ledger_hot_budget():
    led = CoordinationLedger(context="unit", txns_per_chunk=10)
    led.add("hot scan", _CLEAN_HLO, hot=True)
    led.add("drain", _DIRTY_HLO, hot=False, calls_per_chunk=0.5)
    led.assert_budget()  # cold collectives are accounting, not violations
    assert led.hot_collectives() == 0
    assert led.bytes_per_chunk() == pytest.approx(512 * 0.5)
    assert led.bytes_per_txn() == pytest.approx(25.6)
    snap = led.snapshot()
    assert snap["hot_collectives"] == 0
    assert [e["phase"] for e in snap["phases"]] == ["hot scan", "drain"]

    led.add("leaky scan", _DIRTY_HLO, hot=True)
    with pytest.raises(AssertionError, match="leaky scan"):
        led.assert_budget()


@pytest.mark.slow
def test_build_ledger_hot_phases_are_collective_free():
    eng = single_host_engine(TPCCScale(n_warehouses=4),
                             stock_invariant="strict")
    led = build_ledger(eng, chunk_len=4, batch_per_shard=8, metrics=True)
    snap = led.snapshot()
    assert snap["hot_collectives"] == 0
    phases = {e["phase"]: e for e in snap["phases"]}
    # the obs plane's own programs are in their own ledger, hot-budgeted
    assert phases["metrics record"]["hot"]
    assert phases["metrics record"]["collectives"] == {}
    assert phases["metrics counter fold"]["collectives"] == {}
