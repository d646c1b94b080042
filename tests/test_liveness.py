"""Self-detecting liveness: lease lattice laws, the monitor's detection /
hysteresis algebra, the no-caller-mask chaos loop (kill -> detect -> reclaim
-> degraded serving -> revive -> handback), and the cold-line reservation
round-trip that bounds tail starvation.

Deterministic tests always run; hypothesis sweeps (revive-never-oversells,
reservation-rescues-starved-line) run where hypothesis is installed — CI
installs it via the ``test`` extra.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # container without hypothesis: deterministic only
    HAVE_HYPOTHESIS = False

from repro.core.lattice import (LeaseLattice, check_lattice_laws, get_bottom,
                                get_join, pack_lease_stamp,
                                unpack_lease_stamp)
from repro.runtime.failures import EscrowPodSimulator, PodSimulator
from repro.runtime.liveness import LeaseMonitor
from repro.txn import tpcc
from repro.txn.audit import check_cold_ledger


def _scale():
    return tpcc.TPCCScale(n_warehouses=4, districts=2, customers=8,
                          n_items=32, order_capacity=1024, max_lines=15)


def _chaos_sim(**kw):
    defaults = dict(retry_cap=128, retry_max=3, seed=11, stock_scale=20,
                    liveness=True)
    defaults.update(kw)
    return EscrowPodSimulator(_scale(), 4, **defaults)


def _window(sim, batch=12):
    sim.step(batch)
    sim.drain()
    sim.refresh()


# ---------------------------------------------------------------------------
# Lease lattice: registration, laws, stamp packing
# ---------------------------------------------------------------------------


def test_lease_lattice_registered_and_lawful():
    """The lease lattice registers like every other CRDT in the repo and its
    join is commutative/associative/idempotent over adversarial samples
    (incl. epoch-bump dominance and high-seq stamps past 32 bits)."""
    assert get_join("lease") is LeaseLattice.join
    bottom = get_bottom("lease")(3)
    assert np.array_equal(np.asarray(bottom.stamps), np.zeros(3))
    samples = [
        bottom,
        bottom.beat(0, 0, 5),
        bottom.beat(1, 2, 1),                       # epoch 2 dominates
        bottom.beat(0, 1, 0).beat(2, 0, (1 << 33)),  # seq wraps into mask
        LeaseLattice(np.asarray([7, 0, 1 << 40], np.int64)),
    ]
    check_lattice_laws(LeaseLattice.join, samples)


def test_lease_stamp_pack_monotone_across_epochs():
    """Packed stamps order first by epoch, then by seq — a revived replica
    (epoch bump, seq reset) stays strictly above its old incarnation, so
    the fleet MaxReg never moves backwards through a rejoin."""
    assert int(pack_lease_stamp(0, 5)) < int(pack_lease_stamp(0, 6))
    assert int(pack_lease_stamp(0, (1 << 32) - 1)) < int(pack_lease_stamp(1, 0))
    e, s = unpack_lease_stamp(pack_lease_stamp(3, 41))
    assert (int(e), int(s)) == (3, 41)
    lat = LeaseLattice.make(2).beat(0, 0, 100)
    lat2 = lat.beat(0, 1, 0)    # rejoin: epoch 1, seq restarts
    assert int(lat2.stamps[0]) > int(lat.stamps[0])
    # a stale duplicate of the old incarnation joins in as a no-op
    joined = LeaseLattice.join(lat2, lat)
    assert np.array_equal(joined.stamps, lat2.stamps)


# ---------------------------------------------------------------------------
# LeaseMonitor: detection bound, hysteresis, revival
# ---------------------------------------------------------------------------


def test_monitor_detects_within_bound_and_revives():
    mon = LeaseMonitor(3, expiry=1, hysteresis=1)
    seq = [0, 0, 0]

    def beat_all(except_for=()):
        for r in range(3):
            if r not in except_for:
                seq[r] += 1
                mon.beat(r, 0, seq[r])

    for _ in range(3):
        beat_all()
        assert mon.tick().all()
    # replica 1 goes silent: must be declared dead within detection_bound
    died_at = mon.window
    while mon.window < died_at + mon.detection_bound:
        beat_all(except_for=(1,))
        alive = mon.tick()
    assert not alive[1] and alive[0] and alive[2]
    assert mon.detection_lags() == [mon.detection_bound]
    # silence continues: no duplicate detection events
    beat_all(except_for=(1,))
    mon.tick()
    assert len(mon.detections) == 1
    # replica 1 beats again (false suspicion): revived automatically
    beat_all()
    assert mon.tick().all()
    assert mon.revivals and mon.revivals[-1][1] == 1


def test_monitor_straggler_survives_hysteresis():
    """A replica silent for <= expiry + hysteresis windows is never
    declared dead — one slow chunk costs nothing."""
    mon = LeaseMonitor(2, expiry=1, hysteresis=1)
    seq = 0
    for w in range(12):
        seq += 1
        mon.beat(0, 0, seq)
        # replica 1 beats only every other window (always one stall long,
        # inside the hysteresis allowance)
        if w % 2 == 0:
            mon.beat(1, 0, w + 1)
        assert mon.tick().all()
    assert mon.detections == []


def test_monitor_source_polled_each_tick():
    stamps = np.zeros(2, np.int64)
    mon = LeaseMonitor(2, source=lambda w: stamps)
    stamps[:] = [int(pack_lease_stamp(0, 1))] * 2
    assert mon.tick().all()
    # only replica 0 advances from here on
    for w in range(2, 2 + mon.detection_bound):
        stamps[0] = int(pack_lease_stamp(0, w))
        alive = mon.tick()
    assert alive[0] and not alive[1]


# ---------------------------------------------------------------------------
# PodSimulator dataclass hygiene (the default_factory fix)
# ---------------------------------------------------------------------------


def test_pod_simulator_fields_never_alias():
    """Two simulators must not share mutable field storage, and
    caller-provided states/alive must survive __post_init__ (the
    ``list = None`` + unconditional-overwrite footgun this guards)."""
    class _Setup:
        init_fn = staticmethod(lambda key: {"p": np.zeros(2)})
        step_fn = staticmethod(lambda s, b: s)

    a = PodSimulator(_Setup(), n_pods=2)
    b = PodSimulator(_Setup(), n_pods=2)
    assert a.states is not b.states and a.alive is not b.alive
    assert a.metric_joined is not b.metric_joined
    assert a.metric_joined["loss"] is not b.metric_joined["loss"]
    a.kill(0)
    assert b.alive == [True, True]
    # caller-provided fleet image is kept, not clobbered
    provided = [{"p": np.ones(2)}, {"p": np.ones(2)}]
    c = PodSimulator(_Setup(), n_pods=2, states=provided, alive=[True, False])
    assert c.states is provided and c.alive == [True, False]


# ---------------------------------------------------------------------------
# Chaos matrix: the closed loop with NO caller-provided mask
# ---------------------------------------------------------------------------


def _quiesce_and_check(sim):
    sim.quiesce()
    led = sim.cold_ledger()
    check_cold_ledger(led, quiescent=True)
    sim.refresh()           # reconcile shares with post-drain stock
    sim.audit()
    return led


def test_chaos_single_kill_detect_reclaim_degraded_continue():
    """kill -> (lease detects) -> reclaim + successor adoption -> survivors
    keep serving AND the dead shard's cold traffic keeps draining — nobody
    ever hands the simulator an alive mask."""
    sim = _chaos_sim()
    for _ in range(3):
        _window(sim)
    committed_before = sim.committed
    sim.kill(1)
    windows_to_detect = 0
    while sim.alive[1]:
        _window(sim)
        windows_to_detect += 1
        assert windows_to_detect <= sim.monitor.detection_bound, \
            "detection exceeded the lease bound"
    # detection recorded, at the bound for a hard kill
    assert sim.monitor.detection_lags() == [sim.monitor.detection_bound]
    # successor adoption: shard 1 re-keyed to a live replica in ring order
    assert sim.owner_of[1] == 2
    queued_at_dead = sum(1 for _ in sim.pending[1])
    for _ in range(3):
        _window(sim)
    # degraded-mode elastic continue: fleet still commits, and the dead
    # shard's queue is NOT frozen (the successor drains it)
    assert sim.committed > committed_before
    assert len(sim.pending[1]) == 0 or queued_at_dead == 0
    led = _quiesce_and_check(sim)
    assert led["queued"] == 0, "dead shard's cold traffic starved"


def test_chaos_kill_then_revive_hands_shard_back():
    sim = _chaos_sim()
    for _ in range(2):
        _window(sim)
    sim.kill(3)
    for _ in range(sim.monitor.detection_bound + 1):
        _window(sim)
    assert not sim.alive[3] and sim.owner_of[3] == 0  # ring wraps 3 -> 0
    sim.revive(3)
    for _ in range(2):
        _window(sim)
    # the beat (under a bumped epoch) re-admits the replica; ownership
    # hands back deterministically
    assert sim.alive[3] and sim.owner_of[3] == 3
    assert sim.epoch[3] == 1
    _quiesce_and_check(sim)


def test_chaos_false_suspicion_self_fences_then_recovers():
    """A straggler stalled past the lease bound is falsely declared dead;
    it self-fences (stops serving) while suspected, its shard is adopted,
    and its next beat revives it — min-join share safety means the window
    of suspicion can waste throughput but never oversell (the audit's
    never-oversell law holds through the whole episode)."""
    sim = _chaos_sim()
    for _ in range(2):
        _window(sim)
    long_stall = sim.monitor.detection_bound + 2
    sim.stall(0, long_stall)
    saw_suspected = False
    for _ in range(long_stall + 2):
        _window(sim)
        if not sim.alive[0]:
            saw_suspected = True
            assert sim.owner_of[0] == 1      # adopted while suspected
    assert saw_suspected, "stall past the bound must trigger suspicion"
    for _ in range(2):
        _window(sim)
    # the stall ended; beats resumed; the fleet re-admitted it
    assert sim.alive[0] and sim.owner_of[0] == 0
    assert sim.monitor.revivals
    _quiesce_and_check(sim)


def test_chaos_straggler_within_hysteresis_not_suspected():
    sim = _chaos_sim()
    for _ in range(2):
        _window(sim)
    sim.stall(2, sim.lease_expiry + sim.lease_hysteresis)  # inside allowance
    for _ in range(6):
        _window(sim)
        assert sim.alive[2], "straggler inside hysteresis must survive"
    assert sim.monitor.detections == []
    _quiesce_and_check(sim)


def test_chaos_cascading_kills_last_survivor_serves_all():
    sim = _chaos_sim()
    for _ in range(2):
        _window(sim)
    sim.kill(0)
    for _ in range(sim.monitor.detection_bound):
        _window(sim)
    sim.kill(1)
    sim.kill(3)
    for _ in range(sim.monitor.detection_bound + 1):
        _window(sim)
    assert sim.alive == [False, False, True, False]
    assert sim.owner_of == [2, 2, 2, 2]      # one survivor owns everything
    for _ in range(2):
        _window(sim)
    led = _quiesce_and_check(sim)
    assert led["queued"] == 0


def test_liveness_off_is_legacy_bit_identical():
    """liveness=False keeps the omniscient-caller semantics bit-exactly:
    same seeds, same kills, same final state and ledger as before the
    lease layer existed (the PR-7 tests' world)."""
    def run(liveness):
        sim = EscrowPodSimulator(_scale(), 4, retry_cap=64, retry_max=2,
                                 seed=5, stock_scale=10, liveness=liveness)
        for _ in range(4):
            _window(sim, batch=8)
        return sim
    legacy = run(False)
    lease = run(True)
    # no kills: identical traffic, identical state
    for a, b in zip(jax.tree.leaves(legacy.full_state()),
                    jax.tree.leaves(lease.full_state())):
        assert bool((a == b).all())
    assert legacy.cold_ledger() == lease.cold_ledger()


# ---------------------------------------------------------------------------
# Reservations: the round-trip that bounds tail starvation
# ---------------------------------------------------------------------------

_RES_SCALE = tpcc.TPCCScale(1, 2, 16, 64, 1024, 15)
_HOT0 = jnp.asarray([0], jnp.int32)   # cell (0, 0) hot; everything else cold


def _res_window(st, ring, entries, reserve, retry_max=3):
    """One drain window over explicit (w, i, qty) cold entries."""
    n = max(len(entries), 1)
    dst = np.zeros(n, np.int32)
    iid = np.zeros(n, np.int32)
    qty = np.zeros(n, np.int32)
    mask = np.zeros(n, bool)
    for j, (w, i, q) in enumerate(entries):
        dst[j], iid[j], qty[j], mask[j] = w, i, q, True
    return tpcc.apply_stock_updates_strict_tiered_retry(
        st, _HOT0, jnp.asarray(dst), jnp.asarray(iid), jnp.asarray(qty),
        jnp.asarray(mask), jnp.ones(n, jnp.bool_), ring,
        _RES_SCALE.n_items, retry_max=retry_max, reserve=reserve)


def _local_sale(st, cell_i, qty):
    """The owner's hot path consuming local cold stock between drains
    (FCFS: admits iff it fits) — the traffic reservations protect against."""
    have = int(st.s_quantity[0, cell_i])
    if qty > have:
        return st
    return st._replace(
        s_quantity=st.s_quantity.at[0, cell_i].add(-qty),
        s_ytd=st.s_ytd.at[0, cell_i].add(float(qty)))


def _starved_line_outcome(reserve, *, stock, blocker, victim, local_sale,
                          cell=5):
    """Drive the head-of-line starvation schedule; returns (victim_applied,
    finals, end_stock).  Schedule: an OLD blocker enters the ring first
    (greedy-by-age sorts it ahead forever), the victim arrives a window
    later (rejected at arrival by all-or-nothing alongside a helper
    blocker), then the owner's local traffic consumes stock between the
    victim's last-chance window and its final window."""
    st = tpcc.init_state(_RES_SCALE, seed=0)
    st = st._replace(s_quantity=st.s_quantity.at[0, cell].set(stock),
                     s_ytd=st.s_ytd.at[0, cell].set(0.0))
    sold0 = float(st.s_ytd[0, cell])
    ring = tpcc.empty_retry(8)
    finals = 0
    # w0: old blocker alone -> rejected into the ring
    st, ring, f = _res_window(st, ring, [(0, cell, blocker)], reserve)
    finals += int(f)
    # w1: victim + helper together (window total can't fit) -> both ring
    st, ring, f = _res_window(st, ring, [(0, cell, victim),
                                         (0, cell, blocker)], reserve)
    finals += int(f)
    # w2: all three re-present; every prefix poisoned by the old blocker
    st, ring, f = _res_window(st, ring, [], reserve)
    finals += int(f)
    # w3: the victim's LAST-CHANCE window (old blocker finals here and
    # still poisons pass-1; with reserve on, pass 3 grants the victim)
    st, ring, f = _res_window(st, ring, [], reserve)
    finals += int(f)
    # between windows: the owner's local hot path consumes the cell
    before_sale = float(st.s_ytd[0, cell])
    st = _local_sale(st, cell, local_sale)
    sold_locally = float(st.s_ytd[0, cell]) - before_sale
    # w4: victim's final window (reserve off) / completion window (on)
    st, ring, f = _res_window(st, ring, [], reserve)
    finals += int(f)
    for _ in range(3):      # drain the helper out
        st, ring, f = _res_window(st, ring, [], reserve)
        finals += int(f)
    assert int(np.asarray(ring.valid).sum()) == 0
    victim_applied = (float(st.s_ytd[0, cell]) - sold0) - sold_locally
    return victim_applied, finals, int(st.s_quantity[0, cell])


def test_reservation_rescues_starved_line():
    """The property reservations exist for: greedy-by-age ALONE
    final-rejects a small line the reservation path admits.  The victim is
    head-of-line blocked through every retry (an older blocker poisons its
    pass-1 prefix), and by its final window the owner's local traffic has
    consumed the stock that covered it — the reservation's grant-now
    semantics claims the stock one window earlier, while it still fits."""
    kw = dict(stock=10, blocker=100, victim=8, local_sale=3)
    v0, finals0, stock0 = _starved_line_outcome(0, **kw)
    v1, finals1, stock1 = _starved_line_outcome(1, **kw)
    # greedy-by-age alone: victim starves (3 finals: 2 blockers + victim)
    assert v0 == 0.0 and finals0 == 3
    # reservations: victim applied at grant, only the blockers final
    assert v1 >= 8.0 and finals1 == 2
    assert stock1 == stock0 - 8 + 3   # grant debited; local sale fenced out


def test_reserve_zero_is_bit_identical_and_never_reserves():
    """reserve=0 must be the pre-reservation drain bit-exactly: identical
    state/ring/finals, and the reserved lane never sets."""
    st = tpcc.init_state(_RES_SCALE, seed=1)
    st = st._replace(s_quantity=st.s_quantity.at[0, 5].set(7))
    rng = np.random.default_rng(0)
    sa = sb = st
    ra = rb = tpcc.empty_retry(8)
    for w in range(6):
        entries = [(0, 5, int(rng.integers(1, 9))) for _ in range(3)]
        sa, ra, fa = _res_window(sa, ra, entries, reserve=0)
        sb, rb, fb = _res_window(sb, rb, entries, reserve=jnp.asarray(0))
        assert int(fa) == int(fb)
        assert not bool(np.asarray(ra.reserved).any())
        for x, y in zip(jax.tree.leaves((sa, ra)), jax.tree.leaves((sb, rb))):
            assert bool((x == y).all())


def test_reservation_never_oversells_and_ledger_exact():
    """Simulator-level: reservations under real chaos keep stock
    nonnegative at every window (the grant IS the admission), the extended
    ledger identity res_granted == res_completed + reserved_in_ring holds
    continuously, and quiescence closes both ledgers exactly."""
    sim = _chaos_sim(reserve=True, stock_scale=2, seed=3)
    sim.kill(2)
    for w in range(8):
        _window(sim, batch=16)
        assert bool((sim.full_state().s_quantity >= 0).all())
        led = sim.cold_ledger()
        assert led["exact"] and led["reservations_exact"], led
    sim.revive(2)
    for w in range(3):
        _window(sim, batch=16)
    led = _quiesce_and_check(sim)
    assert led["res_granted"] == led["res_completed"]


if HAVE_HYPOTHESIS:

    @settings(max_examples=25, deadline=None)
    @given(stock=st.integers(5, 40), victim=st.integers(2, 10),
           sale_frac=st.floats(0.2, 0.95))
    def test_reservation_rescue_property(stock, victim, sale_frac):
        """Across the starvation regime (victim fits stock; the local sale
        leaves less than the victim needs), greedy-by-age alone ALWAYS
        final-rejects the victim and reservations ALWAYS admit it."""
        if victim > stock:
            victim = stock
        local_sale = int(sale_frac * stock)
        if stock - local_sale >= victim:      # keep inside the regime
            local_sale = stock - victim + 1
        kw = dict(stock=stock, blocker=10 * stock, victim=victim,
                  local_sale=local_sale)
        v0, f0, _ = _starved_line_outcome(0, **kw)
        v1, f1, _ = _starved_line_outcome(1, **kw)
        assert v0 == 0.0 and f0 == 3
        assert v1 >= float(victim) and f1 == 2

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 2 ** 16),
           kills=st.lists(st.tuples(st.integers(0, 3), st.integers(1, 4),
                                    st.integers(1, 5)),
                          min_size=1, max_size=3, unique_by=lambda t: t[0]))
    def test_revive_never_oversells_sweep(seed, kills):
        """Random kill/revive schedules, lease detection only (no caller
        mask): stock stays nonnegative at every window, the ledgers stay
        exact, and the quiescent audit (conservation + never-oversell +
        escrow-covers-stock) passes — false suspicion and revival can waste
        throughput but can never manufacture admission capacity."""
        sim = _chaos_sim(reserve=True, seed=seed, stock_scale=4)
        schedule = {}
        for replica, at, dur in kills:
            schedule[at] = schedule.get(at, []) + [(replica, dur)]
        revive_at = {}
        for w in range(10):
            for replica, dur in schedule.get(w, []):
                sim.kill(replica)
                revive_at.setdefault(w + dur, []).append(replica)
            for replica in revive_at.get(w, []):
                sim.revive(replica)
            _window(sim, batch=8)
            assert bool((sim.full_state().s_quantity >= 0).all())
            led = sim.cold_ledger()
            assert led["exact"] and led["reservations_exact"], led
        for replicas in revive_at.values():
            for replica in replicas:
                if not sim.up[replica]:
                    sim.revive(replica)
        for _ in range(sim.monitor.detection_bound + 1):
            _window(sim, batch=8)
        _quiesce_and_check(sim)


# ---------------------------------------------------------------------------
# Driver wiring + the HLO collective budget with liveness/reserve on
# ---------------------------------------------------------------------------


def test_run_loop_liveness_matches_caller_mask():
    """run_loop(liveness=...) with an always-beating monitor is bit-exact
    to the alive=None run — the self-derived all-alive mask and the
    implicit one compile and execute to the same refresh."""
    from repro.txn.drivers import run_loop
    from repro.txn.engine import single_host_engine

    scale = _scale()
    eng = single_host_engine(scale, stock_invariant="strict")
    state0 = eng.shard_state(tpcc.init_state(scale, seed=0))
    kw = dict(batch_per_shard=8, n_batches=8, remote_frac=0.5,
              merge_every=4, refresh_every=1, seed=7, retry_cap=32,
              retry_max=2)

    def always_beating():
        mon = LeaseMonitor(eng.n_shards)
        seq = {"n": 0}

        def source(window):
            seq["n"] += 1
            return np.asarray([int(pack_lease_stamp(0, seq["n"]))]
                              * eng.n_shards, np.int64)
        mon.source = source
        return mon

    s_ref, e_ref, _ = run_loop(eng, jax.tree.map(jnp.copy, state0), **kw)
    mon = always_beating()
    s_liv, e_liv, _ = run_loop(eng, jax.tree.map(jnp.copy, state0),
                               liveness=mon, **kw)
    assert mon.window > 0, "monitor was never ticked"
    for a, b in zip(jax.tree.leaves((s_ref, e_ref)),
                    jax.tree.leaves((s_liv, e_liv))):
        assert bool((a == b).all())
    # dispatch mode threads the same wiring
    mon2 = always_beating()
    s_d, e_d, _ = run_loop(eng, jax.tree.map(jnp.copy, state0), fused=False,
                           liveness=mon2, **kw)
    assert mon2.window > 0


def test_hot_path_collective_free_with_liveness_and_reserve():
    """Acceptance: the hot path stays HLO-proved collective-free with the
    liveness layer on (heartbeats are host-resident metadata riding the
    drain — the compiled megastep is untouched), and the reserve-enabled
    retry drain keeps the exact collective budget of the plain strict
    drain (reservations are owner-local, never gathered)."""
    from repro.txn.engine import single_host_engine
    from repro.txn.executor import get_fused_executor

    eng = single_host_engine(_scale(), stock_invariant="strict")
    ex = get_fused_executor(eng, ring_rows=4, retry_cap=16)
    ex.prove_megastep_coordination_free(chunk_len=4, batch_per_shard=8)
    plain = ex.count_drain_strict_collectives(8)
    with_reserve = ex.count_drain_strict_retry_collectives(8)
    assert dict(with_reserve.counts) == dict(plain.counts)


def test_obs_session_reports_detection_latency():
    """Detection lags feed the obs plane as a histogram lattice: the
    session snapshot grows a detection_latency summary, and joins from two
    monitors merge commutatively."""
    from repro.obs import ObsSession
    from repro.obs.metrics import (heartbeat_lag_histogram,
                                   heartbeat_lag_summary)
    from repro.core.lattice import HistogramLattice

    sess = ObsSession(metrics=False)
    sess.record_heartbeat_lags([3, 3, 4])
    sess.record_heartbeat_lags([2])
    snap = sess.snapshot()
    assert snap["detection_latency"]["count"] == 4
    assert snap["detection_latency"]["p99_windows"] >= 4
    a = heartbeat_lag_histogram([1, 5])
    b = heartbeat_lag_histogram([8])
    ab = HistogramLattice.join(a, b)
    ba = HistogramLattice.join(b, a)
    assert np.array_equal(np.asarray(ab.counts), np.asarray(ba.counts))
    assert heartbeat_lag_summary(ab)["count"] == 3
