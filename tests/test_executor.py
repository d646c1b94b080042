"""Fused megastep executor (txn/executor.py):

* bit-exact final-state equivalence vs the per-batch dispatch driver on a
  fixed seed (same pre-generated stream, same drain cadence);
* the hot scan's compiled HLO contains ZERO collective ops while the drain
  (off the hot path) is the only communicating program;
* donation actually consumes the input buffers (no doubled live state) and
  the compiled module carries input/output aliasing;
* reduced mixes (no reads / no payments / no deliveries) and ragged tail
  chunks execute correctly;
* a call's ring and counters are zeros on the run sharding, a buffer per
  field, on one device and on four;
* two calls of one chunk each land bit-exactly where one call of both
  chunks does, in both regimes.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.txn.audit import assert_audit
from repro.txn.engine import (run_closed_loop, run_escrow_loop,
                              run_mixed_loop, single_host_engine)
from repro.txn.executor import (FusedExecutor, MixChunk, MixCounters,
                                counters_to_stats, run_fused_loop,
                                stack_chunks)
from repro.txn.engine import generate_mix_batches
from repro.txn.tpcc import TPCCScale, check_consistency, init_state

SCALE = TPCCScale(n_warehouses=4, districts=4, customers=8, n_items=64,
                  order_capacity=128, max_lines=15)


@pytest.fixture(scope="module")
def engine():
    return single_host_engine(SCALE)


@pytest.fixture(scope="module")
def escrow_engine():
    return single_host_engine(SCALE, stock_invariant="strict")


def _tree_equal(a, b):
    eq = jax.tree.map(lambda x, y: bool((x == y).all()), a, b)
    return [f for f, ok in zip(a._fields, eq) if not ok]


def test_fused_bitexact_vs_dispatch(engine):
    """The tentpole equivalence: identical stream, identical cadence =>
    bit-identical final state and identical MixStats counters — including a
    ragged tail chunk (10 batches, merge_every=4 -> chunks of 4, 4, 2)."""
    kw = dict(batch_per_shard=8, n_batches=10, merge_every=4,
              remote_frac=0.3, read_frac=0.25, seed=3)
    s1 = engine.shard_state(init_state(SCALE))
    s1, m1 = run_mixed_loop(engine, s1, fused=False, **kw)
    s2 = engine.shard_state(init_state(SCALE))
    s2, m2 = run_mixed_loop(engine, s2, fused=True, **kw)

    assert _tree_equal(s1, s2) == []
    for f in ("neworders", "payments", "order_statuses", "stock_levels",
              "deliveries", "anti_entropy_rounds", "reads_found",
              "fractures_observed", "lines_repaired"):
        assert getattr(m1, f) == getattr(m2, f), f
    assert m2.fractures_observed == 0  # RAMP atomic visibility holds fused
    assert all(check_consistency(s2).values())
    assert_audit(s2)


def test_escrow_fused_dispatch_legacy_bitexact(escrow_engine):
    """The escrow-regime equivalence, three ways: fused (escrow counters in
    the donated scan carry, refresh fused into the drain), per-batch
    dispatch, and legacy (per-outbox drains, per-batch host stat reads) run
    the identical stream at the identical drain/refresh cadence and land on
    bit-identical state, escrow counters, and MixStats — including a ragged
    tail chunk and a non-trivial refresh cadence."""
    eng = escrow_engine
    kw = dict(batch_per_shard=8, n_batches=10, merge_every=4,
              refresh_every=2, remote_frac=0.3, read_frac=0.25, seed=3,
              mix=True)
    finals = {}
    for name, mode in (("fused", dict(fused=True)),
                       ("dispatch", dict(fused=False)),
                       ("legacy", dict(legacy=True))):
        s = eng.shard_state(init_state(SCALE))
        q0 = s.s_quantity.copy()
        finals[name] = run_escrow_loop(eng, s, **mode, **kw)
    s_f, esc_f, m_f = finals["fused"]
    for other in ("dispatch", "legacy"):
        s_o, esc_o, m_o = finals[other]
        assert _tree_equal(s_f, s_o) == [], other
        assert _tree_equal(esc_f, esc_o) == [], other
        for f in ("neworders", "aborts", "payments", "order_statuses",
                  "stock_levels", "deliveries", "anti_entropy_rounds",
                  "refreshes", "reads_found", "fractures_observed",
                  "lines_repaired"):
            assert getattr(m_f, f) == getattr(m_o, f), (other, f)
    assert m_f.aborts > 0              # adversarial: demand exceeds shares
    assert m_f.refreshes == 1          # 3 drains, refresh_every=2
    assert m_f.fractures_observed == 0
    assert_audit(s_f, escrow=esc_f, initial_stock=q0, strict_stock=True)


def test_escrow_megastep_zero_collectives(escrow_engine):
    """The escrow hot path between refreshes — merge_every full-mix
    iterations including the try_spend admission scan — compiles with ZERO
    collective ops; the fused drain+refresh is the only communicating
    program of the regime."""
    ex = FusedExecutor(escrow_engine, ring_rows=4)
    desc = ex.prove_megastep_coordination_free(chunk_len=4, batch_per_shard=4,
                                               read_per_shard=2)
    assert "NONE" in desc
    assert ex.count_drain_refresh_collectives(4).total_ops > 0
    # escrow executors refuse the free-regime entry points and vice versa
    state = escrow_engine.shard_state(init_state(SCALE))
    with pytest.raises(RuntimeError, match="use run_escrow"):
        ex.run(state, [])
    ex_free = FusedExecutor(single_host_engine(SCALE), ring_rows=4)
    with pytest.raises(RuntimeError, match="use run"):
        ex_free.run_escrow(state, None, [])


def test_megastep_hot_scan_zero_collectives(engine):
    """Definition 5 on the fused path: merge_every full-mix iterations
    compile with no collective ops; the chunk drain is where (all of) the
    communication lives."""
    ex = FusedExecutor(engine, ring_rows=4)
    desc = ex.prove_megastep_coordination_free(chunk_len=4, batch_per_shard=4,
                                               read_per_shard=2)
    assert "NONE" in desc
    # symmetric check on a multi-shard mesh lives in
    # test_engine.py::test_multi_device_proof_subprocess; here the drain
    # must at least compile and clear the ring
    state = engine.shard_state(init_state(SCALE))
    ring = ex.init_ring(4)
    state, ring2 = ex.drain(state, ring)
    assert not bool(jax.device_get(ring2.valid).any())


def test_megastep_donation_reuses_buffers(engine):
    """Donated state/ring/counters: inputs are consumed (buffers deleted,
    not copied) and the compiled module aliases inputs to outputs."""
    ex = FusedExecutor(engine, ring_rows=2)
    no_b, pay_b, os_b, sl_b = generate_mix_batches(
        engine, batch_per_shard=4, n_batches=2, seed=0)
    chunk = stack_chunks(no_b, pay_b, os_b, sl_b, 2)[0]
    state = engine.shard_state(init_state(SCALE))
    ring, counters = ex.init_ring(4), ex.init_counters()

    out = ex.megastep(state, ring, counters, chunk)
    assert state.s_ytd.is_deleted(), "donated state buffer survived"
    assert ring.valid.is_deleted(), "donated ring buffer survived"
    assert counters.neworders.is_deleted(), "donated counter buffer survived"
    text = ex.lowered_megastep(chunk_len=2, batch_per_shard=4,
                               read_per_shard=1).compile().as_text()
    assert "input_output_alias" in text

    state2, ring2 = ex.drain(out[0], out[1])
    assert out[0].s_ytd.is_deleted(), "drain did not consume donated state"
    jax.block_until_ready((state2, ring2))


def test_counters_accumulate_on_device(engine):
    """MixStats comes from ONE device_get over the counter pytree."""
    state = engine.shard_state(init_state(SCALE))
    no_b, pay_b, os_b, sl_b = generate_mix_batches(
        engine, batch_per_shard=8, n_batches=4, seed=7)
    ex = FusedExecutor(engine, ring_rows=4)
    chunks = stack_chunks(no_b, pay_b, os_b, sl_b, 4)
    state, counters, wall = ex.run(state, chunks)
    assert isinstance(counters.neworders, jax.Array)
    stats = counters_to_stats(counters, anti_entropy_rounds=len(chunks),
                              wall_seconds=wall)
    assert stats.neworders == 8 * 4
    assert stats.payments == 8 * 4
    assert stats.order_statuses == stats.stock_levels == 2 * 4
    assert stats.fractures_observed == 0
    assert stats.deliveries > 0


def test_reduced_mix_chunks(engine):
    """None-valued chunk fields statically drop transactions from the scan:
    the New-Order-only closed loop and a payment-less mix both run."""
    kw = dict(batch_per_shard=8, n_batches=6, merge_every=3, seed=11)
    s1 = engine.shard_state(init_state(SCALE))
    s1, r1 = run_closed_loop(engine, s1, fused=True, **kw)
    s2 = engine.shard_state(init_state(SCALE))
    s2, r2 = run_closed_loop(engine, s2, fused=False, **kw)
    assert _tree_equal(s1, s2) == []
    assert r1.committed == r2.committed == 8 * 6
    assert r1.anti_entropy_rounds == r2.anti_entropy_rounds == 2

    # payments+deliveries variant stays consistent end-to-end
    s3 = engine.shard_state(init_state(SCALE))
    s3, _ = run_closed_loop(engine, s3, payments=True, deliveries=True,
                            fused=True, **kw)
    assert all(check_consistency(s3).values())
    assert_audit(s3)


def test_chunk_longer_than_ring_rejected(engine):
    ex = FusedExecutor(engine, ring_rows=2)
    no_b, pay_b, os_b, sl_b = generate_mix_batches(
        engine, batch_per_shard=4, n_batches=3, seed=0)
    chunk = stack_chunks(no_b, pay_b, os_b, sl_b, 3)[0]
    state = engine.shard_state(init_state(SCALE))
    with pytest.raises(ValueError, match="exceeds"):
        ex.megastep(state, ex.init_ring(4), ex.init_counters(), chunk)


def test_fused_loop_direct_api(engine):
    """run_fused_loop is the public entry run_mixed_loop(fused=True) uses."""
    state = engine.shard_state(init_state(SCALE))
    state, stats = run_fused_loop(engine, state, batch_per_shard=8,
                                  n_batches=8, merge_every=8, seed=2)
    assert stats.neworders == 64
    assert stats.anti_entropy_rounds == 1
    assert stats.throughput > 0
    assert all(check_consistency(state).values())
    assert_audit(state)


_BUFFERS = r"""
import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.txn.engine import single_host_engine
from repro.txn.tpcc import TPCCScale
from repro.txn.executor import FusedExecutor

n = len(jax.devices())
scale = TPCCScale(n_warehouses=4, districts=4, customers=8, n_items=64,
                  order_capacity=128, max_lines=15)
eng = single_host_engine(scale)
ex = FusedExecutor(eng, ring_rows=4)
ring_sh = NamedSharding(eng.mesh, P(None, eng.axis_names))
count_sh = NamedSharding(eng.mesh, P(eng.axis_names))
R = 8 * n * scale.max_lines
ring_dt = ("int32", "int32", "int32", "bool")
for ring, counters in (ex.init_buffers(8),
                       (ex.init_ring(8), ex.init_counters())):
    want = [(x, (4, R), dt, ring_sh) for x, dt in zip(ring, ring_dt)] + [
        (x, (n,), "int32", count_sh) for x in counters]
    ptrs = set()
    for x, shape, dt, sh in want:
        assert (x.shape, str(x.dtype)) == (shape, dt), (x.shape, x.dtype)
        assert x._committed and x.sharding == sh, x.sharding
        assert not np.asarray(x).any()
        ptrs |= {s.data.unsafe_buffer_pointer() for s in x.addressable_shards}
    assert len(ptrs) == 13 * n, (len(ptrs), n)

# a call from host state places every table; the next call, on the first
# call's output, moves none
from repro.txn.drivers import generate_mix_batches
from repro.txn.executor import Prepared, stack_chunks
from repro.txn.tpcc import TPCCState, init_state
chunks = stack_chunks(*generate_mix_batches(eng, batch_per_shard=8,
                                            n_batches=2, seed=4), 2)
state = ex.run(init_state(scale), chunks)[0]
assert ex.last_prepare == Prepared(len(TPCCState._fields), 1), ex.last_prepare
ex.run(state, chunks, warmup=False)
assert ex.last_prepare == Prepared(0, 1), ex.last_prepare
print("OK", n)
"""


@pytest.mark.parametrize("devices", [1, 4])
def test_zero_buffers_on_run_sharding(devices):
    """init_ring / init_counters (and init_buffers, which makes both in one
    program) give zeros of the ring's and counters' dtypes and shapes,
    committed to the run sharding, with a buffer of its own for every
    field on every device; a call on the previous call's output moves no
    table. Four host devices run in a subprocess so this process keeps
    one."""
    if devices == 1:
        exec(_BUFFERS, {})
        return
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", _BUFFERS], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK 4" in out.stdout


@pytest.mark.parametrize("stock_invariant", ["restock", "strict"])
def test_split_calls_bitexact(stock_invariant, engine, escrow_engine):
    """Two calls of one chunk each equal one call of both chunks: every
    table (and the escrow shares) bit-exact, and the two calls' counters
    summing to the single call's. Each call starts from fresh zero buffers
    and takes the previous call's state as it is."""
    strict = stock_invariant == "strict"
    eng = escrow_engine if strict else engine
    ex = FusedExecutor(eng, ring_rows=4)
    chunks = stack_chunks(*generate_mix_batches(
        eng, batch_per_shard=8, n_batches=8, remote_frac=0.3, seed=9), 4)

    def call(state, esc, cs):
        if strict:
            state, esc, cnt = ex.run_escrow(state, esc, cs)[:3]
        else:
            state, cnt, _ = ex.run(state, cs)
        return state, esc, jax.device_get(cnt)

    def initial():
        state = eng.shard_state(init_state(SCALE))
        return state, eng.init_escrow(state) if strict else None

    s1, e1, whole = call(*initial(), chunks)
    s2, e2, first = call(*initial(), chunks[:1])
    s2, e2, second = call(s2, e2, chunks[1:])
    assert ex.last_prepare.moved_leaves == 0

    assert _tree_equal(s1, s2) == []
    if strict:
        assert _tree_equal(e1, e2) == []
        assert whole.aborts.sum() > 0
    for f in MixCounters._fields:
        assert np.array_equal(getattr(whole, f),
                              getattr(first, f) + getattr(second, f)), f
    assert whole.neworders.sum() > 0 and whole.deliveries.sum() > 0
