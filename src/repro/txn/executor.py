"""Fused on-device megastep executor for the full TPC-C mix.

The paper's throughput claims (§6, 25x over serializable New-Order) are
about the *coordination-free hot path*; a closed loop that re-enters Python
between transactions measures host dispatch instead. This module removes the
host from the hot path entirely:

* **megastep** — pre-generated batches are stacked along a leading axis and
  ``merge_every`` iterations of the five-transaction mix (New-Order, Payment,
  RAMP Order-Status, RAMP Stock-Level, Delivery) run inside ONE jitted,
  donated :func:`jax.lax.scan`. Remote-stock outboxes are written into a
  fixed-size on-device ring buffer (one row per scan step) and every MixStats
  counter is accumulated in an on-device int32 pytree — zero host transfers
  and zero collectives inside the scan, asserted structurally from the
  compiled HLO (:meth:`FusedExecutor.prove_megastep_coordination_free`,
  mirroring ``Engine.prove_coordination_free``).

* **chunk cadence** — an outer *Python* loop advances one chunk
  (= ``merge_every`` scan steps) at a time. Between chunks a single batched
  anti-entropy call all-gathers the whole ring buffer and applies every
  queued remote stock update at once (one collective program per chunk,
  replacing the seed's one-jitted-call-per-outbox drain). This keeps the
  paper's separation intact and *provable*: the scan megastep compiles with
  no collective ops (Definition 5 on the hot path), while convergence
  (Definition 3) lives in the drain, off the critical path, at a cadence the
  host controls.

* **donation** — state, ring buffer, and counters are donated through both
  the megastep and the drain, so the executor reuses one set of device
  buffers for the entire run (no doubled live state; tests assert the input
  buffers are actually consumed and the compiled module carries
  ``input_output_alias``).

Why the drain order cannot change results: stock counters are commutative
scatter-adds over integer-valued quantities (exact in f32 well below 2**24),
and the decrement-then-restock rule keeps ``s_quantity`` inside the 91-wide
window [10, 100] — one representative per residue class mod 91 — so any
grouping of the same deltas converges to bit-identical state. This is what
makes the fused executor's chunked drain interchangeable with the per-batch
driver's sequential drain (tests/test_executor.py asserts bit-exactness).
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map

from repro.core.lattice import EscrowCounter
from repro.core.planner import CoordClass
from repro.obs import metrics as obsm
from repro.obs import trace
from repro.utils.hlo import assert_no_collectives, collective_stats

from . import ramp, tpcc
from .engine import (Engine, gather_and_apply_outbox,
                     gather_and_apply_outbox_strict,
                     gather_and_apply_outbox_strict_retry,
                     gather_and_refresh_hot_shares,
                     gather_and_refresh_shares)
from .tpcc import (NewOrderBatch, OrderStatusBatch, PaymentBatch,
                   StockLevelBatch, TPCCState)

Array = jax.Array


class OutboxRing(NamedTuple):
    """Fixed-size on-device ring of per-step remote-stock outboxes.

    Row ``i % rows`` holds scan step ``i``'s COO outbox (capacity R = B * L
    entries, ``valid``-masked). The ring is drained — and its valid bits
    cleared — by :meth:`FusedExecutor.drain` between chunks; the scan never
    runs longer than ``rows`` steps without a drain.
    """

    dst_w: Array  # [rows, R] int32 destination warehouse
    i_id: Array   # [rows, R] int32
    qty: Array    # [rows, R] int32
    valid: Array  # [rows, R] bool

    @property
    def rows(self) -> int:
        return self.valid.shape[0]


class MixCounters(NamedTuple):
    """On-device MixStats accumulators, one lane per shard ([n_shards] int32
    globally, [1] per shard inside the megastep). Transferred to the host
    exactly once, after the run's final ``block_until_ready``."""

    neworders: Array
    payments: Array
    order_statuses: Array
    stock_levels: Array
    deliveries: Array
    reads_found: Array
    fractures_observed: Array
    lines_repaired: Array
    aborts: Array   # escrow regime: insufficient-share atomic aborts


class MixChunk(NamedTuple):
    """``chunk_len`` pre-generated batches stacked along a leading axis.

    ``payment`` / ``order_status`` / ``stock_level`` may be None to run a
    reduced mix (e.g. the New-Order-only closed loop); being pytree
    structure, that choice is static per compile.
    """

    neworder: NewOrderBatch
    payment: PaymentBatch | None
    order_status: OrderStatusBatch | None
    stock_level: StockLevelBatch | None

    @property
    def chunk_len(self) -> int:
        return self.neworder.w.shape[0]


def stack_chunks(no_batches: Sequence[NewOrderBatch],
                 pay_batches: Sequence[PaymentBatch] | None,
                 os_batches: Sequence[OrderStatusBatch] | None,
                 sl_batches: Sequence[StockLevelBatch] | None,
                 merge_every: int) -> list[MixChunk]:
    """Group per-step batches into stacked MixChunks of <= merge_every steps."""
    stack = lambda parts: jax.tree.map(lambda *xs: jnp.stack(xs), *parts)
    chunks = []
    for lo in range(0, len(no_batches), merge_every):
        hi = min(lo + merge_every, len(no_batches))
        sl = slice(lo, hi)
        chunks.append(MixChunk(
            neworder=stack(no_batches[sl]),
            payment=stack(pay_batches[sl]) if pay_batches else None,
            order_status=stack(os_batches[sl]) if os_batches else None,
            stock_level=stack(sl_batches[sl]) if sl_batches else None))
    return chunks


@dataclasses.dataclass
class FusedExecutor:
    """Chunked-scan executor over an :class:`Engine`'s mesh and scale.

    ``ring_rows`` bounds the steps a chunk may take between drains (defaults
    to 8, the usual ``merge_every``); ``deliveries`` statically includes the
    per-step Delivery transaction. ``retry_cap`` > 0 (sparse escrow only)
    adds the bounded cold-retry ring to the drain programs: owner-rejected
    remote-cold entries re-present for up to ``retry_max`` drain windows
    (a runtime knob of :meth:`run_escrow`) before counting as final rejects;
    at 0 the non-retry programs are built unchanged (bit-exact default).
    """

    engine: Engine
    ring_rows: int = 8
    deliveries: bool = True
    retry_cap: int = 0

    def __post_init__(self):
        eng = self.engine
        scale = eng.scale
        ax = eng.axis_names
        state_spec = eng.state_spec
        shard1_spec = jax.sharding.PartitionSpec(None, ax)  # dim 1 = batch
        count_spec = eng.batch_spec
        # the engine's coordination plan selects the executor's hot path:
        # FREE -> restock New-Order + restocking drain; ESCROW -> strict
        # New-Order with the escrow counters joining the donated scan carry
        # (sparse HotSetEscrow or dense EscrowCounter per engine layout),
        # strict tiered drain, and the share refresh fused into the drain
        self._escrow = eng.stock_regime is CoordClass.ESCROW
        self._sparse = self._escrow and eng.escrow_layout == "sparse"
        esc_spec = eng.escrow_spec

        def step_tail(state, cnt, pay_b, os_b, sl_b, w_lo):
            """Payment + RAMP reads + Delivery — identical in both regimes.
            Deliberately metrics-free: the obs plane records once per chunk,
            after the scan, from the chunk inputs and the counter deltas."""
            if pay_b is not None:
                with trace.phase(trace.TXN_PAYMENT):
                    state = tpcc.apply_payment(state, pay_b, w_lo=w_lo)
                    cnt = cnt._replace(
                        payments=cnt.payments + pay_b.w.shape[0])
            with trace.phase(trace.TXN_READS):
                if os_b is not None:
                    os_res = ramp.apply_order_status(state, os_b, w_lo=w_lo)
                    cnt = cnt._replace(
                        order_statuses=cnt.order_statuses + os_b.w.shape[0],
                        reads_found=cnt.reads_found
                        + os_res.found.sum().astype(jnp.int32),
                        fractures_observed=cnt.fractures_observed
                        + os_res.fractures_observed().astype(jnp.int32),
                        lines_repaired=cnt.lines_repaired
                        + os_res.repaired.sum().astype(jnp.int32))
                if sl_b is not None:
                    sl_res = ramp.apply_stock_level(state, sl_b, scale,
                                                    w_lo=w_lo)
                    cnt = cnt._replace(
                        stock_levels=cnt.stock_levels + sl_b.w.shape[0],
                        fractures_observed=cnt.fractures_observed
                        + (sl_res.fractured - sl_res.repaired).sum()
                        .astype(jnp.int32),
                        lines_repaired=cnt.lines_repaired
                        + sl_res.repaired.sum().astype(jnp.int32))
            if self.deliveries:
                with trace.phase(trace.TXN_DELIVERY):
                    n_del = state.no_valid.any(axis=2).sum()
                    state = tpcc.apply_delivery(
                        state, jnp.asarray(1, jnp.int32),
                        jnp.asarray(0, jnp.int32))
                    cnt = cnt._replace(
                        deliveries=cnt.deliveries + n_del.astype(jnp.int32))
            return state, cnt

        def _mega_body(state, ring, counters, chunk):
            """Merge-regime chunk scan. Identical with metrics on or off —
            every metric the obs plane wants is recoverable from the chunk
            inputs and the counter totals, recorded off this program by the
            executor's ``_record`` / ``_fold_counters`` dispatches."""
            idx = eng._shard_index()
            w_lo = idx * eng.w_per_shard
            rows = ring.valid.shape[0]
            T = chunk.neworder.w.shape[0]

            def step(carry, xs):
                state, ring, cnt = carry
                no_b, pay_b, os_b, sl_b, i = xs
                B = no_b.w.shape[0]
                with trace.phase(trace.TXN_NEWORDER):
                    state, delta, _ = tpcc.apply_neworder(
                        state, no_b, scale, w_lo=w_lo,
                        w_hi=w_lo + eng.w_per_shard,
                        replica=idx, num_replicas=eng.n_shards)
                    ring = OutboxRing(*(
                        jax.lax.dynamic_update_index_in_dim(r, v, i % rows, 0)
                        for r, v in zip(ring, delta)))
                    cnt = cnt._replace(neworders=cnt.neworders + B)
                state, cnt = step_tail(state, cnt, pay_b, os_b, sl_b, w_lo)
                return (state, ring, cnt), None

            xs = (chunk.neworder, chunk.payment, chunk.order_status,
                  chunk.stock_level, jnp.arange(T))
            (state, ring, counters), _ = jax.lax.scan(
                step, (state, ring, counters), xs)
            return state, ring, counters

        def _mega_escrow_body(state, ring, counters, esc, chunk, want_ok):
            """Escrow-regime chunk scan (strict New-Order; shared by the
            metrics-on/off wrappers). ``want_ok`` (static) is the ONLY
            metrics-on difference: the scan stacks each step's commit mask
            ``ok`` as ys — one per-step output write — because the
            committed-weighted latency histogram needs per-txn admission,
            which counter totals can't reconstruct. All recording happens
            off this program."""
            idx = eng._shard_index()
            w_lo = idx * eng.w_per_shard
            rows = ring.valid.shape[0]
            T = chunk.neworder.w.shape[0]

            def step(carry, xs):
                state, ring, cnt, esc = carry
                no_b, pay_b, os_b, sl_b, i = xs
                B = no_b.w.shape[0]
                with trace.phase(trace.TXN_NEWORDER):
                    if self._sparse:
                        state, spent, delta, _, ok = \
                            tpcc.apply_neworder_escrow_sparse(
                                state, esc.keys, esc.shares[0],
                                esc.spent[0], no_b, scale, w_lo=w_lo,
                                w_hi=w_lo + eng.w_per_shard,
                                replica=idx, num_replicas=eng.n_shards,
                                admission=eng.admission,
                                effects=eng.effects)
                    else:
                        state, spent, delta, _, ok = \
                            tpcc.apply_neworder_escrow(
                                state, esc.shares[0], esc.spent[0], no_b,
                                scale, w_lo=w_lo,
                                w_hi=w_lo + eng.w_per_shard,
                                replica=idx, num_replicas=eng.n_shards,
                                admission=eng.admission,
                                effects=eng.effects)
                    esc = esc._replace(spent=spent[None])
                    ring = OutboxRing(*(
                        jax.lax.dynamic_update_index_in_dim(r, v, i % rows, 0)
                        for r, v in zip(ring, delta)))
                    n_ok = ok.sum().astype(jnp.int32)
                    cnt = cnt._replace(neworders=cnt.neworders + n_ok,
                                       aborts=cnt.aborts + (B - n_ok))
                state, cnt = step_tail(state, cnt, pay_b, os_b, sl_b, w_lo)
                return (state, ring, cnt, esc), (ok if want_ok else None)

            xs = (chunk.neworder, chunk.payment, chunk.order_status,
                  chunk.stock_level, jnp.arange(T))
            (state, ring, counters, esc), ok_ys = jax.lax.scan(
                step, (state, ring, counters, esc), xs)
            return state, ring, counters, esc, ok_ys

        obs_spec = obsm.obs_partition_specs(ax)

        @functools.partial(
            shard_map, mesh=eng.mesh,
            in_specs=(state_spec, shard1_spec, count_spec, shard1_spec),
            out_specs=(state_spec, shard1_spec, count_spec),
            check_vma=False)
        def _megastep(state: TPCCState, ring: OutboxRing,
                      counters: MixCounters, chunk: MixChunk):
            return _mega_body(state, ring, counters, chunk)

        @functools.partial(
            shard_map, mesh=eng.mesh,
            in_specs=(state_spec, shard1_spec, count_spec, esc_spec,
                      shard1_spec),
            out_specs=(state_spec, shard1_spec, count_spec, esc_spec),
            check_vma=False)
        def _megastep_escrow(state: TPCCState, ring: OutboxRing,
                             counters: MixCounters, esc,
                             chunk: MixChunk):
            return _mega_escrow_body(state, ring, counters, esc, chunk,
                                     want_ok=False)[:4]

        @functools.partial(
            shard_map, mesh=eng.mesh,
            in_specs=(state_spec, shard1_spec, count_spec, esc_spec,
                      shard1_spec),
            out_specs=(state_spec, shard1_spec, count_spec, esc_spec,
                       shard1_spec),
            check_vma=False)
        def _megastep_escrow_obs(state: TPCCState, ring: OutboxRing,
                                 counters: MixCounters, esc,
                                 chunk: MixChunk):
            # metrics-on escrow megastep: + the stacked [T, B] commit mask
            return _mega_escrow_body(state, ring, counters, esc, chunk,
                                     want_ok=True)

        # the obs plane's record programs — dispatched off the hot megastep,
        # once per chunk (record) and once per run (fold); both shard_mapped
        # over the same lanes as the megastep, both provably collective-free
        @functools.partial(
            shard_map, mesh=eng.mesh,
            in_specs=(obs_spec, shard1_spec),
            out_specs=obs_spec, check_vma=False)
        def _record_merge(obs, neworder: NewOrderBatch):
            return obsm.record_chunk(obs, neworder, None)

        @functools.partial(
            shard_map, mesh=eng.mesh,
            in_specs=(obs_spec, shard1_spec, shard1_spec),
            out_specs=obs_spec, check_vma=False)
        def _record_escrow(obs, neworder: NewOrderBatch, ok):
            return obsm.record_chunk(obs, neworder, ok)

        @functools.partial(
            shard_map, mesh=eng.mesh,
            in_specs=(obs_spec, count_spec),
            out_specs=obs_spec, check_vma=False)
        def _fold(obs, counters: MixCounters):
            return obsm.fold_counters(
                obs, counters.payments, counters.order_statuses,
                counters.stock_levels, counters.deliveries, counters.aborts)

        @functools.partial(
            shard_map, mesh=eng.mesh,
            in_specs=(state_spec, shard1_spec),
            out_specs=(state_spec, shard1_spec),
            check_vma=False)
        def _drain(state: TPCCState, ring: OutboxRing):
            # one batched anti-entropy round: gather every shard's whole ring
            # (all queued outboxes at once) and apply the entries we own —
            # the same body Engine.anti_entropy runs per outbox
            w_lo = eng._shard_index() * eng.w_per_shard
            with trace.phase(trace.DRAIN_APPLY):
                state = gather_and_apply_outbox(state, ring, ax, w_lo,
                                                eng.w_per_shard, restock=True)
            return state, ring._replace(valid=jnp.zeros_like(ring.valid))

        def _strict_drain_body(state, ring, hot_keys, w_lo):
            # the escrow regime's strict ring drain — hot entries apply
            # unconditionally (share-admitted), cold entries under the
            # owner's per-cell all-or-nothing admission (sparse layout);
            # dense has no cold tier, so rejects are structurally zero
            with trace.phase(trace.DRAIN_APPLY):
                if self._sparse:
                    return gather_and_apply_outbox_strict(
                        state, ring, hot_keys, ax, w_lo, eng.w_per_shard,
                        scale.n_items)
                state = gather_and_apply_outbox(state, ring, ax, w_lo,
                                                eng.w_per_shard,
                                                restock=False)
                return state, jnp.zeros((1,), jnp.int32)

        @functools.partial(
            shard_map, mesh=eng.mesh,
            in_specs=(state_spec, shard1_spec),
            out_specs=(state_spec, shard1_spec, count_spec),
            check_vma=False)
        def _drain_strict(state: TPCCState, ring: OutboxRing):
            w_lo = eng._shard_index() * eng.w_per_shard
            state, rej = _strict_drain_body(
                state, ring, getattr(eng, "hot_keys", None), w_lo)
            return state, ring._replace(
                valid=jnp.zeros_like(ring.valid)), rej

        @functools.partial(
            shard_map, mesh=eng.mesh,
            in_specs=(state_spec, shard1_spec, esc_spec,
                      jax.sharding.PartitionSpec()),
            out_specs=(state_spec, shard1_spec, esc_spec, count_spec),
            check_vma=False)
        def _drain_refresh(state: TPCCState, ring: OutboxRing, esc, alive):
            # the escrow regime's amortized coordination point, fused into
            # the chunk drain: apply every queued (strict) stock update, then
            # re-partition the owners' post-drain stock into fresh shares —
            # one collective program per refresh. ``alive`` ([n_shards],
            # replicated) reclaims dead replicas' headroom at this boundary.
            idx = eng._shard_index()
            w_lo = idx * eng.w_per_shard
            hot_keys = esc.keys if self._sparse else None
            state, rej = _strict_drain_body(state, ring, hot_keys, w_lo)
            with trace.phase(trace.DRAIN_REFRESH):
                if self._sparse:
                    esc = gather_and_refresh_hot_shares(
                        state, esc.keys, ax, idx, eng.n_shards,
                        scale.n_items, w_lo, eng.w_per_shard, alive=alive)
                else:
                    esc = gather_and_refresh_shares(
                        state, ax, idx, eng.n_shards, alive=alive)
            return state, ring._replace(
                valid=jnp.zeros_like(ring.valid)), esc, rej

        retry_spec = tpcc.RetryState(
            *([jax.sharding.PartitionSpec(ax)] * 6))

        @functools.partial(
            shard_map, mesh=eng.mesh,
            in_specs=(state_spec, shard1_spec, retry_spec,
                      jax.sharding.PartitionSpec(),
                      jax.sharding.PartitionSpec()),
            out_specs=(state_spec, shard1_spec, retry_spec, count_spec),
            check_vma=False)
        def _drain_strict_retry(state: TPCCState, ring: OutboxRing, retry,
                                retry_max, reserve):
            # strict ring drain + bounded retry: the owner's rejected cold
            # entries re-present first, fresh rejects requeue up to
            # retry_max windows; reserve > 0 grants last-chance losers an
            # owner reservation (sparse-only; built when retry_cap > 0)
            w_lo = eng._shard_index() * eng.w_per_shard
            with trace.phase(trace.DRAIN_APPLY):
                state, retry, rej = gather_and_apply_outbox_strict_retry(
                    state, ring, retry, eng.hot_keys, ax, w_lo,
                    eng.w_per_shard, scale.n_items, retry_max, reserve)
            return state, ring._replace(
                valid=jnp.zeros_like(ring.valid)), retry, rej

        @functools.partial(
            shard_map, mesh=eng.mesh,
            in_specs=(state_spec, shard1_spec, retry_spec, esc_spec,
                      jax.sharding.PartitionSpec(),
                      jax.sharding.PartitionSpec(),
                      jax.sharding.PartitionSpec()),
            out_specs=(state_spec, shard1_spec, retry_spec, esc_spec,
                       count_spec),
            check_vma=False)
        def _drain_refresh_retry(state: TPCCState, ring: OutboxRing, retry,
                                 esc, alive, retry_max, reserve):
            # fused retry drain + reclaiming share refresh — still one
            # collective program per refresh boundary
            idx = eng._shard_index()
            w_lo = idx * eng.w_per_shard
            with trace.phase(trace.DRAIN_APPLY):
                state, retry, rej = gather_and_apply_outbox_strict_retry(
                    state, ring, retry, eng.hot_keys, ax, w_lo,
                    eng.w_per_shard, scale.n_items, retry_max, reserve)
            with trace.phase(trace.DRAIN_REFRESH):
                esc = gather_and_refresh_hot_shares(
                    state, esc.keys, ax, idx, eng.n_shards, scale.n_items,
                    w_lo, eng.w_per_shard, alive=alive)
            return state, ring._replace(
                valid=jnp.zeros_like(ring.valid)), retry, esc, rej

        # donation: the executor owns ONE live copy of state/ring/counters
        # for the whole run — every call consumes its buffers and hands the
        # same allocation back (input_output_alias in the compiled module)
        self._megastep = jax.jit(_megastep, donate_argnums=(0, 1, 2))
        self._megastep_esc = jax.jit(_megastep_escrow,
                                     donate_argnums=(0, 1, 2, 3))
        self._megastep_esc_obs = jax.jit(_megastep_escrow_obs,
                                         donate_argnums=(0, 1, 2, 3))
        self._record = jax.jit(_record_merge, donate_argnums=0)
        self._record_esc = jax.jit(_record_escrow, donate_argnums=0)
        self._fold_counters = jax.jit(_fold, donate_argnums=0)
        self._drain = jax.jit(_drain, donate_argnums=(0, 1))
        self._drain_strict = jax.jit(_drain_strict, donate_argnums=(0, 1))
        self._drain_refresh = jax.jit(_drain_refresh,
                                      donate_argnums=(0, 1, 2))
        if self.retry_cap > 0:
            if not self._sparse:
                raise ValueError("retry_cap > 0 requires the sparse "
                                 "(two-tier) escrow layout — the retry ring "
                                 "holds cold-tier entries")
            self._drain_strict_retry = jax.jit(_drain_strict_retry,
                                               donate_argnums=(0, 1, 2))
            self._drain_refresh_retry = jax.jit(_drain_refresh_retry,
                                                donate_argnums=(0, 1, 2, 3))

    # -- device buffers ------------------------------------------------------

    def init_ring(self, batch_per_shard: int) -> OutboxRing:
        # committed to the run sharding up front: the jit cache keys on input
        # shardings, so uncommitted first-call buffers would force a second
        # compile once the megastep's (committed) outputs loop back in
        sh = jax.sharding.NamedSharding(
            self.engine.mesh, jax.sharding.PartitionSpec(
                None, self.engine.axis_names))
        R = batch_per_shard * self.engine.n_shards * self.engine.scale.max_lines
        z = lambda dt: jax.device_put(jnp.zeros((self.ring_rows, R), dt), sh)
        return OutboxRing(z(jnp.int32), z(jnp.int32), z(jnp.int32),
                          z(jnp.bool_))

    def init_counters(self) -> MixCounters:
        sh = jax.sharding.NamedSharding(
            self.engine.mesh, jax.sharding.PartitionSpec(
                self.engine.axis_names))
        # distinct buffers per field: donation must not alias two arguments
        return MixCounters(*(
            jax.device_put(jnp.zeros((self.engine.n_shards,), jnp.int32), sh)
            for _ in MixCounters._fields))

    # -- execution -----------------------------------------------------------

    def megastep(self, state: TPCCState, ring: OutboxRing,
                 counters: MixCounters, chunk: MixChunk):
        """Run one chunk (<= ring_rows mix iterations) fully on device."""
        if chunk.chunk_len > self.ring_rows:
            raise ValueError(f"chunk of {chunk.chunk_len} steps exceeds the "
                             f"{self.ring_rows}-row outbox ring")
        if self._escrow:
            raise RuntimeError("escrow-regime executor: use megastep_escrow")
        return self._megastep(state, ring, counters, chunk)

    def megastep_escrow(self, state: TPCCState, ring: OutboxRing,
                        counters: MixCounters, esc, chunk: MixChunk):
        """Escrow-regime chunk: the EscrowCounter joins the donated carry."""
        if chunk.chunk_len > self.ring_rows:
            raise ValueError(f"chunk of {chunk.chunk_len} steps exceeds the "
                             f"{self.ring_rows}-row outbox ring")
        return self._megastep_esc(state, ring, counters, esc, chunk)

    def drain(self, state: TPCCState, ring: OutboxRing):
        """Batched anti-entropy over the whole ring; clears its valid bits
        (merge regime: restocking apply)."""
        return self._drain(state, ring)

    def drain_strict(self, state: TPCCState, ring: OutboxRing):
        """Strict-regime ring drain (hot unconditional, cold all-or-nothing
        at the owner). Returns (state, ring, per-shard cold rejects)."""
        return self._drain_strict(state, ring)

    def _alive(self, alive) -> Array:
        """The refresh's ``[n_shards]`` liveness mask (default all-live)."""
        return jnp.asarray(self.engine._alive_all if alive is None else alive,
                           jnp.int32)

    def drain_refresh(self, state: TPCCState, ring: OutboxRing, esc,
                      alive=None):
        """Strict drain + escrow share refresh fused into one collective
        program. Returns (state, ring, esc, per-shard cold rejects).
        ``alive`` ([n_shards] mask, default all-live) reclaims dead
        replicas' share headroom for the survivors."""
        return self._drain_refresh(state, ring, esc, self._alive(alive))

    def init_retry(self):
        """Per-owner retry ring buffers ([n_shards, retry_cap])."""
        if self.retry_cap <= 0:
            raise RuntimeError("executor built with retry_cap=0")
        return self.engine.init_retry(self.retry_cap)

    def drain_strict_retry(self, state: TPCCState, ring: OutboxRing,
                           retry, retry_max=0, reserve=0):
        """Retry-aware strict ring drain. Returns (state, ring, retry',
        per-shard FINAL-reject counts) — entries still in the ring are
        pending, not rejected. ``reserve`` > 0 (traced) enables the
        owner-granted reservation round-trip for last-chance losers."""
        return self._drain_strict_retry(state, ring, retry,
                                        jnp.asarray(retry_max, jnp.int32),
                                        jnp.asarray(reserve, jnp.int32))

    def drain_refresh_retry(self, state: TPCCState, ring: OutboxRing,
                            retry, esc, alive=None, retry_max=0, reserve=0):
        """Retry-aware drain + reclaiming share refresh (one collective
        program). Returns (state, ring, retry', esc, per-shard final
        rejects)."""
        return self._drain_refresh_retry(state, ring, retry, esc,
                                         self._alive(alive),
                                         jnp.asarray(retry_max, jnp.int32),
                                         jnp.asarray(reserve, jnp.int32))

    @trace.spanned(trace.EXEC_CALL)
    def run(self, state: TPCCState, chunks: Sequence[MixChunk],
            *, warmup: bool = True, obs=None
            ) -> tuple[TPCCState, MixCounters, float]:
        """Drive all chunks: scan megastep + one drain per chunk, a single
        final host sync. Returns (state, counters, wall_seconds); wall time
        excludes compilation (triggered on throwaway copies) and batch prep.

        ``obs`` (an ``repro.obs.ObsSession``) keeps the on-device metrics
        lattice fed beside the run (when the session wants metrics). The
        hot megastep is the SAME compiled program with metrics on or off,
        and the timed loop makes zero extra dispatches: because lattice
        joins are commutative and associative, the per-chunk ``_record``
        folds run after the wall clock stops (bit-identical to inline
        recording), followed by one ``_fold_counters``, landing in
        ``obs.device_metrics`` — zero host transfers, zero collectives.
        Every phase of the call is a host span of ``repro.obs.trace``.
        """
        if self._escrow:
            raise RuntimeError("escrow-regime executor: use run_escrow")
        batch_per_shard = chunks[0].neworder.w.shape[1] // self.engine.n_shards
        with trace.span(trace.EXEC_PREPARE):
            state = self.engine.shard_state(state)  # commit: stable jit key
            ring = self.init_ring(batch_per_shard)
            counters = self.init_counters()
            metrics = obs.init_metrics(self.engine) if obs is not None and \
                obs.wants_metrics else None
        if warmup:
            copy = lambda t: jax.tree.map(lambda x: x.copy(), t)
            for T in sorted({c.chunk_len for c in chunks}):
                chunk = next(c for c in chunks if c.chunk_len == T)
                trace.register(self._megastep, state, ring, counters, chunk)
                w = self.megastep(copy(state), copy(ring), copy(counters),
                                  chunk)
                trace.register(self._drain, w[0], w[1])
                jax.block_until_ready(self.drain(w[0], w[1]))
                if metrics is not None:
                    jax.block_until_ready(
                        self._record(copy(metrics), chunk.neworder))
            if metrics is not None:
                jax.block_until_ready(
                    self._fold_counters(copy(metrics), counters))

        t0 = time.perf_counter()
        for chunk in chunks:
            with trace.span(trace.EXEC_MEGASTEP):
                state, ring, counters = self.megastep(state, ring,
                                                      counters, chunk)
            with trace.span(trace.EXEC_DRAIN):
                state, ring = self.drain(state, ring)
        with trace.span(trace.EXEC_SYNC):
            jax.block_until_ready((state, counters))
        wall = time.perf_counter() - t0
        if metrics is not None:
            # deferred lattice folds: every record is a commutative join of
            # per-chunk inputs, so folding after the timed loop is
            # bit-identical to folding inline — and the hot loop pays zero
            # extra dispatches (dispatch wall time is the one real cost of
            # an extra per-chunk program on this backend)
            with trace.span(trace.EXEC_OBS_FOLD):
                for chunk in chunks:
                    metrics = self._record(metrics, chunk.neworder)
                obs.device_metrics = self._fold_counters(metrics, counters)
        return state, counters, wall

    @trace.spanned(trace.EXEC_CALL)
    def run_escrow(self, state: TPCCState, esc, chunks: Sequence[MixChunk],
                   *, refresh_every: int = 1,
                   refresh_abort_rate: float | None = None,
                   warmup: bool = True, obs=None,
                   retry=None, retry_max: int = 0, alive=None,
                   reserve: int = 0, liveness=None,
                   final_flush: bool = True
                   ) -> tuple[TPCCState, object, MixCounters,
                              float, int, int, object]:
        """Escrow-regime drive: scan megastep + one strict drain per chunk;
        the escrow shares refresh every ``refresh_every``-th drain (fused
        into the same collective program), or adaptively when any replica's
        abort rate since the last refresh crosses ``refresh_abort_rate`` —
        adaptive control reads the on-device abort counters once per chunk
        (the one host sync the fixed cadence does not pay).

        With ``retry_cap`` > 0 the drains run their retry-aware variants:
        ``retry`` (default fresh ring) carries owner-rejected cold entries
        across windows for up to ``retry_max`` presentations, and
        ``cold_rejects`` counts FINAL rejects only; ``final_flush`` adds the
        run-end pending ring entries to that count (set False when the ring
        is checkpointed and the run will resume). ``alive`` ([n_shards]
        mask) threads share reclamation into each refresh; ``liveness`` (a
        ``runtime.liveness.LeaseMonitor``) DERIVES that mask instead — the
        monitor ticks once per chunk (one drain window) and its
        lease-expiry view feeds every refresh, so no caller-provided mask
        is needed. ``reserve`` > 0 (traced — same compiled drain) enables
        the cold-line reservation round-trip. Every phase of the call is a
        host span of ``repro.obs.trace``. Returns (state, esc, counters,
        wall_seconds, refreshes, cold_rejects, retry)."""
        if not self._escrow:
            raise RuntimeError("executor is not in the escrow regime "
                               "(engine plan says merge) — use run()")
        use_retry = self.retry_cap > 0
        batch_per_shard = chunks[0].neworder.w.shape[1] // self.engine.n_shards
        with trace.span(trace.EXEC_PREPARE):
            if use_retry and retry is None:
                retry = self.init_retry()
            state = self.engine.shard_state(state)
            ring = self.init_ring(batch_per_shard)
            counters = self.init_counters()
            metrics = obs.init_metrics(self.engine) if obs is not None and \
                obs.wants_metrics else None
        if warmup:
            self._warm_escrow(state, ring, counters, esc, chunks, metrics,
                              retry, retry_max, alive, reserve)

        adaptive = refresh_abort_rate is not None
        aborts_at_refresh = np.zeros(self.engine.n_shards, np.int64)
        txns_at_refresh = 0
        txns_so_far = 0
        refreshes = 0
        rejs = []
        oks = []
        t0 = time.perf_counter()
        for ci, chunk in enumerate(chunks):
            with trace.span(trace.EXEC_MEGASTEP):
                if metrics is not None:
                    # the commit masks are already megastep outputs —
                    # keeping the handles costs the loop nothing, and the
                    # lattice folds they feed commute, so recording is
                    # deferred past the timed region
                    state, ring, counters, esc, ok = \
                        self._megastep_esc_obs(state, ring, counters, esc,
                                               chunk)
                    oks.append(ok)
                else:
                    state, ring, counters, esc = self.megastep_escrow(
                        state, ring, counters, esc, chunk)
            if adaptive:
                from .drivers import _adaptive_refresh_due
                # per-replica abort rate since the last refresh — one small
                # counter transfer per chunk
                with trace.span(trace.EXEC_READBACK):
                    ab = np.asarray(jax.device_get(counters.aborts), np.int64)
                txns_so_far += chunk.chunk_len * batch_per_shard
                due = _adaptive_refresh_due(ab - aborts_at_refresh,
                                            txns_so_far - txns_at_refresh,
                                            refresh_abort_rate)
                if due:
                    aborts_at_refresh = ab
                    txns_at_refresh = txns_so_far
            else:
                due = (ci + 1) % refresh_every == 0
            if liveness is not None:
                # the liveness monitor ticks once per drain window: its
                # stamp source joins the fleet's heartbeat high-water marks
                # (riding the drain — no extra collective) and the derived
                # lease-expiry mask feeds the next share refresh
                alive = liveness.tick().astype(np.int32)
            if due:
                with trace.span(trace.EXEC_REFRESH):
                    if use_retry:
                        state, ring, retry, esc, rej = \
                            self.drain_refresh_retry(state, ring, retry,
                                                     esc, alive, retry_max,
                                                     reserve)
                    else:
                        state, ring, esc, rej = self.drain_refresh(
                            state, ring, esc, alive)
                refreshes += 1
            else:
                with trace.span(trace.EXEC_DRAIN):
                    if use_retry:
                        state, ring, retry, rej = self.drain_strict_retry(
                            state, ring, retry, retry_max, reserve)
                    else:
                        state, ring, rej = self.drain_strict(state, ring)
            rejs.append(rej)
        with trace.span(trace.EXEC_SYNC):
            jax.block_until_ready((state, esc, counters))
        wall = time.perf_counter() - t0
        if metrics is not None:
            # deferred lattice folds (joins commute — bit-identical to
            # inline recording, zero dispatches inside the timed loop)
            with trace.span(trace.EXEC_OBS_FOLD):
                for chunk, ok in zip(chunks, oks):
                    metrics = self._record_esc(metrics, chunk.neworder, ok)
                for rej in rejs:
                    metrics = obsm.add_cold_rejects(metrics, rej)
                obs.device_metrics = self._fold_counters(metrics, counters)
        with trace.span(trace.EXEC_READBACK):
            cold = int(np.asarray(jax.device_get(rejs)).sum()) if rejs else 0
            if use_retry and final_flush:
                # entries still pending in the ring when the run ends never
                # got their retry_max-th window — surface them as final
                # rejects so optimistic admits == applied + cold_rejects
                # holds exactly
                cold += int(np.asarray(jax.device_get(retry.valid)).sum())
        return state, esc, counters, wall, refreshes, cold, retry

    def _warm_escrow(self, state, ring, counters, esc, chunks, metrics,
                     retry, retry_max, alive, reserve):
        """Compile every program the escrow loop dispatches, on copies, and
        register each with ``repro.obs.trace``."""
        copy = lambda t: jax.tree.map(lambda x: x.copy(), t)
        i32 = lambda v: jnp.asarray(v, jnp.int32)
        for T in sorted({c.chunk_len for c in chunks}):
            chunk = next(c for c in chunks if c.chunk_len == T)
            if metrics is not None:
                trace.register(self._megastep_esc_obs, state, ring, counters,
                               esc, chunk)
                w = self._megastep_esc_obs(
                    copy(state), copy(ring), copy(counters), copy(esc),
                    chunk)
                jax.block_until_ready(
                    self._record_esc(copy(metrics), chunk.neworder, w[4]))
                w = w[:4]
            else:
                trace.register(self._megastep_esc, state, ring, counters,
                               esc, chunk)
                w = self.megastep_escrow(copy(state), copy(ring),
                                         copy(counters), copy(esc), chunk)
            if self.retry_cap > 0:
                trace.register(self._drain_refresh_retry, w[0], w[1], retry,
                               w[3], self._alive(alive), i32(retry_max),
                               i32(reserve))
                w2 = self.drain_refresh_retry(w[0], w[1], copy(retry), w[3],
                                              alive, retry_max, reserve)
                trace.register(self._drain_strict_retry, w2[0], w2[1], w2[2],
                               i32(retry_max), i32(reserve))
                jax.block_until_ready(self.drain_strict_retry(
                    w2[0], w2[1], w2[2], retry_max, reserve))
            else:
                trace.register(self._drain_refresh, w[0], w[1], w[3],
                               self._alive(alive))
                w2 = self.drain_refresh(w[0], w[1], w[3], alive)
                trace.register(self._drain_strict, w2[0], w2[1])
                jax.block_until_ready(self.drain_strict(w2[0], w2[1]))
        if metrics is not None:
            jax.block_until_ready(
                self._fold_counters(copy(metrics), counters))

    # -- structural proofs ---------------------------------------------------

    def _ring_specs(self, batch_per_shard: int) -> OutboxRing:
        R = batch_per_shard * self.engine.n_shards * self.engine.scale.max_lines
        f = jax.ShapeDtypeStruct
        return OutboxRing(f((self.ring_rows, R), jnp.int32),
                          f((self.ring_rows, R), jnp.int32),
                          f((self.ring_rows, R), jnp.int32),
                          f((self.ring_rows, R), jnp.bool_))

    def _counter_specs(self) -> MixCounters:
        f = jax.ShapeDtypeStruct((self.engine.n_shards,), jnp.int32)
        return MixCounters(*(f for _ in MixCounters._fields))

    def _arg_specs(self, chunk_len: int, batch_per_shard: int,
                   read_per_shard: int, payments: bool, reads: bool):
        eng = self.engine
        stack = lambda t: jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((chunk_len,) + s.shape, s.dtype), t)
        B = batch_per_shard * eng.n_shards
        R = read_per_shard * eng.n_shards
        f = jax.ShapeDtypeStruct
        chunk = MixChunk(
            neworder=stack(tpcc.neworder_input_specs(eng.scale, B)),
            payment=stack(PaymentBatch(f((B,), jnp.int32), f((B,), jnp.int32),
                                       f((B,), jnp.int32), f((B,), jnp.float32)))
            if payments else None,
            order_status=stack(tpcc.order_status_input_specs(R))
            if reads else None,
            stock_level=stack(tpcc.stock_level_input_specs(R))
            if reads else None)
        return (tpcc.state_shape_dtypes(eng.scale),
                self._ring_specs(batch_per_shard), self._counter_specs(),
                chunk)

    def lowered_megastep(self, chunk_len: int = 8, batch_per_shard: int = 8,
                         read_per_shard: int = 2, payments: bool = True,
                         reads: bool = True, metrics: bool = False):
        """Lower the PLAN-SELECTED megastep (escrow variant includes the
        EscrowCounter carry). ``metrics=True`` lowers the program the
        metrics-on loop actually runs: in the merge regime that is the SAME
        megastep (the obs plane records off the hot program entirely); in
        the escrow regime it additionally emits the stacked commit mask."""
        state_sds, ring_sds, cnt_sds, chunk = self._arg_specs(
            chunk_len, batch_per_shard, read_per_shard, payments, reads)
        if self._escrow:
            fn = self._megastep_esc_obs if metrics else self._megastep_esc
            return fn.lower(state_sds, ring_sds, cnt_sds,
                            self.engine.escrow_input_specs(), chunk)
        return self._megastep.lower(state_sds, ring_sds, cnt_sds, chunk)

    def lowered_record(self, chunk_len: int = 8, batch_per_shard: int = 8):
        """Lower the obs plane's per-chunk record program (folded once per
        executed chunk, after the timed loop)."""
        B = batch_per_shard * self.engine.n_shards
        stack = lambda t: jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((chunk_len,) + s.shape, s.dtype), t)
        no_sds = stack(tpcc.neworder_input_specs(self.engine.scale, B))
        obs_sds = obsm.obs_metrics_specs(self.engine)
        if self._escrow:
            ok_sds = jax.ShapeDtypeStruct((chunk_len, B), jnp.bool_)
            return self._record_esc.lower(obs_sds, no_sds, ok_sds)
        return self._record.lower(obs_sds, no_sds)

    def lowered_fold_counters(self):
        """Lower the obs plane's once-per-run counter fold."""
        return self._fold_counters.lower(
            obsm.obs_metrics_specs(self.engine), self._counter_specs())

    def prove_megastep_coordination_free(self, chunk_len: int = 8,
                                         batch_per_shard: int = 8,
                                         read_per_shard: int = 2,
                                         metrics: bool = False) -> str:
        """Definition 5 on the fused hot path: merge_every full-mix
        iterations compile to ZERO collective ops. In the escrow regime this
        covers the strict New-Order admission (``try_spend`` against the
        device-resident shares) — everything between refreshes is
        collective-free. ``metrics=True`` proves the same for everything a
        metrics-on run executes per chunk: the (identical or commit-mask-
        emitting) megastep AND the obs plane's record + counter-fold
        programs — the observability plane adds no coordination."""
        ctx = "fused TPC-C escrow megastep" if self._escrow \
            else "fused TPC-C megastep"
        if metrics:
            ctx += " (metrics-on)"
        text = self.lowered_megastep(chunk_len, batch_per_shard,
                                     read_per_shard,
                                     metrics=metrics).compile().as_text()
        assert_no_collectives(text, context=ctx)
        if metrics:
            assert_no_collectives(
                self.lowered_record(chunk_len,
                                    batch_per_shard).compile().as_text(),
                context=ctx + " record program")
            assert_no_collectives(
                self.lowered_fold_counters().compile().as_text(),
                context=ctx + " counter-fold program")
        return collective_stats(text).describe()

    def count_drain_collectives(self, batch_per_shard: int = 8):
        text = self._drain.lower(
            tpcc.state_shape_dtypes(self.engine.scale),
            self._ring_specs(batch_per_shard)).compile().as_text()
        return collective_stats(text)

    def count_drain_strict_collectives(self, batch_per_shard: int = 8):
        """The escrow regime's non-refresh ring drain (coordination ledger
        input: its traffic is the cold tier's owner routing)."""
        text = self._drain_strict.lower(
            tpcc.state_shape_dtypes(self.engine.scale),
            self._ring_specs(batch_per_shard)).compile().as_text()
        return collective_stats(text)

    def count_drain_refresh_collectives(self, batch_per_shard: int = 8):
        """The escrow regime's fused drain+refresh — its only collectives."""
        text = self._drain_refresh.lower(
            tpcc.state_shape_dtypes(self.engine.scale),
            self._ring_specs(batch_per_shard),
            self.engine.escrow_input_specs(),
            jax.ShapeDtypeStruct((self.engine.n_shards,), jnp.int32)
        ).compile().as_text()
        return collective_stats(text)

    def count_drain_strict_retry_collectives(self, batch_per_shard: int = 8):
        """The retry-aware ring drain: same collective budget as the
        non-retry drain (the retry ring is owner-local, never gathered)."""
        text = self._drain_strict_retry.lower(
            tpcc.state_shape_dtypes(self.engine.scale),
            self._ring_specs(batch_per_shard),
            self.engine.retry_input_specs(self.retry_cap),
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32)).compile().as_text()
        return collective_stats(text)


def get_fused_executor(engine: Engine, ring_rows: int = 8,
                       deliveries: bool = True,
                       retry_cap: int = 0) -> FusedExecutor:
    """Memoized per-engine executor: repeated runs (benchmark sweeps, the
    closed-loop drivers) reuse one jit cache instead of recompiling."""
    cache = getattr(engine, "_fused_executors", None)
    if cache is None:
        cache = engine._fused_executors = {}
    key = (ring_rows, deliveries, retry_cap)
    if key not in cache:
        cache[key] = FusedExecutor(engine, ring_rows=ring_rows,
                                   deliveries=deliveries,
                                   retry_cap=retry_cap)
    return cache[key]


# ---------------------------------------------------------------------------
# The closed-loop drivers (run_fused_loop / run_fused_escrow_loop /
# counters_to_stats) moved into txn/drivers.py — the one consolidated
# pending-outbox/stats/audit core. Lazy re-export keeps old imports working
# without an import cycle.
# ---------------------------------------------------------------------------

_DRIVER_EXPORTS = ("counters_to_stats", "run_fused_loop",
                   "run_fused_escrow_loop", "MixStats")


def __getattr__(name):
    if name in _DRIVER_EXPORTS:
        from . import drivers
        return getattr(drivers, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
