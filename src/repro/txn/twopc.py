"""Coordinated (serializable-style) baseline: per-batch synchronous 2PC.

The paper's comparison point: "a traditional database system might use locks
to atomically control the visibility of these updates ... [serializable
approaches incur] throughput reductions ranging from 66-88%".

This engine executes the *same* TPC-C effects but forces the coordination
pattern a 2PC/serializable system would exhibit on a device mesh:

  1. every shard broadcasts its full write intent (no outbox deferral):
     remote stock updates are routed and applied synchronously inside the
     step via all-gather — the prepare phase's payload;
  2. a commit barrier: an all-reduce over per-shard vote bits — the
     prepare/commit round-trips, which also serializes the step latency;
  3. wall-clock costs additionally charge the atomic-commitment latency from
     the Monte-Carlo model (latency.py) per conflicting round, since CPU
     simulation cannot reproduce network stalls.

Its compiled HLO therefore *must* contain collectives on the hot path —
the structural signature of coordination (contrast Engine.prove_
coordination_free) — and its throughput model composes device time with
commitment latency.
"""

from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.utils.hlo import collective_stats

from . import ramp, tpcc
from .tpcc import NewOrderBatch, OrderStatusBatch, TPCCScale, TPCCState


@dataclasses.dataclass
class TwoPCEngine:
    """``strict_stock=True`` is the COORDINATION_REQUIRED fallback the
    planner selects for an opaque "serializable stock" invariant
    (``engine.plan_engine(stock_invariant="serial")``): every step
    synchronously broadcasts the full write intent — the global batch AND
    the global state — and every shard replays the whole batch in timestamp
    order against the gathered stock (strict ``s_quantity >= 0``, atomic
    aborts, no restock), keeping only its own slice. That is exactly the
    redundant, collective-heavy execution a serializable system pays for,
    and the contrast to the escrow regime's local ``try_spend``."""

    scale: TPCCScale
    mesh: Mesh
    axis_names: tuple[str, ...] = ("data",)
    strict_stock: bool = False

    def __post_init__(self):
        self.n_shards = int(np.prod([self.mesh.shape[a] for a in self.axis_names]))
        if self.scale.n_warehouses % self.n_shards:
            raise ValueError("warehouses must divide shards")
        self.w_per_shard = self.scale.n_warehouses // self.n_shards
        spec = P(self.axis_names)
        ax = self.axis_names

        @functools.partial(shard_map, mesh=self.mesh,
                           in_specs=(spec, spec),
                           out_specs=(spec, spec),
                           check_vma=False)
        def _step(state: TPCCState, batch: NewOrderBatch):
            idx = jnp.asarray(0)
            for a in ax:
                idx = idx * self.mesh.shape[a] + jax.lax.axis_index(a)
            w_lo = idx * self.w_per_shard
            state, delta, total = tpcc.apply_neworder(
                state, batch, self.scale, w_lo=w_lo,
                w_hi=w_lo + self.w_per_shard)

            # prepare phase: synchronously route every remote write
            gathered = delta
            for a in reversed(ax):
                gathered = jax.tree.map(
                    lambda x: jax.lax.all_gather(x, a), gathered)
            dst = gathered.dst_w.reshape(-1)
            i_id = gathered.i_id.reshape(-1)
            qty = gathered.qty.reshape(-1)
            valid = gathered.valid.reshape(-1)
            own = valid & (dst >= w_lo) & (dst < w_lo + self.w_per_shard)
            state = tpcc.apply_stock_updates(
                state, dst - w_lo, i_id, qty, own, jnp.ones_like(own))

            # commit barrier: unanimous vote (all-reduce over shards)
            vote = jnp.ones((), jnp.int32)
            for a in ax:
                vote = jax.lax.psum(vote, a)
            committed = vote == self.n_shards
            total = jnp.where(committed, total, 0.0)
            return state, total

        @functools.partial(shard_map, mesh=self.mesh,
                           in_specs=(spec, spec),
                           out_specs=spec,
                           check_vma=False)
        def _read(state: TPCCState, batch: OrderStatusBatch):
            idx = jnp.asarray(0)
            for a in ax:
                idx = idx * self.mesh.shape[a] + jax.lax.axis_index(a)
            w_lo = idx * self.w_per_shard
            # lock acquisition: every shard announces its read intent and
            # waits for a global grant — the read-lock round-trip a
            # serializable system pays to make multi-partition reads atomic
            # (contrast: the RAMP read repairs locally, no collectives).
            granted = jnp.ones((batch.w.shape[0],), jnp.int32)
            for a in reversed(ax):
                granted = jax.lax.all_gather(granted, a)
            res = ramp.apply_order_status(state, batch, w_lo=w_lo)
            # release barrier: unanimous vote before results are returned
            vote = jnp.ones((), jnp.int32)
            for a in ax:
                vote = jax.lax.psum(vote, a)
            ok = (vote == self.n_shards) & (granted.sum() > 0)
            return res._replace(found=res.found & ok)

        @functools.partial(shard_map, mesh=self.mesh,
                           in_specs=(spec, spec),
                           out_specs=(spec, spec),
                           check_vma=False)
        def _step_strict(state: TPCCState, batch: NewOrderBatch):
            idx = jnp.asarray(0)
            for a in ax:
                idx = idx * self.mesh.shape[a] + jax.lax.axis_index(a)
            w_lo = idx * self.w_per_shard
            b_local = batch.w.shape[0]

            def gather(x):
                for a in reversed(ax):
                    x = jax.lax.all_gather(x, a)
                if len(ax) > 1:
                    x = x.reshape((-1,) + x.shape[len(ax):])
                return x

            # prepare phase: broadcast the full write intent — the global
            # batch AND the global state (lock acquisition payload)
            g_batch = jax.tree.map(
                lambda x: gather(x).reshape((-1,) + x.shape[1:]), batch)
            g_state = jax.tree.map(
                lambda x: gather(x).reshape((-1,) + x.shape[1:]), state)

            # serializable execution: every shard replays the WHOLE batch in
            # timestamp order with the entire stock as one escrow share —
            # exact sequential strict-stock semantics, replicated work
            shares = g_state.s_quantity
            spent = jnp.zeros_like(shares)
            g_state, _, delta, _, ok = tpcc.apply_neworder_escrow(
                g_state, shares, spent, g_batch, self.scale,
                w_lo=0, w_hi=self.scale.n_warehouses,
                replica=0, num_replicas=1)
            # everything is "local" in the global replay: empty outbox
            del delta

            # commit: keep only this participant's slice of the new state
            state = jax.tree.map(
                lambda g: jax.lax.dynamic_slice_in_dim(
                    g, w_lo, self.w_per_shard, axis=0), g_state)
            ok_local = jax.lax.dynamic_slice_in_dim(
                ok, idx * b_local, b_local, axis=0)

            # commit barrier: unanimous vote (all-reduce over shards)
            vote = jnp.ones((), jnp.int32)
            for a in ax:
                vote = jax.lax.psum(vote, a)
            ok_local = ok_local & (vote == self.n_shards)
            return state, ok_local

        self._step = jax.jit(_step_strict if self.strict_stock else _step,
                             donate_argnums=0)
        self._read = jax.jit(_read)

    def step(self, state: TPCCState, batch: NewOrderBatch):
        """Returns (state, totals) — or (state, committed mask) under
        ``strict_stock`` (aborted transactions have no effects)."""
        return self._step(state, batch)

    def read_step(self, state: TPCCState, batch: OrderStatusBatch):
        """Order-Status under 2PC-style synchronized visibility: the result
        is correct, but the hot path carries lock/commit collectives and the
        wall clock additionally pays the commitment latency (latency.py)."""
        return self._read(state, batch)

    def hot_path_collectives(self, batch_per_shard: int = 8):
        state_sds = tpcc.state_shape_dtypes(self.scale)
        batch_sds = tpcc.neworder_input_specs(
            self.scale, batch_per_shard * self.n_shards)
        text = self._step.lower(state_sds, batch_sds).compile().as_text()
        return collective_stats(text)

    def read_path_collectives(self, batch_per_shard: int = 8):
        state_sds = tpcc.state_shape_dtypes(self.scale)
        batch_sds = tpcc.order_status_input_specs(
            batch_per_shard * self.n_shards)
        text = self._read.lower(state_sds, batch_sds).compile().as_text()
        return collective_stats(text)


def _conflict_rounds(batch, districts: int) -> int:
    """Transactions on the same district conflict (they contend for the
    sequential o_id); a serializable system must run them as SEQUENTIAL
    atomic-commitment rounds — so a batch costs max-txns-per-district
    rounds of commit latency (the paper's §6.1 worst-case accounting)."""
    key = np.asarray(batch.w) * districts + np.asarray(batch.d)
    _, counts = np.unique(key, return_counts=True)
    return int(counts.max()) if counts.size else 1


def run_closed_loop_2pc(engine: TwoPCEngine, state: TPCCState, *,
                        batch_per_shard: int, n_batches: int,
                        remote_frac: float = 0.01, seed: int = 0,
                        commit_latency_s: float = 0.0,
                        item_skew: float = 0.0):
    """Drive the coordinated baseline. Per batch it charges
    ``commit_latency_s`` x (conflicting rounds on the hottest district) —
    the serialization the coordination-avoiding engine's batched
    increment-and-get makes unnecessary. Under ``strict_stock`` the step
    returns committed masks; aborted (insufficient-stock) transactions are
    reported in ``stats.aborted``."""
    from .engine import RunStats, _tree_copy

    rng = np.random.default_rng(seed)
    B = batch_per_shard * engine.n_shards
    batches = []
    ts0 = 0
    for _ in range(n_batches):
        parts = []
        for s in range(engine.n_shards):
            parts.append(tpcc.generate_neworder(
                rng, engine.scale, batch_per_shard, remote_frac=remote_frac,
                w_lo=s * engine.w_per_shard,
                w_hi=(s + 1) * engine.w_per_shard, ts0=ts0,
                item_skew=item_skew))
            ts0 += batch_per_shard
        batches.append(jax.tree.map(lambda *xs: jnp.concatenate(xs), *parts))

    if engine.strict_stock:
        # warmup on a copy so every batch is timed exactly once
        warm, _ = engine.step(_tree_copy(state), batches[0])
        jax.block_until_ready(warm)
        del warm

        stats = RunStats()
        commit_acc = jnp.zeros((), jnp.int32)
        latency_charged = 0.0
        t0 = time.perf_counter()
        for i in range(n_batches):
            state, ok = engine.step(state, batches[i])
            commit_acc = commit_acc + ok.sum().astype(jnp.int32)
            stats.batches += 1
            latency_charged += commit_latency_s * _conflict_rounds(
                batches[i], engine.scale.districts)
        jax.block_until_ready((state, commit_acc))
        stats.wall_seconds = (time.perf_counter() - t0) + latency_charged
        stats.committed = int(commit_acc)
        stats.aborted = B * n_batches - stats.committed
        return state, stats

    state, _ = engine.step(state, batches[0])  # warmup
    jax.block_until_ready(state)

    stats = RunStats()
    latency_charged = 0.0
    t0 = time.perf_counter()
    for i in range(1, n_batches):
        state, totals = engine.step(state, batches[i])
        stats.committed += B
        stats.batches += 1
        latency_charged += commit_latency_s * _conflict_rounds(
            batches[i], engine.scale.districts)
    jax.block_until_ready(state)
    stats.wall_seconds = (time.perf_counter() - t0) + latency_charged
    return state, stats
