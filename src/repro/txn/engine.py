"""Coordination-avoiding TPC-C execution engine (paper §6.2).

Execution model (the paper's Fig. 1, realized on a device mesh):

* **hot path** — :meth:`Engine.neworder_step`: every shard executes the
  New-Order transactions homed at its warehouses against its local state.
  Foreign-key inserts are installed locally (I-confluent); the district
  order-ID counter is a shard-local batched increment-and-get; remote stock
  updates are *emitted* into a COO outbox instead of being applied. The
  compiled hot path contains **zero collective ops** — asserted structurally
  from its HLO (tests/test_engine.py, launch/dryrun.py).

* **anti-entropy** — :meth:`Engine.anti_entropy`: asynchronously (off the
  critical path, every k batches) shards exchange outboxes via all-gather and
  each owner applies the stock updates destined to it. This is the paper's
  convergence requirement (Definition 3): merges may stall arbitrarily as
  long as they eventually run.

The same effects executed with per-transaction synchronous coordination form
the baseline in twopc.py.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.lattice import EscrowCounter, HotSetEscrow
from repro.core.planner import CoordClass, plan as plan_specs
from repro.core.analyzer import Strategy
from repro.utils.hlo import assert_no_collectives, collective_stats

from . import ramp, tpcc
from .tpcc import (NewOrderBatch, OrderStatusBatch, PaymentBatch,
                   StockDelta, StockLevelBatch, TPCCScale, TPCCState,
                   tpcc_state_specs)

Array = jax.Array


@dataclasses.dataclass
class Engine:
    """Shards TPC-C state by warehouse over ``axis_names`` of ``mesh``.

    At construction the engine declares every TPC-C state element as a
    planner StateSpec (tpcc.tpcc_state_specs) and runs
    ``core.planner.plan()`` over them; the resulting CoordinationPlan — not
    a hand flag — selects the execution strategy per element:

      * COORDINATION_FREE  -> the local merge path (outbox + asynchronous
        anti-entropy), i.e. everything this engine always did;
      * ESCROW             -> the escrowed strict-stock hot path: per-replica
        EscrowCounter shares resident on device, ``try_spend``-style local
        admission inside New-Order, and an amortized share ``refresh`` as
        the ONLY collective of the regime (paper §8);
      * COORDINATION_REQUIRED -> refused here; ``plan_engine`` falls back to
        the synchronous TwoPCEngine baseline.

    ``stock_invariant`` ("restock" | "strict" | "serial") is the
    application's schema declaration for STOCK.S_QUANTITY — the knob is
    *what invariant is demanded*; the regime is derived by the analyzer.

    ``escrow_layout`` selects the ESCROW regime's state layout:

      * "sparse" (default) — the two-tier hot-set layout: a compact
        device-resident HotSetEscrow over the top-K contended cells of the
        Zipfian access profile (``hot_items`` popular item ids x every
        warehouse; see tpcc.select_hot_cells), with the cold tail
        owner-routed through the outbox and serialized strictly at the
        owning shard. ~67x less escrow residency per device at spec scale
        (tpcc.escrow_layout_bytes; asserted >= 50x in the dry-run).
      * "dense" — the PR-3 ``[R, W, I]`` EscrowCounter (every replica holds
        a share of every cell); kept as the comparison baseline for the
        ``escrow_sparse_vs_dense`` benchmark.

    ``admission`` selects the escrow-admission strategy of
    ``tpcc.admit_fcfs`` (both layouts, bit-identical results):

      * "scan"   — the B-step sequential FCFS ``lax.scan`` baseline;
      * "kernel" — the two-level pipeline: contention gate (per-cell total
        demand vs headroom, order-free where it fits) + the Pallas FCFS
        kernel over the residual transactions with the availability vector
        resident in VMEM (kernels/escrow_admit.py);
      * "auto" (default) — per-batch-shape static choice: the memoized
        one-shot backend autotune (tpcc.resolve_admission_cutover) times
        scan vs kernel at first use; tpcc.AUTO_KERNEL_MIN_BATCH is the
        no-autotune fallback.

    ``effects`` selects the ESCROW regime's committed-effects strategy
    (both layouts, bit-identical results):

      * "fused" (default) — the one-kernel megastep
        (kernels/txn_megastep.py): admission, committed effects and the
        RAMP write-set stamp run over one VMEM residency of the hot tiles,
        and the tables take dense vector adds from the kernel's effect
        products;
      * "scan" — the definitional per-phase dispatch path
        (tpcc._neworder_committed_effects), kept as the bit-exactness
        baseline and comparison row (BENCH_megastep_fused.json).
    """

    scale: TPCCScale
    mesh: Mesh
    axis_names: tuple[str, ...] = ("data",)
    stock_invariant: str = "restock"
    escrow_layout: str = "sparse"
    hot_items: int | None = None
    admission: str = "auto"
    effects: str = "fused"

    def __post_init__(self):
        self.n_shards = int(np.prod([self.mesh.shape[a] for a in self.axis_names]))
        if self.scale.n_warehouses % self.n_shards:
            raise ValueError(
                f"{self.scale.n_warehouses} warehouses not divisible by "
                f"{self.n_shards} shards")
        self.w_per_shard = self.scale.n_warehouses // self.n_shards

        # -- the coordination plan drives regime selection -------------------
        self.plan = plan_specs(tpcc_state_specs(self.stock_invariant))
        self.stock_regime = self.plan.entry("stock.s_quantity").coord_class
        if self.stock_regime is CoordClass.REQUIRED:
            raise ValueError(
                "planner classified stock.s_quantity as "
                "COORDINATION_REQUIRED — this coordination-avoiding engine "
                "cannot satisfy it; use plan_engine() to fall back to the "
                "synchronous TwoPCEngine baseline")
        # the district o_id counter must be the deferred-assignment regime —
        # the batched local increment-and-get in apply_neworder implements it
        assert (self.plan.entry("district.d_next_o_id").strategy
                is Strategy.DEFERRED_ASSIGNMENT)
        # strict floor (no restock) iff the plan put stock under escrow
        self._restock = self.stock_regime is CoordClass.FREE

        self.state_spec = P(self.axis_names)   # shard dim 0 (warehouse)
        self.batch_spec = P(self.axis_names)   # per-shard home batches
        # escrow state sharding, per layout: dense shards the whole
        # EscrowCounter on its replica-slot dim; sparse replicates the [K]
        # key table and shards the [R, K] share/spent slots
        if self.escrow_layout not in ("sparse", "dense"):
            raise ValueError(f"unknown escrow_layout {self.escrow_layout!r};"
                             f" choose 'sparse' or 'dense'")
        if self.admission not in tpcc.ADMISSION_MODES:
            raise ValueError(f"unknown admission {self.admission!r}; "
                             f"choose from {tpcc.ADMISSION_MODES}")
        if self.effects not in tpcc.EFFECTS_MODES:
            raise ValueError(f"unknown effects {self.effects!r}; "
                             f"choose from {tpcc.EFFECTS_MODES}")
        if self.hot_items is None:
            self.hot_items = tpcc.default_hot_items(self.scale)
        if self.escrow_layout == "sparse":
            self.escrow_spec = HotSetEscrow(P(), P(self.axis_names),
                                            P(self.axis_names))
        else:
            self.escrow_spec = P(self.axis_names)
        ax = self.axis_names

        @functools.partial(
            shard_map, mesh=self.mesh,
            in_specs=(self.state_spec, self.batch_spec),
            out_specs=(self.state_spec, self.batch_spec, self.batch_spec),
            check_vma=False)
        def _neworder(state: TPCCState, batch: NewOrderBatch):
            idx = self._shard_index()
            w_lo = idx * self.w_per_shard
            state, delta, total = tpcc.apply_neworder(
                state, batch, self.scale, w_lo=w_lo,
                w_hi=w_lo + self.w_per_shard,
                replica=idx, num_replicas=self.n_shards)
            return state, delta, total

        @functools.partial(
            shard_map, mesh=self.mesh,
            in_specs=(self.state_spec, self.batch_spec),
            out_specs=self.state_spec,
            check_vma=False)
        def _anti_entropy(state: TPCCState, outbox: StockDelta):
            w_lo = self._shard_index() * self.w_per_shard
            return gather_and_apply_outbox(state, outbox, ax, w_lo,
                                           self.w_per_shard,
                                           restock=self._restock)

        @functools.partial(
            shard_map, mesh=self.mesh,
            in_specs=(self.state_spec, self.batch_spec),
            out_specs=self.state_spec,
            check_vma=False)
        def _payment(state: TPCCState, batch: PaymentBatch):
            w_lo = self._shard_index() * self.w_per_shard
            return tpcc.apply_payment(state, batch, w_lo=w_lo)

        @functools.partial(
            shard_map, mesh=self.mesh,
            in_specs=(self.state_spec,),
            out_specs=(self.state_spec, self.batch_spec),
            check_vma=False)
        def _delivery(state: TPCCState):
            # one order per district is delivered, and only where one exists
            n = state.no_valid.any(axis=2).sum().reshape(1)
            state = tpcc.apply_delivery(state, jnp.asarray(1, jnp.int32),
                                        jnp.asarray(0, jnp.int32))
            return state, n

        @functools.partial(
            shard_map, mesh=self.mesh,
            in_specs=(self.state_spec, self.batch_spec),
            out_specs=self.batch_spec,
            check_vma=False)
        def _order_status(state: TPCCState, batch: OrderStatusBatch):
            w_lo = self._shard_index() * self.w_per_shard
            return ramp.apply_order_status(state, batch, w_lo=w_lo)

        @functools.partial(
            shard_map, mesh=self.mesh,
            in_specs=(self.state_spec, self.batch_spec),
            out_specs=self.batch_spec,
            check_vma=False)
        def _stock_level(state: TPCCState, batch: StockLevelBatch):
            w_lo = self._shard_index() * self.w_per_shard
            return ramp.apply_stock_level(state, batch, self.scale, w_lo=w_lo)

        self._neworder = jax.jit(_neworder, donate_argnums=0)
        self._anti_entropy = jax.jit(_anti_entropy, donate_argnums=0)
        self._payment = jax.jit(_payment, donate_argnums=0)
        self._delivery = jax.jit(_delivery, donate_argnums=0)
        # read path: no donation — reads must not consume the state
        self._order_status = jax.jit(_order_status)
        self._stock_level = jax.jit(_stock_level)

        if self.stock_regime is CoordClass.ESCROW:
            sparse = self.escrow_layout == "sparse"
            self._hot_keys_np = tpcc.select_hot_cells(self.scale,
                                                      self.hot_items)
            self.hot_keys = jnp.asarray(self._hot_keys_np)

            @functools.partial(
                shard_map, mesh=self.mesh,
                in_specs=(self.state_spec, self.escrow_spec, self.batch_spec),
                out_specs=(self.state_spec, self.escrow_spec, self.batch_spec,
                           self.batch_spec, self.batch_spec),
                check_vma=False)
            def _neworder_escrow(state: TPCCState, esc, batch: NewOrderBatch):
                idx = self._shard_index()
                w_lo = idx * self.w_per_shard
                if sparse:
                    state, spent, delta, total, ok = \
                        tpcc.apply_neworder_escrow_sparse(
                            state, esc.keys, esc.shares[0], esc.spent[0],
                            batch, self.scale, w_lo=w_lo,
                            w_hi=w_lo + self.w_per_shard,
                            replica=idx, num_replicas=self.n_shards,
                            admission=self.admission,
                            effects=self.effects)
                else:
                    state, spent, delta, total, ok = \
                        tpcc.apply_neworder_escrow(
                            state, esc.shares[0], esc.spent[0], batch,
                            self.scale, w_lo=w_lo,
                            w_hi=w_lo + self.w_per_shard,
                            replica=idx, num_replicas=self.n_shards,
                            admission=self.admission,
                            effects=self.effects)
                return (state, esc._replace(spent=spent[None]), delta, total,
                        ok)

            @functools.partial(
                shard_map, mesh=self.mesh,
                in_specs=(self.state_spec, self.escrow_spec, P()),
                out_specs=self.escrow_spec,
                check_vma=False)
            def _refresh(state: TPCCState, esc, alive):
                # THE amortized coordination point of the escrow regime:
                # re-partition the owners' post-drain stock into fresh
                # per-replica shares (spent resets to zero). Sparse gathers
                # ONLY the K hot cells (one psum over [K]) instead of the
                # dense layout's full [W, I] stock all-gather. ``alive``
                # ([n_shards], replicated) reclaims dead replicas' headroom
                # for the survivors at this boundary.
                idx = self._shard_index()
                if sparse:
                    return gather_and_refresh_hot_shares(
                        state, esc.keys, ax, idx, self.n_shards,
                        self.scale.n_items, idx * self.w_per_shard,
                        self.w_per_shard, alive=alive)
                return gather_and_refresh_shares(state, ax, idx,
                                                 self.n_shards, alive=alive)

            @functools.partial(
                shard_map, mesh=self.mesh,
                in_specs=(self.state_spec, self.batch_spec),
                out_specs=(self.state_spec, self.batch_spec),
                check_vma=False)
            def _drain_strict(state: TPCCState, outbox: StockDelta):
                # strict-regime anti-entropy: hot entries (escrow-admitted)
                # apply unconditionally; cold entries are serialized here, at
                # their owner, with per-cell all-or-nothing admission —
                # oversell-free without shares. Dense has no cold tier.
                w_lo = self._shard_index() * self.w_per_shard
                if sparse:
                    return gather_and_apply_outbox_strict(
                        state, outbox, self.hot_keys, ax, w_lo,
                        self.w_per_shard, self.scale.n_items)
                state = gather_and_apply_outbox(state, outbox, ax, w_lo,
                                                self.w_per_shard,
                                                restock=False)
                return state, jnp.zeros((1,), jnp.int32)

            self._neworder_escrow = jax.jit(_neworder_escrow,
                                            donate_argnums=(0, 1))
            self._refresh_escrow = jax.jit(_refresh, donate_argnums=1)
            self._drain_strict = jax.jit(_drain_strict, donate_argnums=0)

            self.retry_spec = tpcc.RetryState(*([P(self.axis_names)] * 6))

            @functools.partial(
                shard_map, mesh=self.mesh,
                in_specs=(self.state_spec, self.batch_spec,
                          self.retry_spec, P(), P()),
                out_specs=(self.state_spec, self.retry_spec,
                           self.batch_spec),
                check_vma=False)
            def _drain_strict_retry(state: TPCCState, outbox: StockDelta,
                                    retry, retry_max, reserve):
                # strict drain with the bounded owner-side retry ring: ring
                # entries are re-presented first, fresh cold rejects requeue
                # (up to retry_max windows) instead of silently dropping;
                # reserve > 0 adds the owner-granted reservation round-trip
                # for last-chance losers. Sparse-only (dense has no cold
                # tier).
                w_lo = self._shard_index() * self.w_per_shard
                return gather_and_apply_outbox_strict_retry(
                    state, outbox, retry, self.hot_keys, ax, w_lo,
                    self.w_per_shard, self.scale.n_items, retry_max,
                    reserve)

            if sparse:
                self._drain_strict_retry = jax.jit(_drain_strict_retry,
                                                   donate_argnums=(0, 2))
            # all-shards-live default for refresh_escrow(alive=None): with
            # every slot live the masked partition is value-identical to
            # the unmasked one, so the non-failure path is unchanged
            self._alive_all = jax.device_put(
                jnp.ones((self.n_shards,), jnp.int32),
                NamedSharding(self.mesh, P()))

    # -- helpers --------------------------------------------------------------

    def _shard_index(self):
        idx = jnp.asarray(0)
        for a in self.axis_names:
            idx = idx * self.mesh.shape[a] + jax.lax.axis_index(a)
        return idx

    def shard_state(self, state: TPCCState) -> TPCCState:
        return self.place_state(state)[0]

    def place_state(self, state: TPCCState) -> tuple[TPCCState, int]:
        """``state`` on the run sharding, and how many leaves had to move.

        A leaf already committed to that placement passes through untouched,
        also where its sharding is spelled otherwise: a program's outputs on
        a one-device mesh say ``P()`` for ``P("data")``, and a put would
        re-wrap every table. The rest go in one batched put."""
        sharding = NamedSharding(self.mesh, self.state_spec)
        leaves, tree = jax.tree.flatten(state)
        move = [i for i, x in enumerate(leaves)
                if not (getattr(x, "_committed", False)
                        and x.sharding.is_equivalent_to(sharding, x.ndim))]
        if move:
            put = jax.device_put([leaves[i] for i in move], sharding)
            for i, x in zip(move, put):
                leaves[i] = x
        return jax.tree.unflatten(tree, leaves), len(move)

    # -- public API -----------------------------------------------------------

    def neworder_step(self, state: TPCCState, batch: NewOrderBatch):
        """Hot path: returns (state, outbox, totals). Zero collectives."""
        return self._neworder(state, batch)

    # -- escrow regime (plan-selected; paper §8) ------------------------------

    def _require_escrow(self):
        if self.stock_regime is not CoordClass.ESCROW:
            raise RuntimeError(
                f"stock regime is {self.stock_regime.value!r}, not escrow — "
                f"construct the engine with stock_invariant='strict' (the "
                f"plan, not a flag, selects the escrow path)")

    def init_escrow(self, state: TPCCState):
        """Device-resident per-replica shares partitioning the current stock.

        sparse layout — a HotSetEscrow over the K hot cells (keys replicated,
        [R, K] shares/spent sharded on the replica-slot dim); dense layout —
        the full [R, W, I] EscrowCounter."""
        self._require_escrow()
        if self.escrow_layout == "sparse":
            q = np.asarray(jax.device_get(state.s_quantity))
            budgets = q.reshape(-1)[self._hot_keys_np]
            esc = HotSetEscrow.make(self.n_shards, self._hot_keys_np, budgets)
            rep = NamedSharding(self.mesh, P())
            sh = NamedSharding(self.mesh, P(self.axis_names))
            return HotSetEscrow(jax.device_put(esc.keys, rep),
                                jax.device_put(esc.shares, sh),
                                jax.device_put(esc.spent, sh))
        shares = tpcc.make_escrow_shares(jax.device_get(state.s_quantity),
                                         self.n_shards)
        sh = NamedSharding(self.mesh, self.escrow_spec)
        return EscrowCounter(jax.device_put(shares, sh),
                             jax.device_put(jnp.zeros_like(shares), sh))

    def neworder_escrow_step(self, state: TPCCState, esc: EscrowCounter,
                             batch: NewOrderBatch):
        """Escrow hot path: strict-stock New-Order with local ``try_spend``
        admission. Returns (state, esc, outbox, totals, committed mask).
        Zero collectives (proved structurally)."""
        self._require_escrow()
        return self._neworder_escrow(state, esc, batch)

    def refresh_escrow(self, state: TPCCState, esc, alive=None):
        """The amortized coordination point: re-partition post-drain stock
        into fresh shares (contains collectives; off the hot path).

        ``alive`` ([n_shards] mask, default all-live) is liveness-aware
        share reclamation: dead replicas' slots refresh to ZERO and their
        headroom — already folded into post-drain stock — partitions among
        the survivors. Zeroed slots survive the conservative min-join, so
        reclamation never manufactures admission capacity."""
        self._require_escrow()
        if alive is None:
            alive = self._alive_all
        return self._refresh_escrow(state, esc, jnp.asarray(alive, jnp.int32))

    def drain_strict(self, state: TPCCState,
                     outbox: StockDelta) -> tuple[TPCCState, Array]:
        """Strict-regime anti-entropy: apply queued outbox entries without
        restock — hot entries unconditionally (share-admitted upstream),
        cold entries under the owner's per-cell all-or-nothing admission.
        Returns (state, per-shard cold-reject counts [n_shards])."""
        self._require_escrow()
        return self._drain_strict(state, outbox)

    def init_retry(self, retry_cap: int) -> tpcc.RetryState:
        """Per-owner bounded retry ring ([n_shards, retry_cap] lanes,
        sharded on the owner dim) for drain_strict_retry."""
        self._require_escrow()
        sh = NamedSharding(self.mesh, P(self.axis_names))
        return jax.tree.map(
            lambda x: jax.device_put(x[None].repeat(self.n_shards, 0), sh),
            tpcc.empty_retry(retry_cap))

    def retry_input_specs(self, retry_cap: int) -> tpcc.RetryState:
        i32 = jax.ShapeDtypeStruct((self.n_shards, retry_cap), jnp.int32)
        b = jax.ShapeDtypeStruct((self.n_shards, retry_cap), jnp.bool_)
        return tpcc.RetryState(i32, i32, i32, i32, b, b)

    def drain_strict_retry(self, state: TPCCState, outbox: StockDelta,
                           retry: tpcc.RetryState, retry_max=0, reserve=0
                           ) -> tuple[TPCCState, tpcc.RetryState, Array]:
        """Strict drain with the bounded cold-retry ring: owner-rejected
        remote-cold entries are re-presented for up to ``retry_max`` drain
        windows (a traced scalar — no recompile per value) before counting
        as FINAL rejects; ``reserve`` > 0 (also traced) converts
        last-chance losers into owner-granted reservations instead (see
        tpcc.apply_stock_updates_strict_tiered_retry). Returns (state,
        retry', per-shard final-reject counts [n_shards]). Sparse layout
        only (dense has no cold tier)."""
        self._require_escrow()
        if self.escrow_layout != "sparse":
            raise RuntimeError("drain_strict_retry requires the sparse "
                               "(two-tier) escrow layout")
        return self._drain_strict_retry(state, outbox, retry,
                                        jnp.asarray(retry_max, jnp.int32),
                                        jnp.asarray(reserve, jnp.int32))

    def escrow_bytes_per_device(self) -> dict:
        """Per-device escrow residency of this engine's layout vs the dense
        baseline (the dry-run's >= 50x memory-cut assertion reads this)."""
        self._require_escrow()
        out = tpcc.escrow_layout_bytes(self.scale, self.hot_items)
        out["layout"] = self.escrow_layout
        out["bytes_per_device"] = (
            out["sparse_bytes_per_device"] if self.escrow_layout == "sparse"
            else out["dense_bytes_per_device"])
        return out

    def anti_entropy(self, state: TPCCState, outbox: StockDelta) -> TPCCState:
        """Asynchronous convergence step (contains collectives, off hot path)."""
        return self._anti_entropy(state, outbox)

    def payment_step(self, state: TPCCState, batch: PaymentBatch) -> TPCCState:
        return self._payment(state, batch)

    def delivery_step(self, state: TPCCState) -> tuple[TPCCState, Array]:
        """Returns (state, per-shard delivered-order counts)."""
        return self._delivery(state)

    def order_status_step(self, state: TPCCState,
                          batch: OrderStatusBatch) -> ramp.OrderStatusResult:
        """RAMP read path: atomic visibility, zero collectives."""
        return self._order_status(state, batch)

    def stock_level_step(self, state: TPCCState,
                         batch: StockLevelBatch) -> ramp.StockLevelResult:
        """RAMP read path: atomic visibility, zero collectives."""
        return self._stock_level(state, batch)

    # -- structural proofs ------------------------------------------------------

    def lowered_neworder(self, batch_per_shard: int):
        state_sds = tpcc.state_shape_dtypes(self.scale)
        batch_sds = tpcc.neworder_input_specs(
            self.scale, batch_per_shard * self.n_shards)
        return self._neworder.lower(state_sds, batch_sds)

    def prove_coordination_free(self, batch_per_shard: int = 8) -> str:
        """Definition 5, structurally: the compiled hot path of the
        PLAN-SELECTED regime has no collectives. Returns the stats line."""
        if self.stock_regime is CoordClass.ESCROW:
            text = self.lowered_neworder_escrow(
                batch_per_shard).compile().as_text()
            assert_no_collectives(
                text, context="TPC-C escrow New-Order hot path")
            return collective_stats(text).describe()
        text = self.lowered_neworder(batch_per_shard).compile().as_text()
        assert_no_collectives(text, context="TPC-C New-Order hot path")
        return collective_stats(text).describe()

    def escrow_input_specs(self):
        if self.escrow_layout == "sparse":
            K = self._hot_keys_np.shape[0]
            return HotSetEscrow(
                jax.ShapeDtypeStruct((K,), jnp.int32),
                jax.ShapeDtypeStruct((self.n_shards, K), jnp.int32),
                jax.ShapeDtypeStruct((self.n_shards, K), jnp.int32))
        W, I = self.scale.n_warehouses, self.scale.n_items
        f = jax.ShapeDtypeStruct((self.n_shards, W, I), jnp.int32)
        return EscrowCounter(f, f)

    def lowered_neworder_escrow(self, batch_per_shard: int):
        self._require_escrow()
        state_sds = tpcc.state_shape_dtypes(self.scale)
        batch_sds = tpcc.neworder_input_specs(
            self.scale, batch_per_shard * self.n_shards)
        return self._neworder_escrow.lower(state_sds,
                                           self.escrow_input_specs(),
                                           batch_sds)

    def count_refresh_collectives(self):
        """The escrow regime's ONLY collective program."""
        self._require_escrow()
        text = self._refresh_escrow.lower(
            tpcc.state_shape_dtypes(self.scale),
            self.escrow_input_specs(),
            jax.ShapeDtypeStruct((self.n_shards,), jnp.int32)
        ).compile().as_text()
        return collective_stats(text)

    def lowered_order_status(self, batch_per_shard: int):
        state_sds = tpcc.state_shape_dtypes(self.scale)
        batch_sds = tpcc.order_status_input_specs(
            batch_per_shard * self.n_shards)
        return self._order_status.lower(state_sds, batch_sds)

    def lowered_stock_level(self, batch_per_shard: int):
        state_sds = tpcc.state_shape_dtypes(self.scale)
        batch_sds = tpcc.stock_level_input_specs(
            batch_per_shard * self.n_shards)
        return self._stock_level.lower(state_sds, batch_sds)

    def prove_read_coordination_free(self, batch_per_shard: int = 8) -> str:
        """The RAMP claim, structurally: both compiled read transactions
        (first round, fracture detection, and lookback repair included)
        contain zero collective ops."""
        descs = []
        for name, lowered in (
                ("order-status", self.lowered_order_status(batch_per_shard)),
                ("stock-level", self.lowered_stock_level(batch_per_shard))):
            text = lowered.compile().as_text()
            assert_no_collectives(text, context=f"RAMP {name} read path")
            descs.append(f"{name}: {collective_stats(text).describe()}")
        return "; ".join(descs)

    def count_anti_entropy_collectives(self, batch_per_shard: int = 8):
        state_sds = tpcc.state_shape_dtypes(self.scale)
        R = batch_per_shard * self.n_shards * self.scale.max_lines
        out_sds = StockDelta(
            jax.ShapeDtypeStruct((R,), jnp.int32),
            jax.ShapeDtypeStruct((R,), jnp.int32),
            jax.ShapeDtypeStruct((R,), jnp.int32),
            jax.ShapeDtypeStruct((R,), jnp.bool_))
        text = self._anti_entropy.lower(state_sds, out_sds).compile().as_text()
        return collective_stats(text)

    def coordination_ledger(self, **kw):
        """The one-shot proofs as a continuously-reported budget: per-phase
        collective counts and bytes-on-wire for this engine's plan-selected
        fused closed loop (repro.obs.ledger.build_ledger kwargs: chunk_len,
        batch_per_shard, refresh_every, metrics, ...). Hot phases are
        budget-checked at zero collectives before the ledger is returned."""
        from repro.obs.ledger import build_ledger
        return build_ledger(self, **kw)


def _multi_axis_all_gather(x, axis_names):
    for a in reversed(axis_names):
        x = jax.lax.all_gather(x, a)
    if len(axis_names) > 1:
        x = x.reshape((-1,) + x.shape[len(axis_names):])
    return x


def gather_and_apply_outbox(state: TPCCState, outbox, axis_names,
                            w_lo, w_per_shard,
                            restock: bool = True) -> TPCCState:
    """The anti-entropy body, shared by Engine.anti_entropy and the fused
    executor's ring drain (one definition keeps their semantics — ownership
    predicate, remote flag, gather layout — bit-identical): all-gather every
    shard's outbox and apply the entries this shard owns.

    ``outbox`` is any pytree with dst_w/i_id/qty/valid leaves of equal total
    size (a StockDelta, or the executor's [rows, R] OutboxRing).
    """
    gathered = jax.tree.map(
        lambda x: _multi_axis_all_gather(x, axis_names), outbox)
    dst = gathered.dst_w.reshape(-1)
    i_id = gathered.i_id.reshape(-1)
    qty = gathered.qty.reshape(-1)
    valid = gathered.valid.reshape(-1)
    own = valid & (dst >= w_lo) & (dst < w_lo + w_per_shard)
    # every outbox entry is, by construction, remote to its owner
    return tpcc.apply_stock_updates(state, dst - w_lo, i_id, qty, own,
                                    jnp.ones_like(own), restock=restock)


def gather_and_refresh_shares(state: TPCCState, axis_names, replica,
                              n_shards: int, alive=None) -> "EscrowCounter":
    """The escrow share-refresh body, shared by Engine.refresh_escrow and
    the fused executor's drain+refresh (one definition keeps the regime's
    only coordination point bit-identical across drivers): all-gather the
    owners' current stock and re-partition it into this replica's fresh
    share slot (spent resets to zero). ``alive`` ([R] mask) reclaims dead
    replicas' headroom for the survivors (tpcc.escrow_share_for)."""
    q = _multi_axis_all_gather(state.s_quantity, axis_names)
    q = q.reshape((-1, q.shape[-1]))                              # [W, I]
    share = tpcc.escrow_share_for(q, replica, n_shards, alive=alive)
    return EscrowCounter(share[None], jnp.zeros_like(share)[None])


def gather_and_apply_outbox_strict(state: TPCCState, outbox, hot_keys,
                                   axis_names, w_lo, w_per_shard,
                                   n_items: int) -> tuple[TPCCState, Array]:
    """The sparse strict-drain body, shared by Engine.drain_strict and the
    fused executor's ring drain (one definition keeps the owner-routed cold
    tier's admission — per-cell all-or-nothing, order-invariant over the
    drain window — bit-identical across drivers): all-gather every shard's
    outbox and strictly apply the entries this shard owns, split by hot-set
    tier (tpcc.apply_stock_updates_strict_tiered).

    Returns (state, cold-reject count [1])."""
    gathered = jax.tree.map(
        lambda x: _multi_axis_all_gather(x, axis_names), outbox)
    dst = gathered.dst_w.reshape(-1)
    i_id = gathered.i_id.reshape(-1)
    qty = gathered.qty.reshape(-1)
    valid = gathered.valid.reshape(-1)
    own = valid & (dst >= w_lo) & (dst < w_lo + w_per_shard)
    state, rejects = tpcc.apply_stock_updates_strict_tiered(
        state, hot_keys, dst, i_id, qty, own, jnp.ones_like(own),
        n_items, w_lo=w_lo)
    return state, rejects.reshape(1)


def gather_and_apply_outbox_strict_retry(state: TPCCState, outbox, retry,
                                         hot_keys, axis_names, w_lo,
                                         w_per_shard, n_items: int,
                                         retry_max, reserve=0) -> tuple[
                                             TPCCState, "tpcc.RetryState",
                                             Array]:
    """The retry-aware sparse strict-drain body, shared by
    Engine.drain_strict_retry and the fused executor's retry ring drain:
    all-gather every shard's outbox and strictly apply the entries this
    shard owns, re-presenting this owner's bounded retry ring first
    (tpcc.apply_stock_updates_strict_tiered_retry; ``reserve`` > 0 enables
    the owner-granted reservation round-trip for last-chance losers).
    ``retry`` arrives as the per-shard [1, C] view; returns (state, retry',
    final-rejects [1])."""
    gathered = jax.tree.map(
        lambda x: _multi_axis_all_gather(x, axis_names), outbox)
    dst = gathered.dst_w.reshape(-1)
    i_id = gathered.i_id.reshape(-1)
    qty = gathered.qty.reshape(-1)
    valid = gathered.valid.reshape(-1)
    own = valid & (dst >= w_lo) & (dst < w_lo + w_per_shard)
    ring = jax.tree.map(lambda x: x[0], retry)
    state, ring, final = tpcc.apply_stock_updates_strict_tiered_retry(
        state, hot_keys, dst, i_id, qty, own, jnp.ones_like(own), ring,
        n_items, w_lo=w_lo, retry_max=retry_max, reserve=reserve)
    return state, jax.tree.map(lambda x: x[None], ring), final.reshape(1)


def gather_and_refresh_hot_shares(state: TPCCState, hot_keys, axis_names,
                                  replica, n_shards: int, n_items: int,
                                  w_lo, w_per_shard,
                                  alive=None) -> "HotSetEscrow":
    """The sparse share-refresh body: sum the owners' current stock of the K
    hot cells across shards (one psum over [K] — vs the dense layout's full
    [W, I] all-gather) and re-partition it into this replica's fresh share
    slot (spent resets to zero). ``alive`` ([R] mask) zeroes dead replicas'
    slots and folds their headroom into the survivors' shares."""
    kw = hot_keys // n_items
    ki = hot_keys % n_items
    own = (kw >= w_lo) & (kw < w_lo + w_per_shard)
    q = jnp.where(own, state.s_quantity[jnp.where(own, kw - w_lo, 0), ki], 0)
    for a in reversed(axis_names):
        q = jax.lax.psum(q, a)
    share = tpcc.escrow_share_for(q, replica, n_shards, alive=alive)
    return HotSetEscrow(hot_keys, share[None], jnp.zeros_like(share)[None])


def single_host_engine(scale: TPCCScale,
                       stock_invariant: str = "restock",
                       **engine_kwargs) -> Engine:
    """Engine over the current process's devices (1 on CPU tests)."""
    devs = np.array(jax.devices())
    mesh = Mesh(devs.reshape(len(devs)), ("data",))
    return Engine(scale, mesh, ("data",), stock_invariant=stock_invariant,
                  **engine_kwargs)


def plan_engine(scale: TPCCScale, mesh: Mesh | None = None,
                axis_names: tuple[str, ...] = ("data",),
                stock_invariant: str = "restock", **engine_kwargs):
    """Plan-driven engine selection — the paper's decision procedure as a
    factory: run the analyzer over the declared TPC-C state specs and return

      * :class:`Engine` when every element is COORDINATION_FREE or ESCROW
        (merge and escrow hot paths, zero collectives between merges /
        refreshes), or
      * the synchronous :class:`repro.txn.twopc.TwoPCEngine` (strict-stock
        variant) when the plan demands COORDINATION_REQUIRED — coordination
        is the fallback, never the default.
    """
    if mesh is None:
        devs = np.array(jax.devices())
        mesh = Mesh(devs.reshape(len(devs)), ("data",))
    cplan = plan_specs(tpcc_state_specs(stock_invariant))
    regime = cplan.entry("stock.s_quantity").coord_class
    if regime is CoordClass.REQUIRED:
        from .twopc import TwoPCEngine
        eng = TwoPCEngine(scale, mesh, axis_names, strict_stock=True)
        eng.plan = cplan
        return eng
    return Engine(scale, mesh, axis_names, stock_invariant=stock_invariant,
                  **engine_kwargs)


# ---------------------------------------------------------------------------
# Closed-loop drivers live in txn/drivers.py (one consolidated
# pending-outbox/stats/audit core for every regime x mode); the names below
# stay importable from this module for compatibility. PEP 562 lazy re-export
# avoids an import cycle (drivers imports this module).
# ---------------------------------------------------------------------------

_DRIVER_EXPORTS = (
    "RunStats", "MixStats", "run_closed_loop", "run_mixed_loop",
    "run_escrow_loop", "run_loop", "generate_mix_batches",
    "generate_neworder_stream", "counters_to_stats", "_concat_outboxes",
    "_home_partitioned", "_neworder_batch", "_tree_copy",
)


def __getattr__(name):
    if name in _DRIVER_EXPORTS:
        from . import drivers
        return getattr(drivers, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
