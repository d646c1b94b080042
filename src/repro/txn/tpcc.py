"""TPC-C in JAX — schema, transaction generators, vectorized effects, and the
twelve consistency criteria (paper §6.2).

Everything is dense and fixed-shape so the whole workload jits and shards:
state arrays carry a leading warehouse dimension ``W`` and are partitioned
over the device mesh by warehouse (the standard TPC-C partitioning the paper
assumes: "under standard partitioning strategies, this synchronous
coordination can be limited to ... each district's order sequence (on a
single server)").

Scaled-down defaults keep CPU tests fast; the dry-run lowers the full-scale
configuration (100k items) without allocating.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.invariants import Invariant, InvariantKind
from repro.core.lattice import hot_position

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class TPCCScale:
    n_warehouses: int = 4
    districts: int = 10          # districts per warehouse (spec: 10)
    customers: int = 64          # customers per district (spec: 3000)
    n_items: int = 256           # item catalog (spec: 100_000)
    order_capacity: int = 128    # order slots per district (ring)
    max_lines: int = 15          # order lines per order (spec: 5..15)

    @staticmethod
    def spec_scale(n_warehouses: int = 256) -> "TPCCScale":
        """Full TPC-C cardinalities (used by the dry-run only)."""
        return TPCCScale(n_warehouses=n_warehouses, districts=10,
                         customers=3000, n_items=100_000,
                         order_capacity=8192, max_lines=15)


class TPCCState(NamedTuple):
    """All tables, warehouse-major. Shardable on dim 0 everywhere."""

    # WAREHOUSE
    w_ytd: Array        # [W]
    w_tax: Array        # [W]
    # DISTRICT
    d_next_o_id: Array  # [W, D] int32 — THE sequential counter (§6.2)
    d_ytd: Array        # [W, D]
    d_tax: Array        # [W, D]
    h_amount_sum: Array  # [W, D] materialized history sum (criteria 8, 9)
    # CUSTOMER
    c_balance: Array       # [W, D, C]
    c_ytd_payment: Array   # [W, D, C]
    c_payment_cnt: Array   # [W, D, C] int32
    c_delivery_cnt: Array  # [W, D, C] int32
    c_discount: Array      # [W, D, C]
    c_delivered_sum: Array  # [W, D, C] materialized sum of delivered OL amounts
    # STOCK
    s_quantity: Array    # [W, I] int32
    s_ytd: Array         # [W, I]
    s_order_cnt: Array   # [W, I] int32
    s_remote_cnt: Array  # [W, I] int32
    # ITEM (read-only; replicated per shard for locality)
    i_price: Array       # [W, I]
    # ORDER / NEW-ORDER / ORDER-LINE (ring-buffered per district)
    o_valid: Array    # [W, D, OC] bool
    o_c_id: Array     # [W, D, OC] int32
    o_ol_cnt: Array   # [W, D, OC] int32
    o_carrier: Array  # [W, D, OC] int32 (-1 = null: undelivered)
    o_entry_d: Array  # [W, D, OC] int32 (logical timestamp)
    no_valid: Array   # [W, D, OC] bool — NEW-ORDER table presence
    ol_valid: Array      # [W, D, OC, L] bool — *prepared* layer (RAMP retention)
    ol_i_id: Array       # [W, D, OC, L] int32
    ol_supply_w: Array   # [W, D, OC, L] int32
    ol_qty: Array        # [W, D, OC, L] int32
    ol_amount: Array     # [W, D, OC, L]
    ol_delivered: Array  # [W, D, OC, L] bool
    # RAMP atomic-visibility metadata (txn/ramp.py): every New-Order write set
    # shares one replica-namespaced timestamp; the ORDER row is the commit
    # record (ts + sibling count o_ol_cnt) and order-lines carry the same
    # stamp. ol_vis is the *committed* layer first-round reads see; ol_valid
    # above is the prepared layer the second (lookback) round repairs from.
    o_ts: Array    # [W, D, OC] int32 — commit-record timestamp (-1 = none)
    ol_ts: Array   # [W, D, OC, L] int32 — prepared-version timestamp (-1 = none)
    ol_vis: Array  # [W, D, OC, L] bool — line visible in the committed layer


def init_state(scale: TPCCScale, seed: int = 0, dtype=jnp.float32) -> TPCCState:
    rng = np.random.default_rng(seed)
    W, D, C = scale.n_warehouses, scale.districts, scale.customers
    I, OC, L = scale.n_items, scale.order_capacity, scale.max_lines
    price = rng.uniform(1.0, 100.0, size=(I,)).astype(np.float32)
    return TPCCState(
        w_ytd=jnp.zeros((W,), dtype),
        w_tax=jnp.asarray(rng.uniform(0.0, 0.2, (W,)).astype(np.float32)),
        d_next_o_id=jnp.zeros((W, D), jnp.int32),
        d_ytd=jnp.zeros((W, D), dtype),
        d_tax=jnp.asarray(rng.uniform(0.0, 0.2, (W, D)).astype(np.float32)),
        h_amount_sum=jnp.zeros((W, D), dtype),
        c_balance=jnp.zeros((W, D, C), dtype),
        c_ytd_payment=jnp.zeros((W, D, C), dtype),
        c_payment_cnt=jnp.zeros((W, D, C), jnp.int32),
        c_delivery_cnt=jnp.zeros((W, D, C), jnp.int32),
        c_discount=jnp.asarray(rng.uniform(0.0, 0.5, (W, D, C)).astype(np.float32)),
        c_delivered_sum=jnp.zeros((W, D, C), dtype),
        s_quantity=jnp.asarray(rng.integers(10, 101, (W, I)).astype(np.int32)),
        s_ytd=jnp.zeros((W, I), dtype),
        s_order_cnt=jnp.zeros((W, I), jnp.int32),
        s_remote_cnt=jnp.zeros((W, I), jnp.int32),
        i_price=jnp.asarray(np.broadcast_to(price, (W, I)).copy()),
        o_valid=jnp.zeros((W, D, OC), jnp.bool_),
        o_c_id=jnp.zeros((W, D, OC), jnp.int32),
        o_ol_cnt=jnp.zeros((W, D, OC), jnp.int32),
        o_carrier=jnp.full((W, D, OC), -1, jnp.int32),
        o_entry_d=jnp.zeros((W, D, OC), jnp.int32),
        no_valid=jnp.zeros((W, D, OC), jnp.bool_),
        ol_valid=jnp.zeros((W, D, OC, L), jnp.bool_),
        ol_i_id=jnp.zeros((W, D, OC, L), jnp.int32),
        ol_supply_w=jnp.zeros((W, D, OC, L), jnp.int32),
        ol_qty=jnp.zeros((W, D, OC, L), jnp.int32),
        ol_amount=jnp.zeros((W, D, OC, L), dtype),
        ol_delivered=jnp.zeros((W, D, OC, L), jnp.bool_),
        o_ts=jnp.full((W, D, OC), -1, jnp.int32),
        ol_ts=jnp.full((W, D, OC, L), -1, jnp.int32),
        ol_vis=jnp.zeros((W, D, OC, L), jnp.bool_),
    )


def state_shape_dtypes(scale: TPCCScale) -> TPCCState:
    """ShapeDtypeStruct stand-in for the dry-run (no allocation)."""
    concrete = jax.eval_shape(lambda: init_state(TPCCScale(
        n_warehouses=scale.n_warehouses, districts=scale.districts,
        customers=scale.customers, n_items=scale.n_items,
        order_capacity=scale.order_capacity, max_lines=scale.max_lines)))
    return concrete


# ---------------------------------------------------------------------------
# Transaction inputs
# ---------------------------------------------------------------------------


class NewOrderBatch(NamedTuple):
    w: Array          # [B] home warehouse
    d: Array          # [B] district
    c: Array          # [B] customer
    n_lines: Array    # [B] 5..15
    i_id: Array       # [B, L] item ids
    supply_w: Array   # [B, L] supplying warehouse (1% remote in spec)
    qty: Array        # [B, L] 1..10
    ts: Array         # [B] logical entry timestamp


class PaymentBatch(NamedTuple):
    w: Array       # [B]
    d: Array       # [B]
    c: Array       # [B]
    amount: Array  # [B]


class OrderStatusBatch(NamedTuple):
    """Order-Status (TPC-C §2.6): customer's most recent order + its lines."""

    w: Array  # [B]
    d: Array  # [B]
    c: Array  # [B]


class StockLevelBatch(NamedTuple):
    """Stock-Level (TPC-C §2.8): distinct recently-ordered items whose home
    stock sits below a threshold."""

    w: Array          # [B]
    d: Array          # [B]
    threshold: Array  # [B] int32 (spec: 10..20)


def generate_neworder(rng: np.random.Generator, scale: TPCCScale, batch: int,
                      remote_frac: float = 0.01,
                      w_lo: int = 0, w_hi: int | None = None,
                      ts0: int = 0, item_skew: float = 0.0) -> NewOrderBatch:
    """Random New-Order inputs for home warehouses in [w_lo, w_hi).

    ``item_skew`` > 0 draws item ids from the Zipfian access profile
    (item_popularity: id == popularity rank) instead of uniformly — the
    contended-workload knob the sparse hot-set escrow layout is built for.
    ``item_skew=0`` (default) keeps the seed's exact uniform stream.
    """
    w_hi = scale.n_warehouses if w_hi is None else w_hi
    L = scale.max_lines
    w = rng.integers(w_lo, w_hi, batch).astype(np.int32)
    n_lines = rng.integers(5, L + 1, batch).astype(np.int32)
    if item_skew > 0:
        cdf = np.cumsum(item_popularity(scale.n_items, item_skew))
        i_id = np.searchsorted(cdf, rng.random((batch, L))).astype(np.int32)
        i_id = np.minimum(i_id, scale.n_items - 1)
    else:
        i_id = rng.integers(0, scale.n_items, (batch, L)).astype(np.int32)
    remote = rng.random((batch, L)) < remote_frac
    other = rng.integers(0, scale.n_warehouses, (batch, L)).astype(np.int32)
    supply = np.where(remote, other, w[:, None]).astype(np.int32)
    return NewOrderBatch(
        w=jnp.asarray(w),
        d=jnp.asarray(rng.integers(0, scale.districts, batch).astype(np.int32)),
        c=jnp.asarray(rng.integers(0, scale.customers, batch).astype(np.int32)),
        n_lines=jnp.asarray(n_lines),
        i_id=jnp.asarray(i_id),
        supply_w=jnp.asarray(supply),
        qty=jnp.asarray(rng.integers(1, 11, (batch, L)).astype(np.int32)),
        ts=jnp.asarray((ts0 + np.arange(batch)).astype(np.int32)),
    )


def generate_payment(rng: np.random.Generator, scale: TPCCScale, batch: int,
                     w_lo: int = 0, w_hi: int | None = None) -> PaymentBatch:
    w_hi = scale.n_warehouses if w_hi is None else w_hi
    return PaymentBatch(
        w=jnp.asarray(rng.integers(w_lo, w_hi, batch).astype(np.int32)),
        d=jnp.asarray(rng.integers(0, scale.districts, batch).astype(np.int32)),
        c=jnp.asarray(rng.integers(0, scale.customers, batch).astype(np.int32)),
        amount=jnp.asarray(rng.uniform(1.0, 5000.0, batch).astype(np.float32)),
    )


def generate_order_status(rng: np.random.Generator, scale: TPCCScale,
                          batch: int, w_lo: int = 0,
                          w_hi: int | None = None) -> OrderStatusBatch:
    w_hi = scale.n_warehouses if w_hi is None else w_hi
    return OrderStatusBatch(
        w=jnp.asarray(rng.integers(w_lo, w_hi, batch).astype(np.int32)),
        d=jnp.asarray(rng.integers(0, scale.districts, batch).astype(np.int32)),
        c=jnp.asarray(rng.integers(0, scale.customers, batch).astype(np.int32)),
    )


def generate_stock_level(rng: np.random.Generator, scale: TPCCScale,
                         batch: int, w_lo: int = 0,
                         w_hi: int | None = None) -> StockLevelBatch:
    w_hi = scale.n_warehouses if w_hi is None else w_hi
    return StockLevelBatch(
        w=jnp.asarray(rng.integers(w_lo, w_hi, batch).astype(np.int32)),
        d=jnp.asarray(rng.integers(0, scale.districts, batch).astype(np.int32)),
        threshold=jnp.asarray(rng.integers(10, 21, batch).astype(np.int32)),
    )


def order_status_input_specs(batch: int) -> OrderStatusBatch:
    f = jax.ShapeDtypeStruct
    return OrderStatusBatch(w=f((batch,), jnp.int32), d=f((batch,), jnp.int32),
                            c=f((batch,), jnp.int32))


def stock_level_input_specs(batch: int) -> StockLevelBatch:
    f = jax.ShapeDtypeStruct
    return StockLevelBatch(w=f((batch,), jnp.int32), d=f((batch,), jnp.int32),
                           threshold=f((batch,), jnp.int32))


def neworder_input_specs(scale: TPCCScale, batch: int) -> NewOrderBatch:
    L = scale.max_lines
    f = jax.ShapeDtypeStruct
    return NewOrderBatch(
        w=f((batch,), jnp.int32), d=f((batch,), jnp.int32),
        c=f((batch,), jnp.int32), n_lines=f((batch,), jnp.int32),
        i_id=f((batch, L), jnp.int32), supply_w=f((batch, L), jnp.int32),
        qty=f((batch, L), jnp.int32), ts=f((batch,), jnp.int32),
    )


# ---------------------------------------------------------------------------
# Remote stock deltas (the RAMP-style asynchronous write set)
# ---------------------------------------------------------------------------


class StockDelta(NamedTuple):
    """COO outbox of stock updates destined for non-local warehouses.

    Fixed capacity R = B * L; ``valid`` marks live entries. Merging outboxes
    is delta-CRDT style: each entry is consumed exactly once by its owner
    during anti-entropy (engine.anti_entropy), after which the outbox clears.
    """

    dst_w: Array  # [R] int32 destination warehouse
    i_id: Array   # [R] int32
    qty: Array    # [R] int32 ordered quantity
    valid: Array  # [R] bool


def _empty_delta(capacity: int) -> StockDelta:
    return StockDelta(jnp.zeros((capacity,), jnp.int32),
                      jnp.zeros((capacity,), jnp.int32),
                      jnp.zeros((capacity,), jnp.int32),
                      jnp.zeros((capacity,), jnp.bool_))


def apply_stock_updates(state: TPCCState, w_idx: Array, i_idx: Array,
                        qty: Array, mask: Array, remote: Array,
                        restock: bool = True) -> TPCCState:
    """Owner-side stock effect (TPC-C §2.4.2.2): decrement with restock.

    S_QUANTITY' = q - qty if q - qty >= 10 else q - qty + 91 ; S_YTD += qty;
    S_ORDER_CNT += 1 ; S_REMOTE_CNT += remote. All via scatter-add/compare —
    commutative counters except S_QUANTITY, whose restock rule is applied by
    the owning shard at merge time (order-dependent but unconstrained by the
    twelve consistency criteria; see DESIGN.md §9).

    ``restock=False`` is the strict-stock regime (``s_quantity >= 0``
    enforced by escrow admission upstream, apply_neworder_escrow): the
    decrement lands as-is, with no +91 re-up. Safety there comes from the
    escrow shares — the sum of admitted spends can never exceed the stock
    the shares partition.
    """
    w_idx = jnp.where(mask, w_idx, 0)
    i_idx = jnp.where(mask, i_idx, 0)
    qty_m = jnp.where(mask, qty, 0)
    one_m = jnp.where(mask, 1, 0).astype(jnp.int32)
    rem_m = jnp.where(mask & remote, 1, 0).astype(jnp.int32)

    s_ytd = state.s_ytd.at[w_idx, i_idx].add(qty_m.astype(state.s_ytd.dtype))
    s_ocnt = state.s_order_cnt.at[w_idx, i_idx].add(one_m)
    s_rcnt = state.s_remote_cnt.at[w_idx, i_idx].add(rem_m)
    # decrement-then-restock: apply total decrement, then add 91 while < 10.
    s_q = state.s_quantity.at[w_idx, i_idx].add(-qty_m)
    if restock:
        deficit = jnp.maximum(0, jnp.ceil((10 - s_q) / 91.0)).astype(jnp.int32)
        s_q = jnp.where(s_q < 10, s_q + deficit * 91, s_q)
    return state._replace(s_quantity=s_q, s_ytd=s_ytd,
                          s_order_cnt=s_ocnt, s_remote_cnt=s_rcnt)


# ---------------------------------------------------------------------------
# New-Order (the paper's measured transaction)
# ---------------------------------------------------------------------------


class FlatLines(NamedTuple):
    """Flattened ``[B*L]`` order-line views shared by admission, effects and
    the outbox build — the mask-INDEPENDENT parts, computed once per batch.
    Call sites apply their own masks (validity, commit, locality) on top."""

    w: Array       # [N] int32 supply warehouse (GLOBAL id)
    i: Array       # [N] int32 item id
    q: Array       # [N] int32 quantity
    local: Array   # [N] bool — supply warehouse within [w_lo, w_hi)
    remote: Array  # [N] bool — supply warehouse != the order's home w


def flatten_order_lines(batch: NewOrderBatch, w_lo: int,
                        w_hi: int) -> FlatLines:
    """THE order-line flattening (one definition: apply_neworder, the
    committed-effects tail, and the fused megastep all consume it, so the
    locality/remoteness conventions can never drift apart)."""
    flat_w = batch.supply_w.reshape(-1)
    return FlatLines(
        w=flat_w, i=batch.i_id.reshape(-1), q=batch.qty.reshape(-1),
        local=(flat_w >= w_lo) & (flat_w < w_hi),
        remote=(batch.supply_w != batch.w[:, None]).reshape(-1))


def apply_neworder(state: TPCCState, batch: NewOrderBatch,
                   scale: TPCCScale,
                   w_lo: int = 0, w_hi: int | None = None,
                   replica: Array | int = 0, num_replicas: int = 1
                   ) -> tuple[TPCCState, StockDelta, Array]:
    """Vectorized coordination-avoiding New-Order.

    Effects (paper §6.2):
      * sequential o_id per district — a *batched* atomic increment-and-get:
        each transaction's o_id = d_next_o_id + its rank among same-district
        transactions in the batch (prefix counting), then the counter advances
        by the per-district count. This is the only synchronization and it is
        local to the district's owning shard.
      * ORDER / NEW-ORDER / ORDER-LINE inserts — foreign-key inserts,
        I-confluent (Table 2), installed locally.
      * STOCK updates — local supply lines applied in place; remote lines
        (supply_w outside [w_lo, w_hi)) are emitted as a StockDelta outbox for
        asynchronous anti-entropy (RAMP-style; no synchronous coordination).
      * RAMP stamping — the whole write set shares one replica-namespaced
        timestamp ``ts * num_replicas + replica`` recorded on the ORDER row
        (the commit record, whose o_ol_cnt doubles as the sibling-key
        metadata) and on every order-line; line visibility (ol_vis) is
        installed atomically here and may be *staged* by txn/ramp.py to model
        in-flight commit propagation across partitions.

    Returns (new_state, remote outbox, per-txn total amounts).
    """
    w_hi = scale.n_warehouses if w_hi is None else w_hi
    ramp_ts = batch.ts * num_replicas + replica                    # [B]
    B, L = batch.i_id.shape
    D, OC = scale.districts, scale.order_capacity
    wl = batch.w - w_lo  # shard-local home-warehouse index

    # ---- sequential ID assignment (batched increment-and-get) -------------
    key = batch.w * D + batch.d                                    # [B]
    same = (key[None, :] == key[:, None])                          # [B, B]
    lower = jnp.tril(jnp.ones((B, B), jnp.bool_), k=-1)
    rank = (same & lower).sum(axis=1).astype(jnp.int32)            # [B]
    o_id = state.d_next_o_id[wl, batch.d] + rank              # [B]
    per_txn_one = jnp.ones((B,), jnp.int32)
    d_next = state.d_next_o_id.at[wl, batch.d].add(per_txn_one)

    slot = o_id % OC                                               # [B]

    # ---- ORDER + NEW-ORDER inserts ----------------------------------------
    line_idx = jnp.arange(L)[None, :]
    line_valid = line_idx < batch.n_lines[:, None]                 # [B, L]

    o_valid = state.o_valid.at[wl, batch.d, slot].set(True)
    o_c_id = state.o_c_id.at[wl, batch.d, slot].set(batch.c)
    o_ol_cnt = state.o_ol_cnt.at[wl, batch.d, slot].set(batch.n_lines)
    o_carrier = state.o_carrier.at[wl, batch.d, slot].set(-1)
    o_entry_d = state.o_entry_d.at[wl, batch.d, slot].set(batch.ts)
    no_valid = state.no_valid.at[wl, batch.d, slot].set(True)
    o_ts = state.o_ts.at[wl, batch.d, slot].set(ramp_ts)

    # ---- ORDER-LINE inserts ------------------------------------------------
    price = state.i_price[wl[:, None], batch.i_id]            # [B, L]
    amount = price * batch.qty.astype(price.dtype)
    amount = jnp.where(line_valid, amount, 0.0)

    # each insert writes the order's WHOLE line row (invalid tail included,
    # with defaults), so index only [B] rows and let the L dim be the scatter
    # update window — 15x fewer scatter rows than per-element [B, L] indices,
    # and this is the hot-path cost on CPU/TPU (scatters are row loops)
    ol_valid = state.ol_valid.at[wl, batch.d, slot].set(line_valid)
    ol_i_id = state.ol_i_id.at[wl, batch.d, slot].set(batch.i_id)
    ol_supply = state.ol_supply_w.at[wl, batch.d, slot].set(batch.supply_w)
    ol_qty = state.ol_qty.at[wl, batch.d, slot].set(
        jnp.where(line_valid, batch.qty, 0))
    ol_amount = state.ol_amount.at[wl, batch.d, slot].set(amount)
    ol_ts = state.ol_ts.at[wl, batch.d, slot].set(
        jnp.where(line_valid, ramp_ts[:, None], -1))
    ol_vis = state.ol_vis.at[wl, batch.d, slot].set(line_valid)

    state = state._replace(
        d_next_o_id=d_next, o_valid=o_valid, o_c_id=o_c_id,
        o_ol_cnt=o_ol_cnt, o_carrier=o_carrier, o_entry_d=o_entry_d,
        no_valid=no_valid, ol_valid=ol_valid, ol_i_id=ol_i_id,
        ol_supply_w=ol_supply, ol_qty=ol_qty, ol_amount=ol_amount,
        o_ts=o_ts, ol_ts=ol_ts, ol_vis=ol_vis)

    # ---- STOCK: local now, remote via outbox -------------------------------
    flat = flatten_order_lines(batch, w_lo, w_hi)
    flat_valid = line_valid.reshape(-1)

    state = apply_stock_updates(state, flat.w - w_lo, flat.i, flat.q,
                                flat_valid & flat.local, flat.remote)

    # outbox: entries stay in batch-position order, valid-masked — the drain
    # applies by mask, so the old argsort compaction was pure overhead on the
    # hot path
    rmask = flat_valid & ~flat.local
    delta = StockDelta(dst_w=jnp.where(rmask, flat.w, 0),
                       i_id=jnp.where(rmask, flat.i, 0),
                       qty=jnp.where(rmask, flat.q, 0),
                       valid=rmask)

    # ---- total amount (returned to the client) -----------------------------
    disc = state.c_discount[wl, batch.d, batch.c]
    tax = state.w_tax[wl] + state.d_tax[wl, batch.d]
    total = amount.sum(axis=1) * (1.0 - disc) * (1.0 + tax)
    return state, delta, total


# ---------------------------------------------------------------------------
# Escrowed strict-stock New-Order (paper §8: amortizing coordination)
# ---------------------------------------------------------------------------


def escrow_share_for(s_quantity, replica, num_replicas: int, alive=None):
    """Replica ``replica``'s share of every stock cell — THE partition
    formula (one definition: init, refresh, and the fused drain+refresh all
    call it, so the audit's conservation law can never desynchronize).

    ``q // R`` each, with the remainder going to the lowest replica slots;
    ``replica`` may be a traced scalar (shard index) or a broadcastable
    array of slot ids.

    ``alive`` (optional ``[R]`` bool/int mask) is the liveness-aware
    reclaim: only the replicas marked live partition the headroom — a dead
    replica's slot gets ZERO (its unspent headroom, already folded back
    into the post-drain stock, lands with the survivors) and the remainder
    goes to the lowest LIVE ranks. With every replica live this reduces
    bit-exactly to the unmasked formula (rank == replica id), and the sum
    over slots equals ``q`` exactly either way — capacity is moved, never
    manufactured.
    """
    q = jnp.asarray(s_quantity, jnp.int32)
    r = jnp.asarray(replica, jnp.int32)
    if alive is None:
        return q // num_replicas + (r < q % num_replicas).astype(jnp.int32)
    alive_i = jnp.asarray(alive, jnp.int32)                   # [R]
    n_live = jnp.maximum(alive_i.sum(), 1)
    rank = jnp.take(jnp.cumsum(alive_i) - 1, r)               # live rank
    share = q // n_live + (rank < q % n_live).astype(jnp.int32)
    return jnp.take(alive_i, r) * share


def make_escrow_shares(s_quantity, num_replicas: int):
    """Partition every stock cell's quantity into per-replica shares.

    Returns an int32 ``[R, W, I]`` array with ``shares.sum(0) == s_quantity``
    exactly, so the global ``s_quantity >= 0`` invariant holds by
    construction while each replica spends only from its own slot.
    """
    q = jnp.asarray(s_quantity, jnp.int32)
    slots = jnp.arange(num_replicas, dtype=jnp.int32).reshape(
        (num_replicas,) + (1,) * q.ndim)
    return escrow_share_for(q, slots, num_replicas)


# ---------------------------------------------------------------------------
# THE escrow-admission core, shared by the dense and sparse layouts: both
# reduce their state to ONE availability vector (avail0 [A]) and per-line
# cell slots (slot [B, L]), then pick an execution strategy for the same
# FCFS semantics. Admission is first-come-first-served in batch order: a
# transaction commits iff every valid line's quantity — including duplicate-
# cell demand within the transaction — fits the remaining availability;
# otherwise the whole transaction aborts with no effects.
# ---------------------------------------------------------------------------


ADMISSION_MODES = ("auto", "scan", "kernel")

# no-autotune threshold: below this per-shard batch the B-step scan is
# cheaper than the gate's pre-pass + kernel launch; above it the gate
# collapses the sequential depth to the contended handful. The live "auto"
# decision is the measured resolve_admission_cutover below; this constant is
# what "auto" uses when autotuning is disabled.
AUTO_KERNEL_MIN_BATCH = 64

# one flip disables the measured cut-over everywhere (tests pin it off to
# keep strategy choice deterministic across hosts)
ADMISSION_AUTOTUNE = True

_CUTOVER_CACHE: dict[tuple, str] = {}


def resolve_admission_cutover(batch: int, n_lines: int = 15, *,
                              cells: int = 4096, trials: int = 3) -> str:
    """One-shot BACKEND-DERIVED admission cut-over (ROADMAP item 2): time
    the scan vs the gate+kernel pipeline once per (backend, batch shape) on
    a synthetic admission problem of that shape, memoize the winner.

    Replaces the CPU-tuned ``AUTO_KERNEL_MIN_BATCH`` constant as the live
    "auto" decision: the crossover moves with the backend (a TPU's kernel
    launch amortizes differently than interpret-mode CPU), so it is measured
    where the program will actually run, at first use, and cached for the
    process lifetime. Timing happens OUTSIDE any trace in the sense that the
    probe arrays are fresh concrete values — calling the two jitted probes
    while an outer trace is live is legal and leaves no residue in the outer
    program (the resolved mode is a static Python string, exactly like the
    constant it replaces). A strategy that fails to compile or run raises:
    the probe never hides a broken kernel behind the other strategy.
    """
    key = (jax.default_backend(), batch, n_lines)
    hit = _CUTOVER_CACHE.get(key)
    if hit is not None:
        return hit
    import time

    rng = np.random.default_rng(0)
    # the TPC-C regime the engine actually runs: plentiful stock under a
    # skewed access profile, contention the exception (the CALM gate's
    # design point) — probing a starved problem instead would measure a
    # workload the hot path never sees and flatter the scan
    avail0 = jnp.asarray(rng.integers(100, 500, size=cells), jnp.int32)
    slot = jnp.asarray(
        (cells * rng.power(4.0, size=(batch, n_lines))).astype(np.int64)
        % cells, jnp.int32)
    qty = jnp.asarray(rng.integers(1, 10, size=(batch, n_lines)), jnp.int32)
    lv = jnp.asarray(rng.random((batch, n_lines)) < 0.8)
    # small batches run in tens of microseconds — repeat enough that the
    # measured wall is timer-resolvable, not scheduler noise
    reps = max(trials, 1024 // max(batch, 1))
    walls = {}
    for mode in ("scan", "kernel"):
        probe = jax.jit(lambda a, s, q, v, mode=mode: admit_fcfs(
            a, s, q, v, admission=mode))
        jax.block_until_ready(probe(avail0, slot, qty, lv))  # compile
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(probe(avail0, slot, qty, lv))
        walls[mode] = time.perf_counter() - t0
    choice = min(walls, key=walls.get)
    _CUTOVER_CACHE[key] = choice
    return choice


def resolve_admission(admission: str, batch: int,
                      n_lines: int | None = None) -> str:
    """Resolve the ``admission=`` knob to a concrete strategy for a batch
    shape (static at trace time): "auto" asks the memoized backend autotune
    (:func:`resolve_admission_cutover`) when the line width is known and
    autotuning is on, else falls back to the ``AUTO_KERNEL_MIN_BATCH``
    constant."""
    if admission not in ADMISSION_MODES:
        raise ValueError(f"unknown admission {admission!r}; "
                         f"choose from {ADMISSION_MODES}")
    if admission == "auto":
        if n_lines is not None and ADMISSION_AUTOTUNE:
            return resolve_admission_cutover(batch, n_lines)
        return "kernel" if batch >= AUTO_KERNEL_MIN_BATCH else "scan"
    return admission


EFFECTS_MODES = ("scan", "fused")


def resolve_effects(effects: str) -> str:
    """Validate the ``effects=`` knob: "scan" is the definitional per-phase
    dispatch path; "fused" routes the strict-stock New-Order through the
    one-kernel megastep (kernels/txn_megastep.py), bit-identically."""
    if effects not in EFFECTS_MODES:
        raise ValueError(f"unknown effects {effects!r}; "
                         f"choose from {EFFECTS_MODES}")
    return effects


def admit_fcfs(avail0: Array, slot: Array, qty: Array, line_valid: Array,
               admission: str = "scan") -> tuple[Array, Array]:
    """FCFS admission of a batch against an availability vector.

    avail0: [A] int32 headroom per cell; slot/qty/line_valid: [B, L] with
    ``slot`` identifying cells (equal slot == same cell). Returns
    (committed [B] bool, avail [A] after all admitted reservations) —
    bit-identical across strategies:

    * ``"scan"`` — the sequential baseline: a B-step ``lax.scan``; every
      step gathers/scatters the whole-``avail`` vector and rebuilds an
      ``[L, L]`` duplicate-demand matrix. Definitional; kept bit-exact.
    * ``"kernel"`` — the two-level pipeline: the contention gate
      (kernels/escrow_admit.contention_gate) commits every transaction
      whose cells' TOTAL batch demand fits headroom — admission is monotone
      there, so order cannot matter — and only the residual transactions
      (the oversubscribed handful at TPC-C skew) run FCFS, inside a Pallas
      kernel with ``avail`` resident in VMEM (a dynamic trip count: the
      sequential depth is the residual count, not B).
    * ``"auto"`` — :func:`resolve_admission` picks per batch shape (the
      memoized backend autotune, or the constant threshold as fallback).
    """
    admission = resolve_admission(admission, slot.shape[0], slot.shape[1])
    if admission == "kernel":
        from repro.kernels.ops import escrow_admit
        return escrow_admit(avail0, slot, qty, line_valid)

    L = slot.shape[1]
    dup_lower = jnp.tril(jnp.ones((L, L), jnp.bool_), k=-1)

    def _admit(avail, xs):
        slot_l, q_l, lv = xs                                       # [L] each
        # demand already placed on the same cell by EARLIER lines of this
        # same transaction (duplicate items in one order)
        same = slot_l[None, :] == slot_l[:, None]
        prior = jnp.where(same & dup_lower & lv[None, :],
                          q_l[None, :], 0).sum(axis=1)
        have = avail[slot_l]
        ok = jnp.all(jnp.where(lv, prior + q_l <= have, True))
        avail = avail.at[slot_l].add(jnp.where(lv & ok, -q_l, 0))
        return avail, ok

    avail, committed = jax.lax.scan(_admit, avail0,
                                    (slot, qty, line_valid))
    return committed, avail


def apply_neworder_escrow(state: TPCCState, shares: Array, spent: Array,
                          batch: NewOrderBatch, scale: TPCCScale,
                          w_lo: int = 0, w_hi: int | None = None,
                          replica: Array | int = 0, num_replicas: int = 1,
                          admission: str = "scan", effects: str = "scan"
                          ) -> tuple[TPCCState, Array, StockDelta, Array, Array]:
    """Strict-stock New-Order: ``s_quantity >= 0`` with NO restock.

    The non-confluent part of the transaction — decrements against the
    stock floor — is admitted against this replica's escrow share
    (``shares``/``spent`` are this replica's ``[W, I]`` slot of the global
    EscrowCounter; W is the GLOBAL warehouse count, since any replica may
    sell any warehouse's items). Admission is first-come-first-served in
    batch (timestamp) order via an inner scan: a transaction commits iff
    every valid line's quantity — including duplicate-cell demand within the
    same transaction — fits in the remaining share; otherwise the WHOLE
    transaction aborts with no effects (TPC-C's atomic rollback).

    Committed effects mirror apply_neworder, except:
      * stock decrements never restock (apply_stock_updates restock=False);
      * sequential o_ids are assigned densely over the COMMITTED
        transactions only (aborts leave no gaps — criterion 3.3.2.3);
      * aborted transactions' scatters are dropped (indices redirected out
        of range under mode="drop").

    Everything stays replica-local: zero collectives — the only coordination
    in the escrow regime is the amortized share refresh (engine/executor).

    ``admission`` selects the :func:`admit_fcfs` strategy ("scan" is the
    bit-exact sequential baseline; "kernel"/"auto" route through the
    contention gate + Pallas FCFS kernel with identical results).
    ``effects`` selects the committed-effects strategy ("scan" is the
    per-phase dispatch baseline; "fused" runs admission + effects + RAMP
    stamping through the one-kernel megastep, bit-identically).

    Returns (state, spent', remote outbox, totals, committed mask [B]).
    """
    w_hi = scale.n_warehouses if w_hi is None else w_hi
    ramp_ts = batch.ts * num_replicas + replica                    # [B]
    B, L = batch.i_id.shape
    I = scale.n_items

    line_idx = jnp.arange(L)[None, :]
    line_valid = line_idx < batch.n_lines[:, None]                 # [B, L]

    # ---- escrow admission through the shared core --------------------------
    # the dense layout's availability vector is this replica's remaining
    # share of every (warehouse, item) cell, flattened w-major
    avail0 = (shares - spent).reshape(-1)
    slot = batch.supply_w * I + batch.i_id                         # [B, L]

    if resolve_effects(effects) == "fused":
        state, avail, delta, total, committed = _neworder_fused_effects(
            state, batch, scale, avail0, slot, line_valid, ramp_ts,
            w_lo, w_hi, admission)
        return state, shares - avail.reshape(shares.shape), delta, total, \
            committed

    committed, avail = admit_fcfs(avail0, slot, batch.qty, line_valid,
                                  admission)
    spent = shares - avail.reshape(shares.shape)

    state, delta, total = _neworder_committed_effects(
        state, batch, scale, committed, line_valid, ramp_ts, w_lo, w_hi)
    return state, spent, delta, total, committed


def _neworder_committed_effects(state: TPCCState, batch: NewOrderBatch,
                                scale: TPCCScale, committed: Array,
                                line_valid: Array, ramp_ts: Array,
                                w_lo: int, w_hi: int
                                ) -> tuple[TPCCState, StockDelta, Array]:
    """Committed-only strict-stock New-Order effects, shared by the dense and
    sparse escrow admission paths (one definition keeps the two layouts'
    committed semantics bit-identical): dense o_ids over committed txns,
    dropped scatters for aborts, restock-free stock decrements, remote lines
    emitted as the outbox."""
    B, L = batch.i_id.shape
    D, OC = scale.districts, scale.order_capacity
    wl = batch.w - w_lo
    line_ok = line_valid & committed[:, None]                      # [B, L]

    # ---- sequential ID assignment over COMMITTED txns only -----------------
    key = batch.w * D + batch.d                                    # [B]
    same = (key[None, :] == key[:, None])                          # [B, B]
    lower = jnp.tril(jnp.ones((B, B), jnp.bool_), k=-1)
    rank = (same & lower & committed[None, :]).sum(axis=1).astype(jnp.int32)
    o_id = state.d_next_o_id[wl, batch.d] + rank                   # [B]
    d_next = state.d_next_o_id.at[wl, batch.d].add(
        committed.astype(jnp.int32))

    # aborted txns scatter out of range and are dropped
    slot = jnp.where(committed, o_id % OC, OC)                     # [B]

    # ---- ORDER + NEW-ORDER inserts (committed only) ------------------------
    at = lambda arr: arr.at[wl, batch.d, slot]
    o_valid = at(state.o_valid).set(True, mode="drop")
    o_c_id = at(state.o_c_id).set(batch.c, mode="drop")
    o_ol_cnt = at(state.o_ol_cnt).set(batch.n_lines, mode="drop")
    o_carrier = at(state.o_carrier).set(-1, mode="drop")
    o_entry_d = at(state.o_entry_d).set(batch.ts, mode="drop")
    no_valid = at(state.no_valid).set(True, mode="drop")
    o_ts = at(state.o_ts).set(ramp_ts, mode="drop")

    # ---- ORDER-LINE inserts (whole row per order, L as scatter window) -----
    price = state.i_price[wl[:, None], batch.i_id]                 # [B, L]
    amount = price * batch.qty.astype(price.dtype)
    amount = jnp.where(line_valid, amount, 0.0)

    ol_valid = at(state.ol_valid).set(line_valid, mode="drop")
    ol_i_id = at(state.ol_i_id).set(batch.i_id, mode="drop")
    ol_supply = at(state.ol_supply_w).set(batch.supply_w, mode="drop")
    ol_qty = at(state.ol_qty).set(
        jnp.where(line_valid, batch.qty, 0), mode="drop")
    ol_amount = at(state.ol_amount).set(amount, mode="drop")
    ol_ts = at(state.ol_ts).set(
        jnp.where(line_valid, ramp_ts[:, None], -1), mode="drop")
    ol_vis = at(state.ol_vis).set(line_valid, mode="drop")

    state = state._replace(
        d_next_o_id=d_next, o_valid=o_valid, o_c_id=o_c_id,
        o_ol_cnt=o_ol_cnt, o_carrier=o_carrier, o_entry_d=o_entry_d,
        no_valid=no_valid, ol_valid=ol_valid, ol_i_id=ol_i_id,
        ol_supply_w=ol_supply, ol_qty=ol_qty, ol_amount=ol_amount,
        o_ts=o_ts, ol_ts=ol_ts, ol_vis=ol_vis)

    # ---- STOCK: admitted spends — local applied now, remote via outbox -----
    flat = flatten_order_lines(batch, w_lo, w_hi)
    flat_ok = line_ok.reshape(-1)

    state = apply_stock_updates(state, flat.w - w_lo, flat.i, flat.q,
                                flat_ok & flat.local, flat.remote,
                                restock=False)

    rmask = flat_ok & ~flat.local
    delta = StockDelta(dst_w=jnp.where(rmask, flat.w, 0),
                       i_id=jnp.where(rmask, flat.i, 0),
                       qty=jnp.where(rmask, flat.q, 0),
                       valid=rmask)

    # ---- total amount (0 for aborted txns) ---------------------------------
    disc = state.c_discount[wl, batch.d, batch.c]
    tax = state.w_tax[wl] + state.d_tax[wl, batch.d]
    total = amount.sum(axis=1) * (1.0 - disc) * (1.0 + tax)
    total = jnp.where(committed, total, 0.0)
    return state, delta, total


def _neworder_fused_effects(state: TPCCState, batch: NewOrderBatch,
                            scale: TPCCScale, avail0: Array, slot: Array,
                            line_valid: Array, ramp_ts: Array,
                            w_lo: int, w_hi: int, admission: str
                            ) -> tuple[TPCCState, Array, StockDelta, Array,
                                       Array]:
    """The FUSED strict-stock New-Order: admission + committed effects +
    RAMP stamping through one megastep (kernels/txn_megastep.py) instead of
    the per-phase dispatch sequence — shared by the dense and sparse escrow
    layouts exactly like ``_neworder_committed_effects`` (the two entry
    points reduce their state to the same (avail0, slot) admission problem
    and hand it here).

    The megastep returns effect PRODUCTS over the hot tiles (admission
    verdicts + settled avail, committed per-district ranks and counts, the
    three stock slabs, the RAMP stamps); this function lands them:

      * district counters advance by ONE dense vector add (the [B, B] rank
        matrix and the d_next scatter-add of the scan path are gone);
      * the stock tables take four dense [Wl, I] vector adds (the scan
        path's four masked whole-table scatter passes are gone);
      * the order/order-line row inserts keep their existing one-scatter-
        per-row path — they are append-mostly table writes, not hot-tile
        state, and the kernel would gain nothing by owning them.

    Bit-exactness with the scan path holds phase by phase: admission is the
    shared FCFS core; rank/d_count/slabs are integer sums in identical
    batch order; s_ytd's f32 adds have integer addends far below 2**24,
    where any association is exact; stamps/amounts/totals are the scan
    path's elementwise formulas on identical inputs.

    Returns (state, settled avail, outbox, totals, committed) — the caller
    derives its layout's spent from ``avail``.
    """
    B, L = batch.i_id.shape
    D, OC, I = scale.districts, scale.order_capacity, scale.n_items
    Wl = state.s_quantity.shape[0]
    wl = batch.w - w_lo

    flat = flatten_order_lines(batch, w_lo, w_hi)
    is_local = flat.local.reshape(B, L)
    remote_line = flat.remote.reshape(B, L)
    local_line = line_valid & is_local
    key_local = (wl * D + batch.d).astype(jnp.int32)               # [B]
    cell_local = jnp.where(
        local_line, (batch.supply_w - w_lo) * I + batch.i_id, 0
    ).astype(jnp.int32)                                            # [B, L]
    price = state.i_price[wl[:, None], batch.i_id]                 # [B, L]
    n_keys, n_cells = Wl * D, Wl * I

    if resolve_admission(admission, B, L) == "kernel":
        from repro.kernels.ops import txn_megastep
        out = txn_megastep(avail0, slot, batch.qty, line_valid, key_local,
                           cell_local, local_line, remote_line, ramp_ts,
                           price, n_keys=n_keys, n_cells=n_cells)
    else:
        # scan admission + the vectorized effect-product lowering (the
        # megastep's products are strategy-independent, so the fused/scan
        # choice composes freely with the admission choice)
        from repro.kernels.txn_megastep import (MegastepOut,
                                                megastep_effect_products)
        committed, avail = admit_fcfs(avail0, slot, batch.qty, line_valid,
                                      "scan")
        out = MegastepOut(committed, avail, *megastep_effect_products(
            committed, batch.qty, line_valid, key_local, cell_local,
            local_line, remote_line, ramp_ts, price, n_keys=n_keys,
            n_cells=n_cells))

    committed = out.committed
    line_ok = line_valid & committed[:, None]

    # ---- district counters: one gather + one dense vector add --------------
    o_id = state.d_next_o_id[wl, batch.d] + out.rank               # [B]
    d_next = state.d_next_o_id + out.d_count.reshape(Wl, D)

    # aborted txns scatter out of range and are dropped (scan path verbatim)
    slot_o = jnp.where(committed, o_id % OC, OC)                   # [B]
    at = lambda arr: arr.at[wl, batch.d, slot_o]
    o_valid = at(state.o_valid).set(True, mode="drop")
    o_c_id = at(state.o_c_id).set(batch.c, mode="drop")
    o_ol_cnt = at(state.o_ol_cnt).set(batch.n_lines, mode="drop")
    o_carrier = at(state.o_carrier).set(-1, mode="drop")
    o_entry_d = at(state.o_entry_d).set(batch.ts, mode="drop")
    no_valid = at(state.no_valid).set(True, mode="drop")
    o_ts = at(state.o_ts).set(ramp_ts, mode="drop")

    ol_valid = at(state.ol_valid).set(line_valid, mode="drop")
    ol_i_id = at(state.ol_i_id).set(batch.i_id, mode="drop")
    ol_supply = at(state.ol_supply_w).set(batch.supply_w, mode="drop")
    ol_qty = at(state.ol_qty).set(
        jnp.where(line_valid, batch.qty, 0), mode="drop")
    ol_amount = at(state.ol_amount).set(out.amount, mode="drop")
    ol_ts = at(state.ol_ts).set(out.ol_ts, mode="drop")
    ol_vis = at(state.ol_vis).set(line_valid, mode="drop")

    # ---- stock tables: four dense vector adds from the slabs ---------------
    dec = out.stock_dec.reshape(Wl, I)
    s_q = state.s_quantity - dec
    s_ytd = state.s_ytd + dec.astype(state.s_ytd.dtype)
    s_ocnt = state.s_order_cnt + out.stock_cnt.reshape(Wl, I)
    s_rcnt = state.s_remote_cnt + out.stock_rcnt.reshape(Wl, I)

    rmask = line_ok.reshape(-1) & ~flat.local
    delta = StockDelta(dst_w=jnp.where(rmask, flat.w, 0),
                       i_id=jnp.where(rmask, flat.i, 0),
                       qty=jnp.where(rmask, flat.q, 0),
                       valid=rmask)

    disc = state.c_discount[wl, batch.d, batch.c]
    tax = state.w_tax[wl] + state.d_tax[wl, batch.d]
    total = out.amount.sum(axis=1) * (1.0 - disc) * (1.0 + tax)
    total = jnp.where(committed, total, 0.0)

    state = state._replace(
        d_next_o_id=d_next, o_valid=o_valid, o_c_id=o_c_id,
        o_ol_cnt=o_ol_cnt, o_carrier=o_carrier, o_entry_d=o_entry_d,
        no_valid=no_valid, ol_valid=ol_valid, ol_i_id=ol_i_id,
        ol_supply_w=ol_supply, ol_qty=ol_qty, ol_amount=ol_amount,
        o_ts=o_ts, ol_ts=ol_ts, ol_vis=ol_vis,
        s_quantity=s_q, s_ytd=s_ytd, s_order_cnt=s_ocnt,
        s_remote_cnt=s_rcnt)
    return state, out.avail, delta, total, committed


# ---------------------------------------------------------------------------
# Sparse hot-set escrow (two-tier layout): escrow only the contended cells,
# owner-route the cold tail. The access profile is Zipfian over item ids
# (id == popularity rank), so the hot set is analytic: the top ``hot_items``
# ids of every warehouse. See core/lattice.py HotSetEscrow.
# ---------------------------------------------------------------------------


def item_popularity(n_items: int, theta: float) -> np.ndarray:
    """Zipfian access profile over the item catalog: item id == popularity
    rank, p(i) ∝ 1 / (i + 1)**theta. ``theta=0`` is uniform."""
    p = 1.0 / np.power(np.arange(1, n_items + 1, dtype=np.float64), theta)
    return p / p.sum()


def default_hot_items(scale: TPCCScale) -> int:
    """Default hot-set width: the top 1% of the item catalog (>= 1). At spec
    scale (100k items) that is 1000 items x every warehouse — the cells that
    soak up the bulk of a Zipfian stream while cutting the escrow residency
    by ~67x (see escrow_layout_bytes)."""
    return max(1, scale.n_items // 100)


def select_hot_cells(scale: TPCCScale, hot_items: int) -> np.ndarray:
    """The top-K contended (warehouse, item) cells as sorted int32 keys
    ``w * n_items + i``. Item popularity is Zipfian by id and uniform over
    warehouses, so the top cells are exactly the ``hot_items`` most popular
    item ids crossed with every warehouse; key order (w-major, ascending
    item) is already sorted."""
    hot_items = min(max(1, hot_items), scale.n_items)
    w = np.arange(scale.n_warehouses, dtype=np.int64)[:, None]
    i = np.arange(hot_items, dtype=np.int64)[None, :]
    keys = (w * scale.n_items + i).reshape(-1)
    assert keys[-1] <= np.iinfo(np.int32).max, "cell key overflows int32"
    return keys.astype(np.int32)


def escrow_layout_bytes(scale: TPCCScale, hot_items: int) -> dict:
    """Per-device escrow residency of the two layouts (int32 everywhere).

    dense  — the replica's ``[1, W, I]`` slice of shares + spent;
    sparse — the replicated ``[K]`` key table + the replica's ``[1, K]``
             slice of shares + spent, K = W * hot_items.
    """
    dense = 2 * scale.n_warehouses * scale.n_items * 4
    K = scale.n_warehouses * min(max(1, hot_items), scale.n_items)
    sparse = 3 * K * 4
    return {"dense_bytes_per_device": dense,
            "sparse_bytes_per_device": sparse,
            "hot_cells": K,
            "reduction_vs_dense": dense / sparse}


def sparse_admission_problem(s_quantity: Array, hot_keys: Array,
                             hot_headroom: Array, supply_w: Array,
                             i_id: Array, n_items: int, w_lo: int,
                             w_hi: int) -> tuple[Array, Array]:
    """The two-tier layout's admission problem: ONE availability vector and
    per-line slots unify the three admission domains, so the FCFS core pays
    a single gather + a single scatter per sequential step (the dense
    layout pays two gathers + one scatter):

      [0, K)            hot-cell headroom  (shares - spent, this replica)
      [K, K + Wl*I)     cold LOCAL stock   (the shard's own s_quantity at
                        call entry; the admission's reservations ARE the
                        owner's serialization of its cold cells)
      [K + Wl*I]        sentinel for cold REMOTE lines — effectively
                        infinite: they are admitted optimistically and
                        settled strictly at their owner during the drain

    Shared by apply_neworder_escrow_sparse and the ``escrow_admission``
    benchmark (which measures admission over exactly this construction).
    """
    K = hot_keys.shape[0]
    Wl = s_quantity.shape[0]
    cell_key = supply_w * n_items + i_id                           # [B, L]
    pos, is_hot = hot_position(hot_keys, cell_key)                 # [B, L]
    is_local = (supply_w >= w_lo) & (supply_w < w_hi)              # [B, L]
    wl_line = jnp.where(is_local, supply_w - w_lo, 0)              # [B, L]

    BIG = jnp.asarray(jnp.iinfo(jnp.int32).max // 2, jnp.int32)
    avail0 = jnp.concatenate([
        hot_headroom, s_quantity.reshape(-1), BIG[None]])
    slot = jnp.where(is_hot, pos,
                     jnp.where(is_local, K + wl_line * n_items + i_id,
                               K + Wl * n_items)).astype(jnp.int32)
    return avail0, slot


def apply_neworder_escrow_sparse(state: TPCCState, hot_keys: Array,
                                 hot_shares: Array, hot_spent: Array,
                                 batch: NewOrderBatch, scale: TPCCScale,
                                 w_lo: int = 0, w_hi: int | None = None,
                                 replica: Array | int = 0,
                                 num_replicas: int = 1,
                                 admission: str = "scan",
                                 effects: str = "scan"
                                 ) -> tuple[TPCCState, Array, StockDelta,
                                            Array, Array]:
    """Strict-stock New-Order over the TWO-TIER escrow layout.

    Admission splits per line by hot-set membership (one ``searchsorted``
    against the sorted ``hot_keys`` table):

      * HOT cell — ``try_spend`` against this replica's ``[K]`` share slot
        (``hot_shares``/``hot_spent``), exactly the dense regime's rule but
        indexed through the hot table;
      * COLD cell, locally owned — strict check-and-reserve against this
        shard's own ``s_quantity`` (the shard IS the cell's owner, and the
        admission scan serializes it, so no shares are needed);
      * COLD cell, remote — admitted optimistically and routed to the
        owning shard through the outbox; the owner serializes all spends on
        its cold cells and applies the entry strictly at drain time
        (apply_stock_updates_strict_tiered), REJECTING it if the cell lacks
        stock. The floor invariant therefore never breaks, at the price of
        best-effort fulfillment for the (rare: remote x cold) tail — the
        reject count is surfaced as MixStats.cold_rejects.

    Everything is replica-local: zero collectives. ``admission`` selects
    the :func:`admit_fcfs` strategy ("scan" baseline vs the contention
    gate + Pallas FCFS kernel, bit-identical); ``effects`` selects the
    committed-effects strategy ("scan" dispatch vs the one-kernel megastep,
    bit-identical). Returns
    (state, hot_spent', remote outbox, totals, committed mask [B]).
    """
    w_hi = scale.n_warehouses if w_hi is None else w_hi
    ramp_ts = batch.ts * num_replicas + replica                    # [B]
    B, L = batch.i_id.shape
    I = scale.n_items
    K = hot_keys.shape[0]

    line_idx = jnp.arange(L)[None, :]
    line_valid = line_idx < batch.n_lines[:, None]                 # [B, L]

    avail0, slot = sparse_admission_problem(
        state.s_quantity, hot_keys, hot_shares - hot_spent,
        batch.supply_w, batch.i_id, I, w_lo, w_hi)

    if resolve_effects(effects) == "fused":
        state, avail, delta, total, committed = _neworder_fused_effects(
            state, batch, scale, avail0, slot, line_valid, ramp_ts,
            w_lo, w_hi, admission)
        return state, hot_shares - avail[:K], delta, total, committed

    # slots identify cells (hot < K <= cold local < sentinel; remote-cold
    # collisions on the sentinel only over-count against BIG, which cannot
    # matter), so the shared FCFS core sees one uniform admission domain
    committed, avail = admit_fcfs(avail0, slot, batch.qty, line_valid,
                                  admission)
    hot_spent = hot_shares - avail[:K]

    state, delta, total = _neworder_committed_effects(
        state, batch, scale, committed, line_valid, ramp_ts, w_lo, w_hi)
    return state, hot_spent, delta, total, committed


def apply_stock_updates_strict_tiered(state: TPCCState, hot_keys: Array,
                                      dst_w: Array, i_idx: Array, qty: Array,
                                      mask: Array, remote: Array,
                                      n_items: int, w_lo: int = 0
                                      ) -> tuple[TPCCState, Array]:
    """Owner-side strict apply of drained outbox entries, split by tier.

    HOT entries were admitted against escrow shares upstream, so they apply
    unconditionally (the shares guarantee capacity). COLD entries were
    admitted optimistically by remote senders; the owner — the only writer
    of its cold cells — enforces the floor here with per-cell ALL-OR-NOTHING
    admission over the drain window: a cell's queued entries land iff their
    total fits its stock, else the whole cell's window is rejected.

    All-or-nothing (instead of FCFS within the window) is intentionally
    conservative: admission depends only on the per-cell TOTAL, which is
    invariant to entry order — exactly what keeps the fused ring drain and
    the dispatch driver's concatenated-outbox drain bit-identical (the
    windows contain the same entries in different orders).

    ``dst_w`` is the GLOBAL destination warehouse (the hot-key space);
    ``w_lo`` rebases it onto this owner's local state rows. Returns
    (state, rejected-entry count).
    """
    key = dst_w * n_items + i_idx                     # global cell key
    _, is_hot = hot_position(hot_keys, key)
    w_idx = jnp.where(mask, dst_w - w_lo, 0)
    i_idx = jnp.where(mask, i_idx, 0)
    cold = mask & ~is_hot
    demand = jnp.zeros_like(state.s_quantity).at[
        jnp.where(cold, w_idx, 0), jnp.where(cold, i_idx, 0)].add(
        jnp.where(cold, qty, 0))
    fits = demand <= state.s_quantity
    admit_cold = cold & fits[w_idx, i_idx]
    rejects = (cold & ~admit_cold).sum().astype(jnp.int32)
    state = apply_stock_updates(state, w_idx, i_idx, qty,
                                (mask & is_hot) | admit_cold, remote,
                                restock=False)
    return state, rejects


class RetryState(NamedTuple):
    """Bounded on-device retry ring for owner-rejected remote-cold entries.

    Fixed capacity C per owner shard; ``valid`` marks live lanes. Every
    entry is, by construction, a cold cell OWNED by the holding shard (it
    was rejected by this owner's own all-or-nothing drain), so re-presenting
    it needs no routing and no collectives — the ring lives and dies inside
    the owner's drain program. ``tries`` counts drain windows the entry has
    already lost; at ``retry_max`` it surfaces as a FINAL reject instead of
    silently dropping on the first miss.
    """

    dst_w: Array     # [C] int32 GLOBAL destination warehouse
    i_id: Array      # [C] int32
    qty: Array       # [C] int32
    tries: Array     # [C] int32 drain windows already lost
    valid: Array     # [C] bool
    reserved: Array  # [C] bool owner-granted reservation (stock already
    #                  debited; completes — frees the lane and counts as
    #                  applied — at the next drain window)


def empty_retry(capacity: int) -> RetryState:
    return RetryState(jnp.zeros((capacity,), jnp.int32),
                      jnp.zeros((capacity,), jnp.int32),
                      jnp.zeros((capacity,), jnp.int32),
                      jnp.zeros((capacity,), jnp.int32),
                      jnp.zeros((capacity,), jnp.bool_),
                      jnp.zeros((capacity,), jnp.bool_))


def apply_stock_updates_strict_tiered_retry(
        state: TPCCState, hot_keys: Array, dst_w: Array, i_idx: Array,
        qty: Array, mask: Array, remote: Array, retry: RetryState,
        n_items: int, w_lo: int = 0, retry_max: Array | int = 0,
        reserve: Array | int = 0
        ) -> tuple[TPCCState, RetryState, Array]:
    """Strict tiered drain with a bounded retry ring (two passes).

    Pass 1 re-presents the ring (entries this owner rejected in earlier
    windows — all cold, all owned here) with per-cell GREEDY-BY-AGE
    admission: entries sort by (cell, tries desc, qty asc) and admit while
    their cell's cumulative demand fits the current stock. Greedy (not the
    window's all-or-nothing) is what makes retrying meaningful at all —
    cold stock is monotone non-increasing under the strict regime, so a
    cohort whose TOTAL was rejected once would be rejected forever; the
    prefix rule instead lands whatever subset fits, oldest first. The
    priority is a pure function of the entry (cell, tries, qty), so
    admission depends only on the ring's entry MULTISET — lane order,
    which differs between the fused ring and the dispatch driver's
    windows, cannot change the outcome (entries tied on all three keys
    are interchangeable).

    Pass 2 is bit-identical to :func:`apply_stock_updates_strict_tiered`
    over the fresh window, run against the post-pass-1 stock (per-cell
    all-or-nothing on the window total, order-invariant as before).

    Losers requeue: a ring entry that has now lost ``retry_max`` windows
    becomes a FINAL reject; a fresh cold reject enqueues with tries=0 (or
    final-rejects immediately when ``retry_max`` — a traced scalar, no
    recompiles per value — is 0). The survivor set compacts ring-first into
    the fixed [C] ring; overflow beyond C surfaces as final rejects rather
    than silent drops. With ``retry_max=0`` and an empty ring this is
    bit-exactly the non-retry drain (pass 1's masked scatter-adds of zero
    are bitwise identity). Returns (state, retry', final-reject count).

    ``reserve`` (traced scalar, default 0 = off) bounds tail starvation
    under sustained contention with an owner-granted RESERVATION
    round-trip. Pass 1's prefix rule head-of-line blocks: the cumulative
    demand includes rejected entries, so a small line sorted behind a big
    never-fitting blocker at the same cell is rejected every window even
    while raw stock covers it — greedy-by-age alone final-rejects it. With
    ``reserve`` on, an entry that has now lost its ``retry_max - 1``'th
    window instead bids for the window's LEFTOVER stock (smallest-first
    within the cell, free of the blocker's prefix): a grant debits stock
    immediately (the reservation IS the admission — never-oversell and
    stock conservation are preserved at every instant) and the entry rides
    the ring one more window flagged ``reserved``; the next drain's pass 0
    completes it (frees the lane — it then counts as applied, not final).
    A failed bid requeues normally and final-rejects on its next loss.
    With ``reserve=0`` every reservation mask is statically false and the
    drain is bit-identical to the reservation-free path.
    """
    retry_max = jnp.asarray(retry_max, jnp.int32)
    reserve = jnp.asarray(reserve, jnp.int32)
    C = retry.valid.shape[0]

    # -- pass 0: complete reservations granted last window (the round-trip's
    # second leg). Their stock was debited at grant time, so completion is
    # pure bookkeeping: the lane frees and the entry leaves the ring without
    # touching the final-reject count — the exact ledger counts it applied.
    done = retry.valid & retry.reserved & (reserve > 0)
    retry = retry._replace(valid=retry.valid & ~done,
                           reserved=jnp.zeros_like(retry.reserved))

    # -- pass 1: ring entries (cold, owned here, remote to their senders) --
    r_valid = retry.valid
    r_w = jnp.where(r_valid, retry.dst_w - w_lo, 0)
    r_i = jnp.where(r_valid, retry.i_id, 0)
    r_cell = jnp.where(r_valid, retry.dst_w * n_items + retry.i_id,
                       jnp.iinfo(jnp.int32).max)          # invalid sort last
    order = jnp.lexsort((retry.qty, -retry.tries, r_cell))
    c_s = r_cell[order]
    q_s = jnp.where(r_valid, retry.qty, 0)[order]
    v_s = r_valid[order]
    csum = jnp.cumsum(q_s)
    seg_start = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), c_s[1:] != c_s[:-1]])
    # cumulative demand within each cell segment (incl. self): csum minus
    # the running total at the segment's start — recoverable by cummax
    # because csum is non-decreasing
    prefix = csum - jax.lax.cummax(jnp.where(seg_start, csum - q_s, 0))
    stock_s = state.s_quantity[
        jnp.where(v_s, retry.dst_w[order] - w_lo, 0),
        jnp.where(v_s, retry.i_id[order], 0)]
    r_admit = jnp.zeros_like(r_valid).at[order].set(
        v_s & (prefix <= stock_s))
    state = apply_stock_updates(state, r_w, r_i, retry.qty, r_admit,
                                jnp.ones_like(r_admit), restock=False)
    r_rej = r_valid & ~r_admit
    r_tries = retry.tries + 1
    r_final = r_rej & (r_tries >= retry_max)
    r_requeue = r_rej & (r_tries < retry_max)

    # -- pass 2: fresh window vs post-pass-1 stock (same formulas as the
    # non-retry drain) --
    key = dst_w * n_items + i_idx
    _, is_hot = hot_position(hot_keys, key)
    w_idx = jnp.where(mask, dst_w - w_lo, 0)
    i_l = jnp.where(mask, i_idx, 0)
    cold = mask & ~is_hot
    demand = jnp.zeros_like(state.s_quantity).at[
        jnp.where(cold, w_idx, 0), jnp.where(cold, i_l, 0)].add(
        jnp.where(cold, qty, 0))
    admit_cold = cold & (demand <= state.s_quantity)[w_idx, i_l]
    state = apply_stock_updates(state, w_idx, i_l, qty,
                                (mask & is_hot) | admit_cold, remote,
                                restock=False)
    f_rej = cold & ~admit_cold
    f_requeue = f_rej & (retry_max > 0)
    f_final = f_rej & (retry_max <= 0)

    # -- pass 3 (reservations): last-chance ring losers bid for the window's
    # leftover stock. Candidates are entries whose NEXT loss would be final;
    # the bid is a per-cell cumulative prefix over candidates only, sorted
    # smallest-qty-first — the big blocker that starves them in pass 1 can
    # never fit here either, but it no longer poisons the prefix. Grants
    # debit stock NOW and mark the lane reserved; pass 0 of the next drain
    # completes them (the owner-granted round-trip).
    last_chance = r_requeue & (r_tries >= retry_max - 1) & (reserve > 0)
    g_cell = jnp.where(last_chance, retry.dst_w * n_items + retry.i_id,
                       jnp.iinfo(jnp.int32).max)
    g_order = jnp.lexsort((retry.qty, g_cell))
    gq_s = jnp.where(last_chance, retry.qty, 0)[g_order]
    gc_s = g_cell[g_order]
    gv_s = last_chance[g_order]
    gcsum = jnp.cumsum(gq_s)
    g_seg = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), gc_s[1:] != gc_s[:-1]])
    g_prefix = gcsum - jax.lax.cummax(jnp.where(g_seg, gcsum - gq_s, 0))
    g_stock = state.s_quantity[
        jnp.where(gv_s, retry.dst_w[g_order] - w_lo, 0),
        jnp.where(gv_s, retry.i_id[g_order], 0)]
    granted = jnp.zeros_like(last_chance).at[g_order].set(
        gv_s & (g_prefix <= g_stock))
    state = apply_stock_updates(state, r_w, r_i, retry.qty, granted,
                                jnp.ones_like(granted), restock=False)

    # -- compact survivors ring-first into the fixed [C] ring --
    cand_keep = jnp.concatenate([r_requeue, f_requeue])
    cand_w = jnp.concatenate([retry.dst_w, dst_w])
    cand_i = jnp.concatenate([retry.i_id, i_idx])
    cand_q = jnp.concatenate([retry.qty, qty])
    cand_t = jnp.concatenate([r_tries, jnp.zeros_like(dst_w)])
    cand_r = jnp.concatenate([granted, jnp.zeros_like(mask)])
    rank = jnp.cumsum(cand_keep.astype(jnp.int32)) - 1
    keep = cand_keep & (rank < C)
    overflow = cand_keep & (rank >= C)
    # scatter through a [C+1] buffer: every dropped entry lands on the dump
    # slot C (discarded by the slice), kept entries on their unique rank
    slot = jnp.where(keep, rank, C)

    def _pack(vals, fill_dtype):
        buf = jnp.zeros((C + 1,), fill_dtype)
        return buf.at[slot].set(
            jnp.where(keep, vals, 0).astype(fill_dtype))[:C]

    new_retry = RetryState(_pack(cand_w, jnp.int32), _pack(cand_i, jnp.int32),
                           _pack(cand_q, jnp.int32), _pack(cand_t, jnp.int32),
                           _pack(keep, jnp.bool_), _pack(cand_r, jnp.bool_))
    final = (r_final.sum() + f_final.sum() + overflow.sum()).astype(jnp.int32)
    return state, new_retry, final


# ---------------------------------------------------------------------------
# Payment & Delivery ("largely uninteresting" per §6.2 — but implemented)
# ---------------------------------------------------------------------------


def apply_payment(state: TPCCState, batch: PaymentBatch,
                  w_lo: int = 0) -> TPCCState:
    """Payment: commutative counter increments (I-confluent, Table 2)."""
    w = batch.w - w_lo
    amt = batch.amount
    return state._replace(
        w_ytd=state.w_ytd.at[w].add(amt),
        d_ytd=state.d_ytd.at[w, batch.d].add(amt),
        h_amount_sum=state.h_amount_sum.at[w, batch.d].add(amt),
        c_balance=state.c_balance.at[w, batch.d, batch.c].add(-amt),
        c_ytd_payment=state.c_ytd_payment.at[w, batch.d, batch.c].add(amt),
        c_payment_cnt=state.c_payment_cnt.at[w, batch.d, batch.c].add(1),
    )


def apply_delivery(state: TPCCState, carrier_id: Array, ts: Array) -> TPCCState:
    """Deliver the oldest undelivered order in every district (single-
    partition, as the spec permits and the paper notes)."""
    W, D, OC = state.no_valid.shape
    # oldest = valid NEW-ORDER slot with the smallest o_entry_d
    key = jnp.where(state.no_valid, state.o_entry_d, jnp.iinfo(jnp.int32).max)
    slot = jnp.argmin(key, axis=2)                       # [W, D]
    has = state.no_valid.any(axis=2)                     # [W, D]

    wI = jnp.arange(W)[:, None].repeat(D, 1)
    dI = jnp.arange(D)[None, :].repeat(W, 0)

    cust = state.o_c_id[wI, dI, slot]                    # [W, D]
    # read side goes through the RAMP prepared layer (ol_valid + matching
    # stamp), never the possibly-lagging visible layer: the credited amount
    # must cover the *complete* write set even mid-propagation (txn/ramp.py).
    line_ok = (state.ol_valid[wI, dI, slot]
               & (state.ol_ts[wI, dI, slot]
                  == state.o_ts[wI, dI, slot][..., None]))
    lines_amt = jnp.where(line_ok, state.ol_amount[wI, dI, slot], 0.0)
    amt = lines_amt.sum(-1) * has                        # [W, D]

    no_valid = state.no_valid.at[wI, dI, slot].set(
        jnp.where(has, False, state.no_valid[wI, dI, slot]))
    o_carrier = state.o_carrier.at[wI, dI, slot].set(
        jnp.where(has, carrier_id, state.o_carrier[wI, dI, slot]))
    delivered = state.ol_delivered.at[wI, dI, slot].set(
        jnp.where(has[..., None], state.ol_valid[wI, dI, slot],
                  state.ol_delivered[wI, dI, slot]))

    c_balance = state.c_balance.at[wI, dI, cust].add(amt)
    c_del_sum = state.c_delivered_sum.at[wI, dI, cust].add(amt)
    c_del_cnt = state.c_delivery_cnt.at[wI, dI, cust].add(has.astype(jnp.int32))
    return state._replace(no_valid=no_valid, o_carrier=o_carrier,
                          ol_delivered=delivered, c_balance=c_balance,
                          c_delivered_sum=c_del_sum, c_delivery_cnt=c_del_cnt)


# ---------------------------------------------------------------------------
# The twelve consistency criteria (TPC-C §3.3.2.1-12), executable
# ---------------------------------------------------------------------------


def check_consistency(state: TPCCState, atol: float = 1e-2) -> dict[int, bool]:
    """Evaluate all twelve criteria on a (converged) state."""
    s = jax.device_get(state)
    out = {}
    # 1: W_YTD = sum(D_YTD)
    out[1] = bool(np.allclose(s.w_ytd, s.d_ytd.sum(-1), atol=atol))
    # 2: D_NEXT_O_ID - 1 = max(O_ID) = max(NO_O_ID)  [dense ids from 0 here:
    #    d_next_o_id == count(valid orders); max slot entry consistent]
    order_count = s.o_valid.sum(-1)
    out[2] = bool(np.array_equal(s.d_next_o_id, order_count))
    # 3: NEW-ORDER ids are a contiguous range (no gaps)
    #    ring-encoded: undelivered orders are the most recent ones
    no_count = s.no_valid.sum(-1)
    delivered = (s.o_valid & ~s.no_valid).sum(-1)
    out[3] = bool(np.array_equal(no_count + delivered, order_count))
    # 4: sum(O_OL_CNT) = count(ORDER-LINE)
    out[4] = bool(np.array_equal(
        np.where(s.o_valid, s.o_ol_cnt, 0).sum(-1), s.ol_valid.sum((-1, -2))))
    # 5: carrier is null iff a NEW-ORDER row exists
    out[5] = bool(np.all((s.o_carrier < 0) == s.no_valid | ~s.o_valid))
    # 6: per-order O_OL_CNT equals its line count
    out[6] = bool(np.all(np.where(s.o_valid, s.o_ol_cnt, 0)
                         == s.ol_valid.sum(-1)))
    # 7: OL_DELIVERY_D set iff the order was delivered
    deliv_order = s.o_valid & (s.o_carrier >= 0)
    out[7] = bool(np.all(s.ol_delivered ==
                         (s.ol_valid & deliv_order[..., None])))
    # 8: W_YTD = sum(H_AMOUNT) per warehouse
    out[8] = bool(np.allclose(s.w_ytd, s.h_amount_sum.sum(-1), atol=atol))
    # 9: D_YTD = sum(H_AMOUNT) per district
    out[9] = bool(np.allclose(s.d_ytd, s.h_amount_sum, atol=atol))
    # 10: C_BALANCE = sum(delivered OL_AMOUNT) - sum(H_AMOUNT)
    out[10] = bool(np.allclose(s.c_balance,
                               s.c_delivered_sum - s.c_ytd_payment, atol=atol))
    # 11: orders minus new-orders = delivered orders
    out[11] = bool(np.array_equal(order_count - no_count, delivered))
    # 12: C_BALANCE + C_YTD_PAYMENT = delivered order-line sum
    out[12] = bool(np.allclose(s.c_balance + s.c_ytd_payment,
                               s.c_delivered_sum, atol=atol))
    return out


def tpcc_invariants() -> list[tuple[int, Invariant, bool]]:
    """The twelve criteria as analyzer objects with the paper's grouping:

      * 3.3.2.[4-7, 11]  — foreign-key style          -> I-confluent
      * 3.3.2.[2-3]      — sequential ID assignment   -> NOT I-confluent
      * 3.3.2.[1, 8-10, 12] — materialized counters   -> I-confluent

    Returns (criterion number, invariant, expected confluent?).
    """
    fk = InvariantKind.FOREIGN_KEY
    mv = InvariantKind.MATERIALIZED_VIEW
    seq = InvariantKind.AUTO_INCREMENT
    rows = [
        (1, Invariant("w_ytd_sums_d_ytd", mv, "warehouse.w_ytd",
                      params={"source": "district.d_ytd"}), True),
        (2, Invariant("d_next_o_id_sequential", seq, "district.d_next_o_id"), False),
        (3, Invariant("no_o_id_contiguous", seq, "new_order.o_id"), False),
        (4, Invariant("ol_count_matches_o_ol_cnt", fk, "order_line.o_id",
                      params={"references": "order.o_id"}), True),
        (5, Invariant("carrier_null_iff_new_order", fk, "order.carrier",
                      params={"references": "new_order.o_id"}), True),
        (6, Invariant("o_ol_cnt_per_order", fk, "order.o_ol_cnt",
                      params={"references": "order_line.o_id"}), True),
        (7, Invariant("ol_delivery_iff_carrier", fk, "order_line.delivery_d",
                      params={"references": "order.carrier"}), True),
        (8, Invariant("w_ytd_sums_history", mv, "warehouse.w_ytd",
                      params={"source": "history.h_amount"}), True),
        (9, Invariant("d_ytd_sums_history", mv, "district.d_ytd",
                      params={"source": "history.h_amount"}), True),
        (10, Invariant("c_balance_materialized", mv, "customer.c_balance",
                       params={"source": "order_line.ol_amount"}), True),
        (11, Invariant("order_minus_neworder_delivered", fk, "order.o_id",
                       params={"references": "new_order.o_id"}), True),
        (12, Invariant("c_balance_plus_ytd", mv, "customer.c_balance",
                       params={"source": "order_line.ol_amount"}), True),
    ]
    return rows


# ---------------------------------------------------------------------------
# TPC-C as a planner state tree: every table/column the engine mutates,
# declared as (lattice, ops, invariants). core/planner.plan() over these
# specs is what SELECTS the engine's execution regime per state element —
# the paper's "coordinate only where the analyzer proves non-confluence".
# ---------------------------------------------------------------------------


STOCK_INVARIANTS = ("restock", "strict", "serial")


def tpcc_state_specs(stock_invariant: str = "restock"):
    """TPC-C state elements as core.planner.StateSpec declarations.

    ``stock_invariant`` is the *application's schema declaration* for
    STOCK.S_QUANTITY (the knob is what invariant the app demands — the
    execution regime is then derived by the analyzer, never hand-picked):

      "restock" — the spec's §2.4.2.2 rule (+91 re-up keeps the quantity in
          one residue window): no floor invariant to violate, decrements are
          plain commutative counter updates -> COORDINATION_FREE (merge
          path, asynchronous anti-entropy).
      "strict"  — a hard ``s_quantity >= 0`` floor with no restock:
          GREATER_THAN x decrement is NOT I-confluent (Table 2), but the
          paper's §8 escrow method applies -> ESCROW (per-replica shares,
          local try_spend, amortized refresh as the only collective).
      "serial"  — an opaque/custom "exact serializable stock" demand the
          analyzer has no local rule for -> COORDINATION_REQUIRED (the 2PC
          engine is the fallback; see engine.plan_engine).
    """
    from repro.core.planner import StateSpec
    from repro.core.txn import Op, OpKind

    def inv(name, kind, target, params=None):
        return Invariant(name, kind, target, None, params or {})

    fk = InvariantKind.FOREIGN_KEY
    mv = InvariantKind.MATERIALIZED_VIEW

    if stock_invariant == "restock":
        stock_spec = StateSpec(
            "stock.s_quantity", "pncounter",
            (Op(OpKind.DECREMENT, "stock.s_quantity"),
             Op(OpKind.INCREMENT, "stock.s_quantity")),
            (),
            merge_every=0,
            note="spec restock rule: decrement-then-+91 keeps one residue "
                 "window; no floor invariant -> commutative counter")
    elif stock_invariant == "strict":
        stock_spec = StateSpec(
            "stock.s_quantity", "escrow",
            (Op(OpKind.DECREMENT, "stock.s_quantity"),),
            (inv("s_quantity_nonneg", InvariantKind.GREATER_THAN,
                 "stock.s_quantity", {"threshold": -1}),),
            merge_every=0,
            note="hard s_quantity >= 0 floor, no restock: concurrent "
                 "decrements can jointly cross it -> escrow shares (§8)")
    elif stock_invariant == "serial":
        stock_spec = StateSpec(
            "stock.s_quantity", "lww",
            (Op(OpKind.DECREMENT, "stock.s_quantity"),),
            (inv("s_quantity_serializable", InvariantKind.CUSTOM,
                 "stock.s_quantity",
                 {"semantics": "globally ordered exact stock"}),),
            merge_every=1,
            note="opaque serializability demand: no local rule -> "
                 "synchronous coordination (2PC fallback)")
    else:
        raise ValueError(f"unknown stock_invariant {stock_invariant!r}; "
                         f"choose from {STOCK_INVARIANTS}")

    return [
        StateSpec(
            "warehouse.w_ytd", "sum",
            (Op(OpKind.INCREMENT, "warehouse.w_ytd"),),
            (inv("w_ytd_sums_history", mv, "warehouse.w_ytd",
                 {"source": "history.h_amount"}),),
            merge_every=0,
            note="criteria 1/8: materialized payment sums, commutative"),
        StateSpec(
            "district.d_ytd", "sum",
            (Op(OpKind.INCREMENT, "district.d_ytd"),),
            (inv("d_ytd_sums_history", mv, "district.d_ytd",
                 {"source": "history.h_amount"}),),
            merge_every=0),
        StateSpec(
            "district.d_next_o_id", "max",
            (Op(OpKind.INSERT, "district.d_next_o_id"),),
            (inv("d_next_o_id_sequential", InvariantKind.AUTO_INCREMENT,
                 "district.d_next_o_id"),),
            merge_every=0,
            note="criteria 2/3: dense sequential o_ids — deferred "
                 "commit-time assignment by the district's owning shard "
                 "(the batched increment-and-get in apply_neworder)"),
        StateSpec(
            "order.rows", "versioned",
            (Op(OpKind.INSERT, "order.rows"),),
            (inv("ol_count_matches_o_ol_cnt", fk, "order_line.o_id",
                 {"references": "order.rows"}),),
            merge_every=0,
            note="criteria 4/6: FK inserts, I-confluent"),
        StateSpec(
            "new_order.rows", "2pset",
            (Op(OpKind.INSERT, "new_order.rows"),
             Op(OpKind.CASCADING_DELETE, "new_order.rows")),
            (inv("carrier_null_iff_new_order", fk, "order.carrier",
                 {"references": "new_order.rows"}),),
            merge_every=0,
            note="criteria 5/11: Delivery's removal is a cascading "
                 "tombstone, monotone under merge"),
        StateSpec(
            "order_line.rows", "versioned",
            (Op(OpKind.INSERT, "order_line.rows"),),
            (inv("ol_delivery_iff_carrier", fk, "order_line.rows",
                 {"references": "order.carrier"}),),
            merge_every=0),
        StateSpec(
            "customer.c_balance", "sum",
            (Op(OpKind.INCREMENT, "customer.c_balance"),
             Op(OpKind.DECREMENT, "customer.c_balance")),
            (inv("c_balance_materialized", mv, "customer.c_balance",
                 {"source": "order_line.ol_amount"}),),
            merge_every=0,
            note="criteria 10/12: balance is a materialized view of "
                 "payments and delivered order-lines"),
        StateSpec(
            "stock.s_ytd", "sum",
            (Op(OpKind.INCREMENT, "stock.s_ytd"),),
            (inv("s_ytd_materialized", mv, "stock.s_ytd",
                 {"source": "order_line.ol_qty"}),),
            merge_every=0),
        stock_spec,
    ]
