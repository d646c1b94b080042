"""Consolidated closed-loop drivers for every TPC-C regime and mode.

PR-3 grew three near-duplicate drivers inside engine.py (``run_closed_loop``
/ ``run_mixed_loop`` / ``run_escrow_loop``, each with fused / dispatch /
legacy variants). This module replaces them with ONE core, :func:`run_loop`,
holding the shared pending-outbox / stats / audit skeleton:

* **stream generation** — a single source draws the identical
  home-partitioned batch streams for every execution mode (the fused ↔
  dispatch bit-exactness contract rests on this), including the Zipfian
  ``item_skew`` knob the sparse hot-set escrow layout is built around;
* **execution** — ``fused=True`` (default) runs the chunked-scan megastep
  executor (txn/executor.py); ``fused=False`` is the per-batch dispatch
  baseline; ``legacy=True`` additionally restores the seed's host behavior
  (per-batch ``int(...)`` stat reads forcing a device sync every batch, and
  per-outbox anti-entropy calls in the merge regime — the escrow regime
  always drains a whole window in one batched call, because the sparse cold
  tier's per-cell all-or-nothing admission is defined over the window);
* **regimes** — the engine's plan-selected regime picks the hot path: merge
  (restock New-Order + asynchronous anti-entropy) or escrow (strict
  New-Order against the hot-set/dense shares, strict drains, amortized
  share refresh). 2PC lives in twopc.py, coordination is never a driver
  concern here;
* **refresh cadence** — fixed every ``refresh_every`` drains (the PR-3
  behavior and the config fallback), or ADAPTIVE via
  ``refresh_abort_rate``: refresh as soon as any replica's escrow abort
  rate since the last refresh crosses the threshold. Adaptive mode reads
  one small counter per drain window (a host sync the fixed cadence does
  not pay) — feedback control is inherently a host decision;
* **audit** — ``audit=True`` snapshots the initial stock and runs the
  independent consistency oracle (txn/audit.py) on the final state.

``run_closed_loop`` / ``run_mixed_loop`` / ``run_escrow_loop`` remain as
thin signature-compatible wrappers; engine.py lazily re-exports them.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.planner import CoordClass
from repro.obs import trace

from . import tpcc
from .tpcc import NewOrderBatch, StockDelta, TPCCState

Array = jax.Array


# ---------------------------------------------------------------------------
# Stats
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RunStats:
    committed: int = 0
    batches: int = 0
    anti_entropy_rounds: int = 0
    aborted: int = 0       # escrow regime: insufficient-share atomic aborts
    refreshes: int = 0     # escrow regime: amortized share-refresh rounds
    wall_seconds: float = 0.0

    @property
    def throughput(self) -> float:
        return self.committed / self.wall_seconds if self.wall_seconds else 0.0


@dataclasses.dataclass
class MixStats:
    """Closed-loop stats for the five-transaction mix."""

    neworders: int = 0
    payments: int = 0
    order_statuses: int = 0
    stock_levels: int = 0
    deliveries: int = 0
    anti_entropy_rounds: int = 0
    reads_found: int = 0
    fractures_observed: int = 0   # must stay 0: RAMP atomic visibility
    lines_repaired: int = 0       # 2nd-round (lookback) activity
    aborts: int = 0               # escrow regime: insufficient-share aborts
    refreshes: int = 0            # escrow regime: share-refresh rounds
    cold_rejects: int = 0         # sparse escrow: owner-rejected cold entries
    wall_seconds: float = 0.0

    @property
    def committed(self) -> int:
        return (self.neworders + self.payments + self.order_statuses
                + self.stock_levels + self.deliveries)

    @property
    def throughput(self) -> float:
        return self.committed / self.wall_seconds if self.wall_seconds else 0.0


def counters_to_stats(counters, *, anti_entropy_rounds: int,
                      wall_seconds: float, refreshes: int = 0,
                      cold_rejects: int = 0) -> MixStats:
    """One device_get over the executor's on-device counter pytree."""
    c = jax.device_get(counters)
    return MixStats(
        neworders=int(c.neworders.sum()),
        payments=int(c.payments.sum()),
        order_statuses=int(c.order_statuses.sum()),
        stock_levels=int(c.stock_levels.sum()),
        deliveries=int(c.deliveries.sum()),
        anti_entropy_rounds=anti_entropy_rounds,
        reads_found=int(c.reads_found.sum()),
        fractures_observed=int(c.fractures_observed.sum()),
        lines_repaired=int(c.lines_repaired.sum()),
        aborts=int(c.aborts.sum()),
        refreshes=refreshes,
        cold_rejects=cold_rejects,
        wall_seconds=wall_seconds)


# ---------------------------------------------------------------------------
# Stream generation (the single source of the stream layout)
# ---------------------------------------------------------------------------


def _concat_outboxes(pending: list[StockDelta]) -> StockDelta:
    """All queued outboxes as ONE StockDelta, applied in a single
    anti-entropy call (vs the seed's one jitted call per outbox).

    No longer on any driver path — the dispatch loop accumulates into a
    reused device-resident window buffer instead of re-concatenating a
    host-side pending list every drain (see :class:`_OutboxWindow`) —
    but kept importable (engine.py re-exports it) for external callers."""
    if len(pending) == 1:
        return pending[0]
    return jax.tree.map(lambda *xs: jnp.concatenate(xs), *pending)


# the window buffer's three device ops, jitted once (module-level cache) and
# donated so every drain window reuses ONE allocation instead of fresh
# concatenate buffers per drain
_window_put = jax.jit(
    lambda buf, delta, i: jax.tree.map(
        lambda b, v: jax.lax.dynamic_update_index_in_dim(b, v, i, 0),
        buf, delta),
    donate_argnums=0)
_window_flat = jax.jit(
    lambda buf: jax.tree.map(lambda x: x.reshape(-1), buf))
_window_clear = jax.jit(
    lambda buf: buf._replace(valid=jnp.zeros_like(buf.valid)),
    donate_argnums=0)


class _OutboxWindow:
    """Fixed-size ``[rows, R]`` on-device outbox accumulator for the
    dispatch drivers (the per-batch analog of the fused executor's
    OutboxRing): per-batch deltas are written into successive rows of one
    donated buffer, and each drain reads the SAME flattened shape —
    replacing the old host-side pending list whose re-concatenation
    allocated fresh buffers every window and compiled a second drain shape
    for the ragged tail (tail rows simply stay ``valid=False``)."""

    def __init__(self, delta: StockDelta, rows: int):
        self.rows = rows
        self._buf = jax.tree.map(
            lambda x: jnp.zeros((rows,) + x.shape, x.dtype), delta)
        self._n = 0

    def put(self, delta: StockDelta) -> None:
        self._buf = _window_put(self._buf, delta,
                                jnp.asarray(self._n, jnp.int32))
        self._n += 1

    def flat(self) -> StockDelta:
        """The window as one flattened StockDelta (row-major: identical
        entry order to concatenating the per-batch deltas)."""
        return _window_flat(self._buf)

    def clear(self) -> None:
        self._buf = _window_clear(self._buf)
        self._n = 0

    def __len__(self) -> int:
        return self._n


def _tree_copy(t):
    return jax.tree.map(lambda x: x.copy(), t)


def _neworder_batch(engine, rng: np.random.Generator, batch_per_shard: int,
                    remote_frac: float, ts0: int,
                    item_skew: float = 0.0) -> tuple[NewOrderBatch, int]:
    """One home-partitioned New-Order batch (shard s gets txns for its
    warehouse range); returns (batch, advanced ts0). The single source of
    the stream layout — the fused/dispatch bit-exactness contract rests on
    every driver drawing identical streams."""
    parts = []
    for s in range(engine.n_shards):
        parts.append(tpcc.generate_neworder(
            rng, engine.scale, batch_per_shard, remote_frac=remote_frac,
            w_lo=s * engine.w_per_shard,
            w_hi=(s + 1) * engine.w_per_shard, ts0=ts0,
            item_skew=item_skew))
        ts0 += batch_per_shard
    return jax.tree.map(lambda *xs: jnp.concatenate(xs), *parts), ts0


def generate_neworder_stream(engine, *, batch_per_shard: int,
                             n_batches: int, remote_frac: float,
                             rng: np.random.Generator, ts0: int = 0,
                             item_skew: float = 0.0) -> list[NewOrderBatch]:
    """Home-partitioned New-Order batches for a whole run."""
    batches = []
    for _ in range(n_batches):
        batch, ts0 = _neworder_batch(engine, rng, batch_per_shard,
                                     remote_frac, ts0, item_skew)
        batches.append(batch)
    return batches


def _home_partitioned(gen, rng, engine, per_shard: int, **kw):
    parts = [gen(rng, engine.scale, per_shard,
                 w_lo=s * engine.w_per_shard,
                 w_hi=(s + 1) * engine.w_per_shard, **kw)
             for s in range(engine.n_shards)]
    return jax.tree.map(lambda *xs: jnp.concatenate(xs), *parts)


def generate_mix_batches(engine, *, batch_per_shard: int,
                         n_batches: int, remote_frac: float = 0.01,
                         read_frac: float = 0.25, seed: int = 0,
                         item_skew: float = 0.0):
    """Pre-generate the five-transaction-mix batch streams (home-partitioned,
    one rng). Shared by the fused executor and the per-batch dispatch driver
    so both execute the identical transaction stream."""
    rng = np.random.default_rng(seed)
    per_shard_reads = max(1, int(batch_per_shard * read_frac))
    ts0 = 0
    no_batches, pay_batches, os_batches, sl_batches = [], [], [], []
    for _ in range(n_batches):
        batch, ts0 = _neworder_batch(engine, rng, batch_per_shard,
                                     remote_frac, ts0, item_skew)
        no_batches.append(batch)
        pay_batches.append(_home_partitioned(
            tpcc.generate_payment, rng, engine, batch_per_shard))
        os_batches.append(_home_partitioned(
            tpcc.generate_order_status, rng, engine, per_shard_reads))
        sl_batches.append(_home_partitioned(
            tpcc.generate_stock_level, rng, engine, per_shard_reads))
    return no_batches, pay_batches, os_batches, sl_batches


# ---------------------------------------------------------------------------
# Adaptive refresh controller (satellite: abort-rate-triggered refresh)
# ---------------------------------------------------------------------------


def _adaptive_refresh_due(aborts_since, txns_since, rate: float) -> bool:
    """Refresh iff ANY replica's escrow abort rate since the last refresh
    crossed ``rate``. Shared by the dispatch loop and the fused executor so
    both make identical decisions from identical counters."""
    ab = np.asarray(aborts_since, np.int64)
    tx = np.maximum(1, np.asarray(txns_since, np.int64))
    return bool((ab > rate * tx).any())


# ---------------------------------------------------------------------------
# THE consolidated closed-loop core
# ---------------------------------------------------------------------------


def run_loop(engine, state: TPCCState, esc=None, *,
             batch_per_shard: int, n_batches: int,
             remote_frac: float = 0.01, merge_every: int = 8,
             refresh_every: int = 1, refresh_abort_rate: float | None = None,
             read_frac: float = 0.25, item_skew: float = 0.0, seed: int = 0,
             payments: bool = False, reads: bool = False,
             deliveries: bool = False, fused: bool = True,
             legacy: bool = False, audit: bool = False, obs=None,
             retry_cap: int = 0, retry_max: int = 0, retry=None,
             alive=None, liveness=None, retry_reserve: int = 0,
             final_flush: bool = True,
             return_retry: bool = False,
             ) -> tuple[TPCCState, object, MixStats]:
    """Drive the engine's plan-selected regime over a pre-generated stream.

    One pending-outbox/stats/audit core for every (regime x mode x mix)
    combination — see the module docstring for the knobs. Batches are
    pre-generated (the generator is not the system under test); wall time
    covers device execution only (compilation happens on throwaway copies,
    so all ``n_batches`` batches are timed).

    ``obs`` (an ``repro.obs.ObsSession``) attaches the observability plane:
    the on-device metrics lattice is folded from deferred per-chunk recorder
    programs after the timed loop — lattice joins commute, so the result is
    bit-identical to inline recording and the loop pays zero extra
    dispatches (fused mode only — the per-batch dispatch baseline predates
    the chunked executor), tracer spans wrap the megastep /
    outbox-drain / share-refresh / audit phases, and ``obs.snapshot()``
    afterwards holds stats + latency quantiles + spans (+ ledger when the
    session asks for one). Metrics are write-only: a metrics-on run's final
    state is bit-identical to metrics-off (tests/test_obs.py).

    Returns ``(state, escrow-or-None, MixStats)``; ``stats.neworders``
    counts COMMITTED New-Orders (escrow aborts land in ``stats.aborts``,
    owner-side cold-tier rejections in ``stats.cold_rejects``).

    Failure-tolerance knobs (escrow regime): ``retry_cap`` > 0 bounds an
    on-device cold-retry ring — owner-rejected remote-cold entries
    re-present for up to ``retry_max`` drain windows before counting as
    FINAL ``cold_rejects`` (``retry`` resumes a checkpointed ring;
    ``final_flush=False`` leaves run-end pending entries in the returned
    ring instead of flushing them to the reject count). ``alive``
    ([n_shards] mask) threads share reclamation into every refresh;
    ``liveness`` (a ``runtime.liveness.LeaseMonitor``) replaces the caller-
    provided mask with a SELF-DERIVED one — the monitor is ticked once per
    drain window and its alive mask feeds the refresh, so kill -> detect ->
    reclaim closes with no omniscient caller. ``retry_reserve=1`` enables
    owner-granted reservations: a ring entry on its last permitted retry is
    granted stock ahead of the young cold queue (smallest-first per cell)
    instead of final-rejecting, bounding tail starvation; ``retry_reserve=0``
    is bit-identical to the pre-reservation path.
    ``return_retry=True`` appends the retry ring to the return tuple.
    """
    escrow = engine.stock_regime is CoordClass.ESCROW
    if legacy:
        fused = False
    if obs is not None and obs.wants_metrics and not fused:
        raise ValueError("on-device metrics require the fused executor "
                         "(fused=True); dispatch/legacy modes support "
                         "tracer spans only")
    if escrow and esc is None:
        esc = engine.init_escrow(state)
    q0 = state.s_quantity.copy() if audit else None

    # -- streams: one source for every mode ---------------------------------
    if reads:
        no_b, pay_b, os_b, sl_b = generate_mix_batches(
            engine, batch_per_shard=batch_per_shard, n_batches=n_batches,
            remote_frac=remote_frac, read_frac=read_frac, seed=seed,
            item_skew=item_skew)
        if not payments:
            pay_b = None
    else:
        rng = np.random.default_rng(seed)
        no_b = generate_neworder_stream(
            engine, batch_per_shard=batch_per_shard, n_batches=n_batches,
            remote_frac=remote_frac, rng=rng, item_skew=item_skew)
        pay_b = [_home_partitioned(tpcc.generate_payment, rng, engine,
                                   batch_per_shard)
                 for _ in range(n_batches)] if payments else None
        os_b = sl_b = None

    if retry_cap > 0 and not escrow:
        raise ValueError("retry_cap > 0 requires the escrow regime "
                         "(the retry ring holds strict cold-tier entries)")
    if fused:
        state, esc, stats, retry = _fused_loop(
            engine, state, esc, no_b, pay_b, os_b, sl_b,
            merge_every=merge_every, refresh_every=refresh_every,
            refresh_abort_rate=refresh_abort_rate, deliveries=deliveries,
            escrow=escrow, obs=obs, retry_cap=retry_cap,
            retry_max=retry_max, retry=retry, alive=alive,
            liveness=liveness, retry_reserve=retry_reserve,
            final_flush=final_flush)
    else:
        state, esc, stats, retry = _dispatch_loop(
            engine, state, esc, no_b, pay_b, os_b, sl_b,
            batch_per_shard=batch_per_shard, merge_every=merge_every,
            refresh_every=refresh_every,
            refresh_abort_rate=refresh_abort_rate, deliveries=deliveries,
            escrow=escrow, legacy=legacy, retry_cap=retry_cap,
            retry_max=retry_max, retry=retry, alive=alive,
            liveness=liveness, retry_reserve=retry_reserve,
            final_flush=final_flush)

    if audit:
        from .audit import assert_audit
        with trace.span(trace.AUDIT):
            if escrow:
                assert_audit(state, escrow=esc, initial_stock=q0,
                             strict_stock=True)
            else:
                assert_audit(state)
    if obs is not None:
        # one host transfer of the metrics lattice + the snapshot's
        # step→seconds calibration; the optional ledger compiles its phase
        # programs here, outside every timed region
        obs.finish(engine, stats, total_steps=n_batches,
                   ledger_kw=dict(chunk_len=min(merge_every, n_batches),
                                  batch_per_shard=batch_per_shard,
                                  refresh_every=refresh_every,
                                  payments=payments or reads, reads=reads,
                                  metrics=obs.wants_metrics))
    if return_retry:
        return state, esc, stats, retry
    return state, esc, stats


def _fused_loop(engine, state, esc, no_b, pay_b, os_b, sl_b, *,
                merge_every, refresh_every, refresh_abort_rate, deliveries,
                escrow, obs=None, retry_cap=0, retry_max=0, retry=None,
                alive=None, liveness=None, retry_reserve=0,
                final_flush=True):
    from .executor import get_fused_executor, stack_chunks

    chunks = stack_chunks(no_b, pay_b, os_b, sl_b, merge_every)
    ex = get_fused_executor(engine, ring_rows=merge_every,
                            deliveries=deliveries, retry_cap=retry_cap)
    if escrow:
        state, esc, counters, wall, refreshes, cold, retry = ex.run_escrow(
            state, esc, chunks, refresh_every=refresh_every,
            refresh_abort_rate=refresh_abort_rate, obs=obs, retry=retry,
            retry_max=retry_max, alive=alive, liveness=liveness,
            reserve=retry_reserve, final_flush=final_flush)
        return state, esc, counters_to_stats(
            counters, anti_entropy_rounds=len(chunks), wall_seconds=wall,
            refreshes=refreshes, cold_rejects=cold), retry
    state, counters, wall = ex.run(state, chunks, obs=obs)
    return state, None, counters_to_stats(
        counters, anti_entropy_rounds=len(chunks), wall_seconds=wall), None


def _dispatch_loop(engine, state, esc, no_b, pay_b, os_b, sl_b, *,
                   batch_per_shard, merge_every, refresh_every,
                   refresh_abort_rate, deliveries, escrow, legacy,
                   retry_cap=0, retry_max=0, retry=None, alive=None,
                   liveness=None, retry_reserve=0, final_flush=True):
    """The per-batch dispatch baseline (one jitted call per transaction type
    per batch) — the comparison target the fused executor is measured
    against, and the reference semantics for bit-exactness tests."""
    use_retry = escrow and retry_cap > 0
    if use_retry and retry is None:
        retry = engine.init_retry(retry_cap)
    n_batches = len(no_b)
    B = batch_per_shard * engine.n_shards
    reads = os_b is not None
    R = (max(1, os_b[0].w.shape[0] // engine.n_shards) * engine.n_shards
         if reads else 0)

    # -- warmup compiles on copies; the timed loop then covers every batch --
    warm = _tree_copy(state)
    wesc = _tree_copy(esc) if escrow else None
    if escrow:
        warm, wesc, outbox, _, _ = engine.neworder_escrow_step(warm, wesc,
                                                               no_b[0])
    else:
        warm, outbox, _ = engine.neworder_step(warm, no_b[0])
    if pay_b is not None:
        warm = engine.payment_step(warm, pay_b[0])
    res = (engine.order_status_step(warm, os_b[0]),
           engine.stock_level_step(warm, sl_b[0])) if reads else None
    if deliveries:
        warm, _ = engine.delivery_step(warm)
    # escrow windows drain batched in EVERY mode (the sparse cold tier's
    # all-or-nothing admission is defined over the whole window); the merge
    # regime keeps the seed's per-outbox drain under legacy. Batched modes
    # accumulate into ONE reused [rows, R] device window buffer, so every
    # drain compiles to a single flattened shape (ragged tails ride along as
    # valid=False rows instead of a second compile)
    rows = min(merge_every, n_batches)
    if legacy and not escrow:
        warm = engine.anti_entropy(warm, outbox)
    else:
        wwin = _OutboxWindow(outbox, rows)
        wwin.put(outbox)
        if use_retry:
            warm, _, _ = engine.drain_strict_retry(
                warm, wwin.flat(), engine.init_retry(retry_cap), retry_max,
                retry_reserve)
        elif escrow:
            warm, _ = engine.drain_strict(warm, wwin.flat())
        else:
            warm = engine.anti_entropy(warm, wwin.flat())
        wwin.clear()
        del wwin
    if escrow:
        wesc = engine.refresh_escrow(warm, wesc, alive)
    jax.block_until_ready((warm, wesc, res))
    del warm, wesc, outbox, res

    stats = MixStats()
    zero = 0 if legacy else jnp.zeros((), jnp.int32)
    # on-device stat accumulators: no per-iteration host round-trips (the
    # seed's int(...) reads — restored under ``legacy`` — forced a device
    # sync every batch)
    commit_acc, found_acc, fract_acc = zero, zero, zero
    rep_acc, del_acc, rej_acc = zero, zero, zero
    # per-replica commit tallies feed the adaptive refresh controller
    adaptive = escrow and refresh_abort_rate is not None
    pr_commit = jnp.zeros((engine.n_shards,), jnp.int32) if adaptive else None
    commits_at_refresh = np.zeros(engine.n_shards, np.int64)
    txns_at_refresh = 0
    rounds = 0
    pending: list[StockDelta] = []   # legacy merge mode only
    window: _OutboxWindow | None = None
    t0 = time.perf_counter()
    for i in range(n_batches):
        if escrow:
            state, esc, outbox, _, ok = engine.neworder_escrow_step(
                state, esc, no_b[i])
            commit_acc = commit_acc + (int(ok.sum()) if legacy
                                       else ok.sum().astype(jnp.int32))
            if adaptive:
                pr_commit = pr_commit + ok.reshape(
                    engine.n_shards, -1).sum(axis=1).astype(jnp.int32)
        else:
            state, outbox, _ = engine.neworder_step(state, no_b[i])
            stats.neworders += B
        if legacy and not escrow:
            pending.append(outbox)
        else:
            if window is None:
                window = _OutboxWindow(outbox, rows)
            window.put(outbox)
        if pay_b is not None:
            state = engine.payment_step(state, pay_b[i])
            stats.payments += B
        if reads:
            os_res = engine.order_status_step(state, os_b[i])
            sl_res = engine.stock_level_step(state, sl_b[i])
            stats.order_statuses += R
            stats.stock_levels += R
            if legacy:
                # seed behavior: host-side int() reads force a device sync
                # every single batch
                found_acc = found_acc + int(os_res.found.sum())
                fract_acc = fract_acc + int(os_res.fractures_observed()) \
                    + int((sl_res.fractured - sl_res.repaired).sum())
                rep_acc = rep_acc + int(os_res.repaired.sum()
                                        + sl_res.repaired.sum())
            else:
                found_acc = found_acc + os_res.found.sum()
                fract_acc = (fract_acc + os_res.fractures_observed()
                             + (sl_res.fractured - sl_res.repaired).sum())
                rep_acc = rep_acc + os_res.repaired.sum() + sl_res.repaired.sum()
        if deliveries:
            state, delivered = engine.delivery_step(state)
            del_acc = (del_acc + int(delivered.sum())) if legacy \
                else del_acc + delivered.sum()
        queued = len(pending) if (legacy and not escrow) else len(window)
        if queued == merge_every or i == n_batches - 1:
            # one batched drain of the whole window (Definition 3:
            # convergence may lag the hot path, but must happen); merge-
            # regime legacy mode keeps the seed's one jitted call per outbox
            if use_retry:
                state, retry, rej = engine.drain_strict_retry(
                    state, window.flat(), retry, retry_max, retry_reserve)
                rej_acc = rej_acc + (int(rej.sum()) if legacy
                                     else rej.sum().astype(jnp.int32))
                window.clear()
            elif escrow:
                state, rej = engine.drain_strict(state, window.flat())
                rej_acc = rej_acc + (int(rej.sum()) if legacy
                                     else rej.sum().astype(jnp.int32))
                window.clear()
            elif legacy:
                for ob in pending:
                    state = engine.anti_entropy(state, ob)
                pending = []
            else:
                state = engine.anti_entropy(state, window.flat())
                window.clear()
            stats.anti_entropy_rounds += 1
            rounds += 1
            if escrow:
                if liveness is not None:
                    # self-derived mask: one monitor tick per drain window,
                    # feeding the reclamation refresh below — no caller-
                    # provided omniscient view
                    alive = liveness.tick().astype(np.int32)
                if adaptive:
                    # the one host read adaptive control costs, per window
                    commits_now = np.asarray(jax.device_get(pr_commit),
                                             np.int64)
                    txns_now = batch_per_shard * (i + 1)
                    due = _adaptive_refresh_due(
                        (txns_now - txns_at_refresh)
                        - (commits_now - commits_at_refresh),
                        txns_now - txns_at_refresh, refresh_abort_rate)
                    if due:
                        commits_at_refresh = commits_now
                        txns_at_refresh = txns_now
                else:
                    due = rounds % refresh_every == 0
                if due:
                    # the amortized coordination point, aligned with the drain
                    esc = engine.refresh_escrow(state, esc, alive)
                    stats.refreshes += 1
    jax.block_until_ready((state, esc, commit_acc, found_acc, fract_acc,
                           rep_acc, del_acc, rej_acc))
    stats.wall_seconds = time.perf_counter() - t0
    # single host transfer for the data-dependent counters
    if escrow:
        stats.neworders = int(commit_acc)
        stats.aborts = B * n_batches - stats.neworders
        stats.cold_rejects = int(rej_acc)
        if use_retry and final_flush:
            # pending ring entries at run end never got their last window —
            # flush them to the final-reject count (exact accounting)
            stats.cold_rejects += int(np.asarray(
                jax.device_get(retry.valid)).sum())
    stats.reads_found = int(found_acc)
    stats.fractures_observed = int(fract_acc)
    stats.lines_repaired = int(rep_acc)
    stats.deliveries = int(del_acc)
    return state, esc, stats, retry


# ---------------------------------------------------------------------------
# Signature-compatible wrappers (the public driver API)
# ---------------------------------------------------------------------------


def run_closed_loop(engine, state: TPCCState, *,
                    batch_per_shard: int, n_batches: int,
                    remote_frac: float = 0.01, merge_every: int = 8,
                    seed: int = 0, payments: bool = False,
                    deliveries: bool = False, fused: bool = True,
                    refresh_every: int = 1,
                    refresh_abort_rate: float | None = None,
                    item_skew: float = 0.0,
                    ) -> tuple[TPCCState, RunStats]:
    """New-Order closed loop (+ optional Payment/Delivery riders). On an
    escrow-regime engine the New-Order-only stream runs the strict hot path
    and the stats carry aborts/refreshes."""
    escrow = engine.stock_regime is CoordClass.ESCROW
    if escrow and (payments or deliveries):
        raise NotImplementedError(
            "escrow regime: use run_escrow_loop(mix=True) for the full "
            "transaction mix")
    state, _, m = run_loop(
        engine, state, batch_per_shard=batch_per_shard, n_batches=n_batches,
        remote_frac=remote_frac, merge_every=merge_every,
        refresh_every=refresh_every, refresh_abort_rate=refresh_abort_rate,
        item_skew=item_skew, seed=seed, payments=payments, reads=False,
        deliveries=deliveries, fused=fused)
    return state, RunStats(
        committed=m.neworders, batches=n_batches,
        anti_entropy_rounds=m.anti_entropy_rounds, aborted=m.aborts,
        refreshes=m.refreshes, wall_seconds=m.wall_seconds)


def run_mixed_loop(engine, state: TPCCState, *,
                   batch_per_shard: int, n_batches: int,
                   remote_frac: float = 0.01, merge_every: int = 8,
                   read_frac: float = 0.25, seed: int = 0,
                   fused: bool = True, legacy: bool = False,
                   refresh_every: int = 1,
                   refresh_abort_rate: float | None = None,
                   item_skew: float = 0.0, obs=None,
                   ) -> tuple[TPCCState, MixStats]:
    """The full five-transaction mix (New-Order, Payment, RAMP Order-Status
    / Stock-Level, Delivery) under the engine's plan-selected regime."""
    state, _, stats = run_loop(
        engine, state, batch_per_shard=batch_per_shard, n_batches=n_batches,
        remote_frac=remote_frac, merge_every=merge_every,
        refresh_every=refresh_every, refresh_abort_rate=refresh_abort_rate,
        read_frac=read_frac, item_skew=item_skew, seed=seed, payments=True,
        reads=True, deliveries=True, fused=fused, legacy=legacy, obs=obs)
    return state, stats


def run_escrow_loop(engine, state: TPCCState, esc=None, *,
                    batch_per_shard: int, n_batches: int,
                    remote_frac: float = 0.01, merge_every: int = 8,
                    refresh_every: int = 1,
                    refresh_abort_rate: float | None = None,
                    read_frac: float = 0.25, seed: int = 0, mix: bool = True,
                    fused: bool = True, legacy: bool = False,
                    item_skew: float = 0.0, obs=None,
                    ) -> tuple[TPCCState, object, MixStats]:
    """Drive the escrow regime: strict-stock New-Order (plus the rest of the
    mix when ``mix=True``), one batched strict drain per ``merge_every``
    window, and the amortized share refresh — every ``refresh_every`` drains
    (fixed fallback) or abort-rate-triggered via ``refresh_abort_rate``.

    Returns (state, escrow, MixStats) — ``stats.neworders`` counts COMMITTED
    New-Orders; insufficient-share atomic aborts are in ``stats.aborts``;
    owner-side cold-tier rejections (sparse layout, remote cold lines that
    lost the race at their owner) in ``stats.cold_rejects``.
    """
    engine._require_escrow()
    state, esc, stats = run_loop(
        engine, state, esc, batch_per_shard=batch_per_shard,
        n_batches=n_batches, remote_frac=remote_frac,
        merge_every=merge_every, refresh_every=refresh_every,
        refresh_abort_rate=refresh_abort_rate, read_frac=read_frac,
        item_skew=item_skew, seed=seed, payments=mix, reads=mix,
        deliveries=mix, fused=fused, legacy=legacy, obs=obs)
    return state, esc, stats


def run_fused_loop(engine, state: TPCCState, *,
                   batch_per_shard: int, n_batches: int,
                   remote_frac: float = 0.01, merge_every: int = 8,
                   read_frac: float = 0.25, seed: int = 0,
                   ) -> tuple[TPCCState, MixStats]:
    """The full five-transaction mix on the fused executor (the public entry
    ``run_mixed_loop(fused=True)`` uses)."""
    return run_mixed_loop(engine, state, batch_per_shard=batch_per_shard,
                          n_batches=n_batches, remote_frac=remote_frac,
                          merge_every=merge_every, read_frac=read_frac,
                          seed=seed, fused=True)


def run_fused_escrow_loop(engine, state: TPCCState, esc=None, *,
                          batch_per_shard: int, n_batches: int,
                          remote_frac: float = 0.01, merge_every: int = 8,
                          refresh_every: int = 1, read_frac: float = 0.25,
                          seed: int = 0, mix: bool = True,
                          refresh_abort_rate: float | None = None,
                          ) -> tuple[TPCCState, object, MixStats]:
    """The escrow regime on the fused executor (the public entry
    ``run_escrow_loop(fused=True)`` uses)."""
    return run_escrow_loop(engine, state, esc,
                           batch_per_shard=batch_per_shard,
                           n_batches=n_batches, remote_frac=remote_frac,
                           merge_every=merge_every,
                           refresh_every=refresh_every,
                           refresh_abort_rate=refresh_abort_rate,
                           read_frac=read_frac, seed=seed, mix=mix,
                           fused=True)
