"""Coordination-free observability plane.

Three pillars, one session object:

* :mod:`repro.obs.metrics` — the on-device metrics lattice (per-txn-type
  latency-proxy histograms, per-replica abort/cold-reject counters, the live
  item-access histogram), fed by deferred per-chunk recorder programs whose
  lattice joins commute — bit-identical to inline recording, zero dispatches
  in the timed loop;
* :mod:`repro.obs.trace` — the executor's host spans and named device
  phases, on the profiler's clock, and the scope table that maps a
  program's instructions to its phases;
* :mod:`repro.obs.ledger` — the coordination ledger (per-phase collective
  counts and bytes-on-wire from compiled HLO; hot phases budgeted at zero).

:class:`ObsSession` bundles them for the closed-loop drivers: pass one to
``drivers.run_loop(obs=...)`` and read ``session.snapshot()`` after the run.
"""

from __future__ import annotations

import json

import jax
import numpy as np

from .ledger import CoordinationLedger, build_ledger
from .metrics import (N_TXN_TYPES, OBS_BINS, TXN_TYPES, ObsMetrics,
                      add_cold_rejects, heartbeat_lag_histogram,
                      heartbeat_lag_summary, init_obs_metrics,
                      item_access_summary, latency_summary, make_obs_metrics,
                      obs_metrics_join, obs_metrics_specs,
                      obs_partition_specs)

__all__ = [
    "ObsSession", "CoordinationLedger", "build_ledger",
    "ObsMetrics", "make_obs_metrics", "init_obs_metrics", "obs_metrics_join",
    "obs_metrics_specs", "obs_partition_specs", "add_cold_rejects",
    "latency_summary", "item_access_summary", "heartbeat_lag_histogram",
    "heartbeat_lag_summary", "TXN_TYPES", "N_TXN_TYPES",
    "OBS_BINS",
]


class ObsSession:
    """One closed-loop run's observability state.

    ``metrics=True`` threads the on-device :class:`ObsMetrics` lattice
    through the fused megastep (write-only: the transaction path never reads
    it, so final state is bit-identical to a metrics-off run);
    ``ledger=True`` builds the coordination ledger at finish (compiles the
    phase programs once, outside any timed region).
    """

    def __init__(self, metrics: bool = True, ledger: bool = False):
        self.wants_metrics = metrics
        self.wants_ledger = ledger
        self.device_metrics: ObsMetrics | None = None
        self.metrics: ObsMetrics | None = None   # host copy, set at finish
        self.heartbeat_lag = None                # HistogramLattice | None
        self.ledger: CoordinationLedger | None = None
        self.stats = None
        self._engine = None
        self._run_kw: dict = {}

    # -- driver-side hooks ---------------------------------------------------

    def init_metrics(self, engine) -> ObsMetrics | None:
        """Called by the executor at run start; returns the device pytree the
        megastep carries (or None when metrics are off)."""
        self._engine = engine
        if not self.wants_metrics:
            return None
        self.device_metrics = init_obs_metrics(engine)
        return self.device_metrics

    def finish(self, engine, stats, *, total_steps: int | None = None,
               ledger_kw: dict | None = None) -> None:
        """One host transfer of the metrics lattice + optional ledger build.
        ``total_steps`` (scan steps executed) calibrates the latency proxy's
        step→seconds conversion from the run's wall clock."""
        self._engine = engine
        self.stats = stats
        self._run_kw = dict(ledger_kw or {})
        self._total_steps = total_steps
        if self.device_metrics is not None:
            self.metrics = jax.device_get(self.device_metrics)
        if self.wants_ledger:
            self.ledger = build_ledger(engine, **self._run_kw)

    # -- export --------------------------------------------------------------

    @property
    def step_wall_s(self) -> float | None:
        """Measured wall seconds per scan step (includes the amortized drain
        share — the client-visible number)."""
        wall = getattr(self.stats, "wall_seconds", None)
        if wall and getattr(self, "_total_steps", None):
            return wall / self._total_steps
        return None

    def latency_summary(self) -> dict | None:
        if self.metrics is None:
            return None
        return latency_summary(self.metrics, self.step_wall_s)

    def item_access_summary(self, top_k: int = 10) -> dict | None:
        if self.metrics is None:
            return None
        return item_access_summary(self.metrics, top_k)

    def record_heartbeat_lags(self, lags) -> None:
        """Fold detection-latency samples (``LeaseMonitor.detection_lags``,
        in drain windows) into the session's heartbeat-lag histogram.
        Repeated records are the LOCAL monotone write (bin adds on this
        session's lane); merging views from *distinct* observers is the
        lattice join (``HistogramLattice.join`` over their lanes)."""
        hist = heartbeat_lag_histogram(lags)
        self.heartbeat_lag = hist if self.heartbeat_lag is None else \
            self.heartbeat_lag._replace(
                counts=self.heartbeat_lag.counts + hist.counts)

    def detection_latency_summary(self) -> dict | None:
        if self.heartbeat_lag is None:
            return None
        return heartbeat_lag_summary(self.heartbeat_lag)

    def snapshot(self) -> dict:
        """The full JSON-ready snapshot: closed-loop stats, per-txn-type
        latency quantiles, counters, item-access profile, and the
        coordination ledger."""
        snap: dict = {"schema": "repro.obs/1"}
        if self.stats is not None:
            s = self.stats
            snap["stats"] = {f: getattr(s, f) for f in
                             s.__dataclass_fields__}  # type: ignore[attr-defined]
            snap["stats"]["committed"] = s.committed
            snap["stats"]["throughput"] = s.throughput
        if self.step_wall_s is not None:
            snap["step_wall_s"] = self.step_wall_s
        if self.metrics is not None:
            snap["latency"] = self.latency_summary()
            snap["counters"] = {
                "aborts_per_replica":
                    np.asarray(self.metrics.aborts.slots).tolist(),
                "cold_rejects_per_replica":
                    np.asarray(self.metrics.cold_rejects.slots).tolist(),
            }
            snap["item_access"] = self.item_access_summary()
        if self.heartbeat_lag is not None:
            snap["detection_latency"] = self.detection_latency_summary()
        if self.ledger is not None:
            snap["ledger"] = self.ledger.snapshot()
        return snap

    def to_json(self, **kw) -> str:
        return json.dumps(self.snapshot(), **{"indent": 2, **kw})

    def dashboard(self) -> str:
        """Text view: latency table + ledger."""
        parts = []
        lat = self.latency_summary()
        if lat:
            sw = self.step_wall_s
            parts.append("per-transaction-type latency proxy"
                         + (" (measured steps → seconds)" if sw else
                            " (scan-step units)") + ":")
            parts.append(f"  {'txn type':<14}{'count':>9}{'p50':>10}"
                         f"{'p99':>10}")
            for name, row in lat.items():
                if sw:
                    p50 = f"{row['p50_s'] * 1e6:>8.0f}us"
                    p99 = f"{row['p99_s'] * 1e6:>8.0f}us"
                else:
                    p50 = f"{row['p50_steps']:>8.1f}st"
                    p99 = f"{row['p99_steps']:>8.1f}st"
                parts.append(f"  {name:<14}{row['count']:>9}{p50:>10}"
                             f"{p99:>10}")
        if self.ledger is not None:
            parts.append(self.ledger.table())
        return "\n".join(parts)
