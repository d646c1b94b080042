"""One run of one cell: set-up, the measured window of closed-loop rounds,
the optional profiler trace, and the comparison that decides ``correct``.

The window drives the program's own entry, ``FusedExecutor.run`` (merge
regime) or ``FusedExecutor.run_escrow`` (escrow regime), one round at a
time: a round hands the next ``chunks_per_round`` chunks of the traffic to
the entry with ``warmup=False`` and reads the round's counters back to the
host. A round's clock starts when its transactions sit in host memory and
stops when the host holds their verdicts. A thread of the client draws the
traffic ahead of the loop; the loop's wait for it is reported.

Set-up (``setup_s``) runs from process start to the first timed round: the
engine, the tables loaded with the configuration's data from the seed (TPC-C's
initial population, orders and history included), and two rounds of the
stream through the entry (the first with the executor's own warm-up, which
compiles every program the rounds use). The collector is off in the window:
set-up's objects are frozen out of its reach first.
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import importlib.util
import os
import queue
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.txn import tpcc
from repro.txn.engine import Engine
from repro.txn.executor import MixChunk, get_fused_executor

from . import spec
from .peaks import PEAKS
from .trace import (ROUND_SPAN, TraceView, breakdown, load_xplane,
                    save_events)
from .traffic.generator import Generator, Shape, load_mix

REFERENCE_DIR = Path(__file__).resolve().parent / "reference"
SETUP_ROUNDS = 2          # rounds of the stream run inside set-up
TRACE_SECONDS = 3.0       # longest stretch of the window a trace covers
FEED_DEPTH = 4            # chunks the client draws ahead of the loop


def load_reference(name: str):
    path = REFERENCE_DIR / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(f"bench_ref_{name}",
                                                      path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Round:
    start: float          # perf_counter seconds
    end: float
    wait: float           # seconds the loop waited for the generator
    counters: np.ndarray  # [len(COUNTERS)] int64, summed over the chips
    chunks: int


@dataclasses.dataclass
class RunRecord:
    """What a run measured; the metric readers take their numbers here."""

    config: dict
    mix: object
    setup_s: float
    window_s: float
    rounds: list          # window rounds only
    counter_names: tuple
    peaks: object = None  # bench.peaks.Peaks, where the kind has them
    trace: TraceView | None = None
    traced_chunks: int = 0

    def counter(self, name: str) -> np.ndarray:
        i = self.counter_names.index(name)
        return np.array([r.counters[i] for r in self.rounds], np.int64)

    def decided(self) -> float:
        """Transactions decided in the window: every New-Order (committed
        or refused for stock), Payment and Order-Status, and a tenth of
        each delivered order (one TPC-C Delivery delivers one order in each
        of a warehouse's ten districts). Stock-Levels are left out: the
        program reads their order lines but never computes their answer,
        the count of low-stock items."""
        c = {k: self.counter(k).sum() for k in self.counter_names}
        return float(c["neworders"] + c["aborts"] + c["payments"]
                     + c["order_statuses"] + c["deliveries"] / 10.0)


class Feed:
    """The client: a thread that draws chunk ``k`` for ``k = start, ...``
    ahead of the loop."""

    def __init__(self, gen: Generator, start: int, depth: int = FEED_DEPTH):
        self._gen = gen
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._next = start
        self._error = None
        self._thread = threading.Thread(target=self._work, daemon=True)
        self._thread.start()

    def _work(self):
        k = self._next
        try:
            while not self._stop.is_set():
                item = self._gen.chunk(k)
                while not self._stop.is_set():
                    try:
                        self._q.put(item, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                k += 1
        except Exception as e:  # surfaced to the loop by take()
            self._error = e

    def take(self):
        """The next chunk, and the seconds spent waiting for it."""
        t = time.perf_counter()
        while True:
            if self._error is not None:
                raise RuntimeError("traffic generator failed") \
                    from self._error
            try:
                item = self._q.get(timeout=0.1)
                return item, time.perf_counter() - t
            except queue.Empty:
                continue

    def close(self):
        self._stop.set()
        self._thread.join(timeout=30)
        if self._thread.is_alive():
            raise RuntimeError("traffic generator did not stop")


class CompileCounter:
    """Counts compilations and persistent-cache loads while armed."""

    def __init__(self):
        self.armed = False
        self.compiles = 0
        self.cache_hits = 0

        def on_duration(event, duration, **kw):
            if self.armed and \
                    event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        def on_event(event, **kw):
            if self.armed and event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


@functools.partial(jax.jit, donate_argnums=0,
                   static_argnames=("new_orders", "payment", "qty"))
def load_initial(state, o_c_id, o_ol_cnt, o_carrier, ol_i_id, ol_amount, *,
                 new_orders: int, payment: float, qty: int):
    """The deployment's initial ORDER, NEW-ORDER, ORDER-LINE and HISTORY
    rows written into the empty tables, on the device: order ``o`` of a
    district in ring slot ``o``, entry date ``o - N0`` (older than every
    transaction of the run), commit stamp 0; each customer with one
    payment of ``payment``."""
    W, D, N0 = o_c_id.shape
    C = state.c_balance.shape[2]
    o_id = jnp.arange(N0)
    delivered = (o_id < N0 - new_orders)[None, None, :]
    valid = jnp.arange(ol_i_id.shape[3]) < o_ol_cnt[..., None]
    full = (W, D, N0)

    def put(x, v):
        return x.at[:, :, :N0].set(jnp.broadcast_to(v, x[:, :, :N0].shape)
                                   .astype(x.dtype))

    def const(x, v):
        return jnp.full_like(x, v)

    s = state
    return s._replace(
        w_ytd=const(s.w_ytd, D * C * payment),
        d_ytd=const(s.d_ytd, C * payment),
        h_amount_sum=const(s.h_amount_sum, C * payment),
        c_balance=const(s.c_balance, -payment),
        c_ytd_payment=const(s.c_ytd_payment, payment),
        c_payment_cnt=const(s.c_payment_cnt, int(payment > 0)),
        d_next_o_id=const(s.d_next_o_id, N0),
        o_valid=put(s.o_valid, True),
        o_c_id=put(s.o_c_id, o_c_id),
        o_ol_cnt=put(s.o_ol_cnt, o_ol_cnt),
        o_carrier=put(s.o_carrier, o_carrier),
        o_entry_d=put(s.o_entry_d, jnp.broadcast_to(o_id - N0, full)),
        no_valid=put(s.no_valid, ~delivered),
        o_ts=put(s.o_ts, 0),
        ol_valid=put(s.ol_valid, valid),
        ol_vis=put(s.ol_vis, valid),
        ol_delivered=put(s.ol_delivered, valid & delivered[..., None]),
        ol_qty=put(s.ol_qty, jnp.where(valid, qty, 0)),
        ol_ts=put(s.ol_ts, jnp.where(valid, 0, -1)),
        ol_i_id=put(s.ol_i_id, ol_i_id),
        ol_supply_w=put(s.ol_supply_w, jnp.arange(W)[:, None, None, None]),
        ol_amount=put(s.ol_amount, ol_amount))


def to_mix_chunk(ch):
    """The generator's arrays as the program's stacked batch types."""
    return MixChunk(
        neworder=tpcc.NewOrderBatch(ch.no_w, ch.no_d, ch.no_c,
                                    ch.no_n_lines, ch.no_i_id,
                                    ch.no_supply_w, ch.no_qty, ch.no_ts),
        payment=tpcc.PaymentBatch(ch.pay_w, ch.pay_d, ch.pay_c,
                                  ch.pay_amount),
        order_status=tpcc.OrderStatusBatch(ch.os_w, ch.os_d, ch.os_c),
        stock_level=tpcc.StockLevelBatch(ch.sl_w, ch.sl_d,
                                         ch.sl_threshold))


class Cell:
    """The program set up for one cell: engine, tables, executor."""

    def __init__(self, cfg: dict, mix, seed: int, devices,
                 float_dtype: str | None = None):
        self.ref = load_reference(cfg["reference"])
        self.chips = len(devices)
        scale = tpcc.TPCCScale(
            n_warehouses=cfg["n_warehouses"], districts=cfg["districts"],
            customers=cfg["customers"], n_items=cfg["n_items"],
            order_capacity=cfg["order_capacity"],
            max_lines=cfg["max_lines"])
        e = cfg["engine"]
        self.engine = Engine(
            scale, Mesh(np.array(devices), ("data",)), ("data",),
            stock_invariant=e["stock_invariant"],
            escrow_layout=e["escrow_layout"], hot_items=e["hot_items"],
            admission=e["admission"], effects=e["effects"])
        self.escrow = e["stock_invariant"] == "strict"

        dtype = jnp.dtype(float_dtype or cfg["float_dtype"])
        self.data = self.ref.make_data(cfg, seed)
        W, I = cfg["n_warehouses"], cfg["n_items"]
        d = self.data
        state = tpcc.init_state(scale, 0, dtype=dtype)._replace(
            s_quantity=jnp.asarray(d.s_quantity),
            i_price=jnp.asarray(np.broadcast_to(d.i_price, (W, I))),
            w_tax=jnp.asarray(d.w_tax), d_tax=jnp.asarray(d.d_tax),
            c_discount=jnp.asarray(d.c_discount))
        state = load_initial(state, d.o_c_id, d.o_ol_cnt, d.o_carrier,
                             d.ol_i_id, d.ol_amount, new_orders=d.new_orders,
                             payment=d.payment, qty=self.ref.INITIAL_QTY)
        self.state = self.engine.shard_state(state)
        self.esc = self.engine.init_escrow(self.state) if self.escrow \
            else None
        self.executor = get_fused_executor(self.engine,
                                           ring_rows=mix.chunk_steps)
        jax.block_until_ready(self.state)
        self.gen = Generator(mix, Shape(
            W, cfg["districts"], cfg["customers"], I, cfg["max_lines"],
            self.chips), seed)

    def round(self, chunks, warmup: bool = False) -> np.ndarray:
        """One call of the program's entry over ``chunks``; returns the
        counters (summed over chips) once the host holds them."""
        mc = [to_mix_chunk(c) for c in chunks]
        ex = self.executor
        if self.escrow:
            self.state, self.esc, counters, _, _, cold, _ = ex.run_escrow(
                self.state, self.esc, mc, refresh_every=1, warmup=warmup)
        else:
            self.state, counters, _ = ex.run(self.state, mc, warmup=warmup)
            cold = 0
        c = jax.device_get(counters)
        out = [int(np.asarray(getattr(c, k)).sum())
               for k in self.ref.COUNTERS[:-1]]
        return np.array(out + [cold], np.int64)

    def fetch(self, width: int) -> dict:
        """The program's final tables on the host; line tables over the
        first ``width`` slots of every district."""
        s = self.state
        got = {}
        for name in s._fields:
            x = getattr(s, name)
            if name.startswith("ol_"):
                x = x[:, :, :width]
            got[name] = np.asarray(jax.device_get(x))
        if self.esc is not None:
            got["shares"] = np.asarray(jax.device_get(self.esc.shares))
            got["spent"] = np.asarray(jax.device_get(self.esc.spent))
        return got

    def free(self):
        self.state = self.esc = None


def peak_bytes(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             t0: float | None = None, devices=None, bench: dict | None = None,
             float_dtype: str | None = None, keep_trace: str | None = None,
             mix=None, log=print) -> dict:
    """Run one cell once and return the result line's object. ``mix``
    stands in for the cell's traffic file (tests run tiny mixes)."""
    t0 = time.perf_counter() if t0 is None else t0
    bench = spec.load_benchmark() if bench is None else bench
    cell = spec.find_cell(bench, cell_name)
    cfg = spec.load_config(bench, cell["config"])
    mix = load_mix(cell["traffic"]) if mix is None else mix
    devices = jax.devices()[:cell["chips"]] if devices is None else devices
    counter = CompileCounter()
    counter.armed = True

    c = Cell(cfg, mix, seed, devices, float_dtype)
    stream, round_chunks, setup_counters = [], [], []
    feed = Feed(c.gen, 0)
    try:
        for i in range(SETUP_ROUNDS):
            chunks = [feed.take()[0] for _ in range(mix.chunks_per_round)]
            setup_counters.append(c.round(chunks, warmup=(i == 0)))
            stream += chunks
            round_chunks.append(len(chunks))
        # no collector pass over the set-up's objects inside the window
        gc.collect()
        gc.freeze()
        gc.disable()
        setup_s = time.perf_counter() - t0
        setup_compiles = counter.compiles
        log(f"setup_s {setup_s:.3f}: {setup_compiles} compiles, "
            f"{counter.cache_hits} persistent-cache loads")

        counter.compiles = counter.cache_hits = 0
        rounds, traced_from, traced_to = [], None, None
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace \
            else None
        w0 = time.perf_counter()
        while time.perf_counter() - w0 < seconds:
            if trace and traced_from is None and rounds:
                _start_trace(trace_dir)
                traced_from = len(rounds)
            chunks, waits = [], 0.0
            for _ in range(mix.chunks_per_round):
                ch, wait = feed.take()
                chunks.append(ch)
                waits += wait
            a = time.perf_counter()
            with jax.profiler.TraceAnnotation(ROUND_SPAN):
                cnt = c.round(chunks)
            b = time.perf_counter()
            rounds.append(Round(a, b, waits, cnt, len(chunks)))
            stream += chunks
            round_chunks.append(len(chunks))
            if (traced_from is not None and traced_to is None
                    and b - rounds[traced_from].start >= min(
                        TRACE_SECONDS, seconds / 2)):
                jax.profiler.stop_trace()
                traced_to = len(rounds)
        w1 = time.perf_counter()
        if traced_from is not None and traced_to is None:
            jax.profiler.stop_trace()
            traced_to = len(rounds)
        counter.armed = False
    finally:
        gc.enable()
        gc.unfreeze()
        feed.close()

    window_compiles = counter.compiles + counter.cache_hits
    mem = peak_bytes(devices)

    # the comparison: the reference replays the whole stream
    t_check = time.perf_counter()
    all_counters = np.stack(setup_counters + [r.counters for r in rounds])
    o_valid = np.asarray(jax.device_get(c.state.o_valid))
    o_entry = np.asarray(jax.device_get(c.state.o_entry_d))
    claims = c.ref.claims_from_orders(o_valid, o_entry) if c.escrow \
        else None
    exp = c.ref.replay(cfg, c.data, stream, c.chips, claims)
    width = min(cfg["order_capacity"],
                max(exp.max_orders, int(o_valid.sum(-1).max())) + 1)
    got = c.fetch(width)
    c.free()
    checks, detail = c.ref.compare(cfg, c.data, exp, got, all_counters,
                                   round_chunks)
    limits = cfg["limits"]
    correct = all(checks[k] <= limits[k] for k in checks)
    log(f"check: reference and comparison "
        f"{time.perf_counter() - t_check:.3f} s over {len(stream)} chunks")
    print("detail: " + ", ".join(f"{k} {v:.3g}" for k, v in detail.items()),
          file=sys.stderr, flush=True)

    dev0 = devices[0]
    rec = RunRecord(config=cfg, mix=mix, setup_s=setup_s,
                    window_s=w1 - w0, rounds=rounds,
                    counter_names=c.ref.COUNTERS,
                    peaks=PEAKS.get(dev0.device_kind))
    _log_window(rec, window_compiles, mem, log)

    result = {"correct": bool(correct)}
    # every submitted New-Order, Payment and read has to come back decided
    submitted = sum(mix.chunk_steps * r.chunks * c.chips * (
        mix.neworders_per_step + mix.payments_per_step
        + 2 * mix.reads_per_step) for r in rounds)
    returned = sum(int(rec.counter(k).sum()) for k in (
        "neworders", "aborts", "payments", "order_statuses", "stock_levels"))
    result["attempted"] = int(submitted)
    result["failed"] = int(abs(submitted - returned))

    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    if trace:
        events = _read_trace(trace_dir, keep_trace)
        view = TraceView(events)
        rec.trace = view
        rec.traced_chunks = len(view.rounds) * mix.chunks_per_round
        if view.devices:
            busy = [view.busy_ns(d) for d in view.devices]
            device["busy_s"] = float(np.mean(busy)) / 1e9
        device["window_s"] = view.window_ns / 1e9
        result["breakdown"] = breakdown(view)
        log(f"trace: {len(view.rounds)} rounds, {len(view.devices)} "
            f"device planes, {len(events)} events")

    metrics = {}
    for m in spec.cell_metrics(bench, cell_name, trace):
        value = spec.load_reader(m["name"])(rec)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = {k: {"value": checks[k], "limit": limits[k]}
                        for k in checks}
    return result


def _log_window(rec: RunRecord, compiles: int, mem, log) -> None:
    n = len(rec.rounds)
    waits = sum(r.wait for r in rec.rounds)
    no = rec.counter("neworders")
    ab = rec.counter("aborts")
    log(f"window: {n} rounds in {rec.window_s:.3f} s; generator wait "
        f"{waits:.6f} s; {compiles} compiles or cache loads in the window")
    if n:
        ms = np.percentile([(r.end - r.start) * 1e3 for r in rec.rounds],
                           [0, 5, 25, 50, 75, 95, 100])
        log("round ms: min, p5, p25, p50, p75, p95, max: "
            + ", ".join(f"{x:.3f}" for x in ms))
        j = int(np.argmax([r.end - r.start for r in rec.rounds]))
        log(f"slowest round: {j} of {n}, "
            f"{rec.rounds[j].start - rec.rounds[0].start:.3f} s into the "
            f"window")
        log(f"committed New-Orders per minute (tpmC-style): "
            f"{no.sum() / rec.window_s * 60:.1f}")
        k = max(1, n // 10)
        share = lambda a, b: float(a.sum() / max(1, a.sum() + b.sum()))
        log(f"committed share of New-Orders: first tenth "
            f"{share(no[:k], ab[:k]):.4f}, last tenth "
            f"{share(no[-k:], ab[-k:]):.4f}")
        log(f"cold rejects in the window: "
            f"{int(rec.counter('cold_rejects').sum())}")
    log(f"peak_bytes_in_use: {mem}")


def _start_trace(trace_dir: str) -> None:
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def _read_trace(trace_dir: str, keep: str | None):
    paths = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not paths:
        raise RuntimeError("the profiler wrote no trace")
    events = load_xplane(paths[-1])
    if keep:
        os.makedirs(keep, exist_ok=True)
        save_events(events, Path(keep) / "events.json.gz")
    shutil.rmtree(trace_dir, ignore_errors=True)
    return events
