#!/usr/bin/env python3
"""Chip smoke test: the escrow-regime TPC-C engine on TPU, at TPC-C's own
cardinalities, driven through the entry points a user calls.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the sharded engine on a 2x2 host

One chip (``WAREHOUSES`` warehouses, spec cardinalities):

1. the escrow regime (strict ``s_quantity >= 0``, sparse hot-set shares,
   fused one-kernel megastep) over a seeded, Zipf-skewed full mix; the
   compiled megastep must hold the Pallas kernel (``tpu_custom_call``);
2. the same stream through the definitional path — the per-batch dispatch
   driver with ``effects="scan"``, ``admission="scan"``, no kernel:
   integer tables bit-identical, the strict-stock audit passing on both;
3. what ``admission="auto"`` resolves to on this chip;
4. a short merge-regime run (restock stock, full mix): all twelve TPC-C
   consistency criteria hold.

``--four-chips`` runs only the sharded path: the escrow regime on a
four-chip ``("data",)`` mesh at 4 x ``WAREHOUSES`` warehouses against the
definitional path on the same mesh, the audit, the zero-collective proof of
the hot path, and each chip holding a quarter of the state.

Without a TPU the script exits non-zero and prints no result. The last
line of standard output is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.txn import tpcc  # noqa: E402
from repro.txn.audit import assert_audit  # noqa: E402
from repro.txn.drivers import run_escrow_loop, run_mixed_loop  # noqa: E402
from repro.txn.engine import single_host_engine  # noqa: E402
from repro.txn.executor import get_fused_executor  # noqa: E402
from repro.utils.jax_cache import use_compile_cache  # noqa: E402

# warehouses per chip: the most whose megastep kernel (avail + three stock
# slabs, whole-array VMEM) compiles within one v5e core's default scoped
# VMEM — 11 is refused (tests/test_tpu_compile.py)
WAREHOUSES = 10
BATCH_PER_SHARD = 256
READ_FRAC = 0.25
ESCROW_RUN = dict(batch_per_shard=BATCH_PER_SHARD, merge_every=8,
                  n_batches=32, remote_frac=0.01, item_skew=1.0,
                  read_frac=READ_FRAC)
MERGE_RUN = dict(batch_per_shard=BATCH_PER_SHARD, merge_every=8,
                 n_batches=16, remote_frac=0.01, read_frac=READ_FRAC)
SEED = 0


def log(msg: str) -> None:
    print(msg, flush=True)


def bytes_per_device(tree) -> dict:
    """Bytes of ``tree`` resident on each device."""
    out: dict = {}
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            out[shard.device] = out.get(shard.device, 0) + shard.data.nbytes
    return out


def compare_tables(a, b) -> tuple[list[str], float]:
    """(integer/bool leaves of ``a`` and ``b`` that differ, max |diff| over
    the float leaves)."""
    differ, worst = [], 0.0
    la, _ = jax.tree_util.tree_flatten_with_path(a)
    lb = jax.tree.leaves(b)
    for (path, x), y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        if np.issubdtype(x.dtype, np.floating):
            worst = max(worst, float(np.max(np.abs(x - y), initial=0.0)))
        elif not np.array_equal(x, y):
            differ.append(jax.tree_util.keystr(path))
    return differ, worst


def escrow_run(scale, n_chips: int, fused: bool, **engine_kw):
    """One seeded escrow-regime run; returns (engine, state, escrow, stats,
    initial stock, seconds)."""
    eng = single_host_engine(scale, stock_invariant="strict", **engine_kw)
    state = eng.shard_state(tpcc.init_state(scale, SEED))
    q0 = state.s_quantity.copy()
    t0 = time.perf_counter()
    state, esc, stats = run_escrow_loop(eng, state, fused=fused, mix=True,
                                        seed=SEED, **ESCROW_RUN)
    jax.block_until_ready((state, esc))
    return eng, state, esc, stats, q0, time.perf_counter() - t0


def escrow_phase(scale, n_chips: int):
    """Kernel path vs definitional path on one stream, both audited.
    Returns the kernel-path engine."""
    log(f"escrow phase: {scale.n_warehouses} warehouses on {n_chips} chip(s)"
        f" — {scale.districts} districts, {scale.customers} customers per "
        f"district, {scale.n_items} items, {scale.order_capacity}-order "
        f"ring, {scale.max_lines} lines")
    finals = {}
    # the reference runs per batch: on the chip, the scan admission inside
    # the fused executor's chunk scan did not finish in minutes (PERF.md)
    for name, fused, kw in (
            ("kernel", True, dict(admission="kernel")),
            ("scan", False, dict(admission="scan", effects="scan"))):
        eng, state, esc, stats, q0, secs = escrow_run(scale, n_chips, fused,
                                                      **kw)
        if name == "kernel":
            per = bytes_per_device(state)
            log(f"state on device: {sum(per.values())} bytes; per chip: "
                + ", ".join(f"{d}={n}" for d, n in sorted(
                    per.items(), key=lambda kv: kv[0].id)))
            if len(set(per.values())) != 1 or len(per) != n_chips:
                raise AssertionError(f"state is not split evenly over the "
                                     f"{n_chips} chip(s): {per}")
        rep = assert_audit(state, escrow=esc, initial_stock=q0,
                           strict_stock=True)
        log(f"  {name}: committed {stats.neworders} New-Orders, aborted "
            f"{stats.aborts}, cold rejects {stats.cold_rejects}; "
            f"{stats.payments} payments, {stats.deliveries} deliveries, "
            f"{stats.fractures_observed} fractured reads; "
            f"{rep.describe()} (strict stock); run incl. compile "
            f"{secs:.1f} s")
        finals[name] = (eng, jax.device_get(state), jax.device_get(esc),
                        stats)
        del state, esc

    eng, s_k, e_k, m_k = finals["kernel"]
    _, s_s, e_s, m_s = finals["scan"]
    if m_k.aborts == 0:
        raise AssertionError("no transaction aborted: the residual FCFS "
                             "walk was never exercised")
    differ, worst = compare_tables((s_k, e_k), (s_s, e_s))
    counts = lambda m: (m.neworders, m.aborts, m.cold_rejects, m.payments,
                        m.deliveries, m.reads_found, m.fractures_observed)
    if differ or counts(m_k) != counts(m_s):
        raise AssertionError(f"kernel path differs from the scan path: "
                             f"{differ} {counts(m_k)} {counts(m_s)}")
    if worst > 1e-2:
        raise AssertionError(f"float tables differ by {worst}")
    log(f"  kernel == scan: integer tables bit-identical, float tables max "
        f"|diff| {worst}")

    return eng


def kernel_check(eng) -> None:
    """The kernel, not a reference, is what the escrow megastep runs."""
    ex = get_fused_executor(eng, ring_rows=ESCROW_RUN["merge_every"])
    t0 = time.perf_counter()
    text = ex.lowered_megastep(
        chunk_len=ESCROW_RUN["merge_every"],
        batch_per_shard=BATCH_PER_SHARD,
        read_per_shard=max(1, int(BATCH_PER_SHARD * READ_FRAC))
    ).compile().as_text()
    secs = time.perf_counter() - t0
    if "tpu_custom_call" not in text:
        raise AssertionError("escrow megastep holds no tpu_custom_call")
    log(f"  escrow megastep: tpu_custom_call present; lower + compile "
        f"{secs:.1f} s")


def merge_phase(scale) -> None:
    eng = single_host_engine(scale, stock_invariant="restock")
    state = eng.shard_state(tpcc.init_state(scale, SEED))
    t0 = time.perf_counter()
    state, stats = run_mixed_loop(eng, state, seed=SEED, **MERGE_RUN)
    crit = tpcc.check_consistency(state)
    held = sum(crit.values())
    log(f"merge phase: {stats.neworders} New-Orders, {stats.payments} "
        f"payments, {stats.deliveries} deliveries; consistency {held}/12; "
        f"run incl. compile {time.perf_counter() - t0:.1f} s")
    if held != 12:
        raise AssertionError(f"consistency criteria failed: {crit}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded four-chip escrow phase")
    args = ap.parse_args()
    n_chips = 4 if args.four_chips else 1

    cache = use_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})"
                 f"; this script runs on the chip only")
    if len(devices) != n_chips:
        sys.exit(f"chip_smoke: this phase runs on exactly {n_chips} chip(s)"
                 f"; {len(devices)} found")
    log(f"device: {dev.device_kind} x {len(devices)}; compile cache {cache}")

    eng = escrow_phase(tpcc.TPCCScale.spec_scale(WAREHOUSES * n_chips),
                       n_chips)
    kernel_check(eng)
    if n_chips > 1:
        log(f"  hot path collectives: "
            f"{eng.prove_coordination_free(BATCH_PER_SHARD)}")
    else:
        mode = tpcc.resolve_admission("auto", BATCH_PER_SHARD,
                                      tpcc.TPCCScale().max_lines)
        log(f"admission='auto' resolves to {mode!r} at batch "
            f"{BATCH_PER_SHARD}")
        merge_phase(tpcc.TPCCScale.spec_scale(WAREHOUSES))
    stats = dev.memory_stats() or {}
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
