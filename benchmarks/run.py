"""Benchmark harness: one function per paper table/figure + the roofline
table derived from the dry-run artifacts. Includes the ``ramp_read`` row
(RAMP atomic-visibility reads vs 2PC-synchronized reads + the full TPC-C
mix; see repro/txn/ramp.py).

Prints ``name,us_per_call,derived`` CSV (one line per benchmark) followed by
the full roofline table when results/dryrun_baseline.json exists.

  PYTHONPATH=src:. python -m benchmarks.run            # everything
  PYTHONPATH=src:. python -m benchmarks.run --only table2,fig4_neworder
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

DRYRUN_JSON = os.path.join(os.path.dirname(__file__), "..", "results",
                           "dryrun_baseline.json")
OBS_SNAPSHOT_JSON = os.path.join(os.path.dirname(__file__), "..", "results",
                                 "obs_snapshot.json")


def roofline_table(path: str = DRYRUN_JSON, mesh: str | None = None,
                   attn_impl: str = "naive") -> list[dict]:
    """Build the 3-term roofline rows from saved dry-run cells."""
    from benchmarks import roofline as rl
    from repro.models.config import SHAPES

    with open(path) as f:
        cells = json.load(f)
    rows = []
    for c in cells:
        if not c.get("ok") or c.get("arch") == "tpcc":
            continue
        if mesh and c["mesh"] != mesh:
            continue
        if c.get("skipped"):
            rows.append({"arch": c["arch"], "shape": c["shape"],
                         "mesh": c["mesh"], "skipped": True,
                         "reason": c["reason"][:60]})
            continue
        chips = 512 if c["mesh"] == "2x16x16" else 256
        r = rl.build(c["arch"], SHAPES[c["shape"]], c["mesh"], chips,
                     attn_impl=attn_impl,
                     collective_bytes=c["collectives"].get(
                         "loop_scaled_bytes", c["collectives"]["bytes"]))
        row = r.row()
        row["hbm_gb_per_dev"] = round(
            (c["memory"].get("argument_bytes") or 0)
            / 1e9 + (c["memory"].get("temp_bytes") or 0) / 1e9, 2)
        row["compile_s"] = c.get("compile_seconds")
        rows.append(row)
    return rows


def print_roofline(rows: list[dict]) -> None:
    hdr = (f"{'arch':22s} {'shape':12s} {'mesh':8s} {'t_comp':>9s} "
           f"{'t_mem':>9s} {'t_coll':>9s} {'bound':>7s} {'useful':>7s} "
           f"{'MFU@roof':>8s} {'GB/dev':>7s}")
    print(hdr)
    print("-" * len(hdr))
    for r in rows:
        if r.get("skipped"):
            print(f"{r['arch']:22s} {r['shape']:12s} {r['mesh']:8s} "
                  f"{'skip: ' + r['reason']}")
            continue
        print(f"{r['arch']:22s} {r['shape']:12s} {r['mesh']:8s} "
              f"{r['t_compute_ms']:8.2f}m {r['t_memory_ms']:8.2f}m "
              f"{r['t_collective_ms']:8.2f}m {r['bottleneck'][:7]:>7s} "
              f"{r['useful_frac']:7.3f} {r['mfu_at_roofline']:8.3f} "
              f"{r['hbm_gb_per_dev']:7.2f}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--no-roofline", action="store_true")
    ap.add_argument("--json-out", default="")
    args = ap.parse_args()

    from benchmarks import paper_figures
    from repro.utils.jax_cache import use_compile_cache

    use_compile_cache()

    wanted = set(args.only.split(",")) if args.only else None
    all_rows = {}
    print("name,us_per_call,derived")
    for fn in paper_figures.ALL:
        if wanted and fn.__name__ not in wanted:
            continue
        rows, summary = fn()
        all_rows[summary["name"]] = rows
        print(f"{summary['name']},{summary['us_per_call']:.1f},"
              f"\"{summary['derived']}\"", flush=True)

    if not args.no_roofline and os.path.exists(DRYRUN_JSON):
        print("\n== roofline (baseline, from dry-run artifacts) ==")
        rows = roofline_table()
        print_roofline(rows)
        all_rows["roofline"] = rows
    elif not args.no_roofline:
        print(f"\n(roofline table skipped: {DRYRUN_JSON} not found — run "
              f"PYTHONPATH=src:. python -m repro.launch.dryrun first)")

    if not args.no_roofline and os.path.exists(OBS_SNAPSHOT_JSON):
        from benchmarks.roofline import txn_engine_row
        with open(OBS_SNAPSHOT_JSON) as f:
            snap = json.load(f)
        if snap.get("ledger"):
            row = txn_engine_row(
                snap["ledger"],
                throughput_txn_s=snap.get("stats", {}).get("throughput"))
            all_rows["txn_engine_roofline"] = [row]
            print("\n== txn engine (from the run's coordination ledger) ==")
            print(f"  {row['context']}: {row['measured_bytes_per_txn']} "
                  f"bytes/txn measured vs {row['model_floor_bytes_per_txn']} "
                  f"floor ({row['overhead_vs_floor']}x drain batching "
                  f"overhead); wire-bound ceiling "
                  f"{row['wire_bound_txn_s']:,.0f} txn/s/link; hot "
                  f"collectives {row['hot_collectives']}")

    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(all_rows, f, indent=1, default=str)
        print(f"wrote {args.json_out}")


if __name__ == "__main__":
    main()
