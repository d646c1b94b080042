#!/usr/bin/env python3
"""The chip benchmark's command: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs the cell named in ``BENCHMARK.json`` on the machine it is started on
and prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and, with
``--trace 1``, ``breakdown``), and last ``checks``: each number the
comparison with the plain reference read, beside its limit. The same
numbers end standard error.

Exits non-zero, printing no result, where JAX finds no TPU, a chip whose
peaks ``bench/peaks.py`` lacks, or fewer chips than the cell asks for.

``--control bf16`` runs the program with its float tables in bfloat16, the
lower-precision control the limits are set against; the benchmark's own
runs never pass it.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("bf16",), default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--keep-trace", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()

    import jax
    from repro.utils.jax_cache import use_compile_cache

    use_compile_cache()
    # every program of a run goes to the cache, however fast it compiled,
    # so that only a checkout's first run of a cell compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    from bench import harness, spec
    from bench.peaks import UnknownDevice, peaks_for

    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"bench: JAX found no TPU (platform "
                 f"{devices[0].platform!r}); the benchmark runs on the chip")
    try:
        peaks_for(devices[0].device_kind)
    except UnknownDevice as e:
        sys.exit(f"bench: {e}")
    if len(devices) < cell["chips"]:
        sys.exit(f"bench: {args.workload} needs {cell['chips']} chips; "
                 f"{len(devices)} found")

    result = harness.run_cell(
        args.workload, args.seed, args.seconds, bool(args.trace), t0=T0,
        bench=bench, devices=devices[:cell["chips"]],
        float_dtype="bfloat16" if args.control == "bf16" else None,
        keep_trace=args.keep_trace,
        log=lambda msg: print(msg, flush=True))
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
