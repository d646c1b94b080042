"""Shared set-up of the benchmark's CPU tests: tiny cells run through the
harness with a tiny traffic mix, the chip check skipped."""

from __future__ import annotations

import json
from pathlib import Path

from bench import harness, spec
from bench.traffic.generator import Mix

FIXTURES = Path(__file__).resolve().parent / "fixtures"
REL = "tests/bench/fixtures"


def tiny_mix(item_dist: str = "uniform", **kw) -> Mix:
    base = dict(name="tiny", chunk_steps=4, chunks_per_round=1,
                neworders_per_step=8, payments_per_step=8, read_frac=0.25,
                remote_frac=0.1, item_dist=item_dist, zipf_theta=1.0)
    base.update(kw)
    return Mix(**base)


def tiny_bench(config: str, chips: int = 1) -> dict:
    """BENCHMARK.json with one more cell, ``tiny``, over a fixture
    configuration."""
    bench = spec.load_benchmark()
    bench["configs"].append({"name": "tiny-config",
                             "file": f"{REL}/{config}.json"})
    bench["workloads"].append({"name": "tiny", "config": "tiny-config",
                               "traffic": "tiny", "chips": chips})
    return bench


def run_tiny(config: str, *, seed: int = 2**31 + 7, seconds: float = 0.05,
             chips: int = 1, float_dtype=None, mix=None) -> dict:
    import jax

    escrow = json.loads((FIXTURES / f"{config}.json").read_text())[
        "engine"]["stock_invariant"] == "strict"
    mix = mix or tiny_mix("zipf" if escrow else "uniform")
    return harness.run_cell("tiny", seed, seconds, False,
                            bench=tiny_bench(config, chips), mix=mix,
                            devices=jax.devices()[:chips],
                            float_dtype=float_dtype, log=lambda m: None)


def failing(result: dict) -> list[str]:
    """The checks a result reads over their limits."""
    return [k for k, c in result["checks"].items()
            if c["value"] > c["limit"]]
