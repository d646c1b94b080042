"""Finds what ``BENCHMARK.json`` names: cells, configurations, traffic mixes
and metric readers, each by its name.

A later change adds a cell, a configuration, a traffic mix or a metric by
adding files and entries only: a configuration is the JSON file its entry
names, a traffic mix is ``bench/traffic/<traffic>.json``, and a metric is
``bench/metrics/<name>.py`` with a ``read(record)`` function.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
METRICS_DIR = Path(__file__).resolve().parent / "metrics"


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _named(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}; known: "
                   f"{[e['name'] for e in entries]}")


def find_cell(bench: dict, name: str) -> dict:
    return _named(bench["workloads"], name, "workload")


def load_config(bench: dict, name: str, root: Path = ROOT) -> dict:
    """The configuration's file, with its name added."""
    entry = _named(bench["configs"], name, "configuration")
    with open(root / entry["file"]) as f:
        cfg = json.load(f)
    cfg["name"] = name
    return cfg


def cell_metrics(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports: the end-to-end ones with
    ``trace`` off, the per-layer ones with it on; an entry with a
    ``workloads`` key applies only to the cells it lists."""
    entries = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in entries
            if "workloads" not in m or cell in m["workloads"]]


def load_reader(name: str, directory: Path = METRICS_DIR):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    path = directory / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    if spec is None or not path.exists():
        raise KeyError(f"no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
