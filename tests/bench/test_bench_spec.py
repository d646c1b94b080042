"""BENCHMARK.json keeps the contract, and everything it names is found by
its name: cells, configurations, traffic mixes, metric readers."""

import json
import re

import pytest

from bench import spec
from bench.peaks import PEAKS, UnknownDevice, peaks_for
from bench.traffic.generator import load_mix

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    for p in bench["paths"]:
        assert (spec.ROOT / p).is_dir()
    assert len(json.dumps(bench)) < 64 * 1024


def test_cells_find_their_configuration_and_traffic(bench):
    for cell in bench["workloads"]:
        assert NAME.match(cell["name"]) and cell["chips"] in (1, 4)
        assert len(cell["why"]) <= 200
        cfg = spec.load_config(bench, cell["config"])
        assert cfg["chips"] == cell["chips"]
        assert cfg["n_warehouses"] % cell["chips"] == 0
        load_mix(cell["traffic"])
        assert spec.find_cell(bench, cell["name"]) is cell
    with pytest.raises(KeyError):
        spec.find_cell(bench, "no-such-cell")


def test_configurations(bench):
    for c in bench["configs"]:
        assert NAME.match(c["name"]) and len(c["source"]) <= 200
        cfg = spec.load_config(bench, c["name"])
        assert cfg["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert key in cfg and NAME.match(key)
        assert set(cfg["limits"]) >= {"table_mismatch", "float_gap"}
        used = [w for w in bench["workloads"] if w["config"] == c["name"]]
        assert used, c["name"]


def test_metrics_have_readers(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert callable(spec.load_reader(m["name"]))
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for cell in cells:
        assert spec.cell_metrics(bench, cell, trace=True)
        assert {m["name"] for m in spec.cell_metrics(
            bench, cell, trace=False)} == e2e


def test_peaks_by_device_kind():
    assert peaks_for("TPU v5 lite").hbm_bytes_per_s == 819e9
    assert all(p.source for p in PEAKS.values())
    with pytest.raises(UnknownDevice):
        peaks_for("cpu")
