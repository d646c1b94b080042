"""The one-chip escrow deployment ``tpcc-escrow-w96``: its admission
problem fits the megastep's VMEM budget, and it is sound against the plain
reference at a size a CPU run holds (the same file shrunk in cardinality
only)."""

import json

import jax
import pytest

from bench import harness, spec
from repro.kernels.txn_megastep import MAX_VMEM_BYTES, megastep_vmem_bytes

from benchlib import failing, tiny_mix

CELL = "escrow-w96-zipf1"
# cardinalities a CPU run holds; widths, districts, engine and limits stay
SHRUNK = dict(n_warehouses=2, customers=8, n_items=64, order_capacity=256,
              initial_orders_per_district=6,
              initial_new_orders_per_district=2)


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def _config(bench):
    return spec.load_config(bench, spec.find_cell(bench, CELL)["config"])


def test_one_chip_admission_fits_the_kernel_vmem(bench):
    """Every warehouse is local to the one chip, so avail holds the hot
    slots and all local stock; the kernel keeps it whole in VMEM."""
    cfg = _config(bench)
    assert cfg["chips"] == 1 and cfg["reduced"] == ["n_warehouses"]
    W = cfg["n_warehouses"]
    n_avail = W * (cfg["engine"]["hot_items"] + cfg["n_items"]) + 1
    assert megastep_vmem_bytes(n_avail) <= MAX_VMEM_BYTES


def _run_shrunk(bench, tmp_path, float_dtype=None):
    cfg = _config(bench)
    cfg.update(SHRUNK)
    cfg["engine"] = dict(cfg["engine"], hot_items=4)
    path = tmp_path / "tpcc-escrow-w96-shrunk.json"
    path.write_text(json.dumps(cfg))
    b = json.loads(json.dumps(bench))
    b["configs"].append({"name": "shrunk", "file": str(path)})
    b["workloads"].append({"name": "shrunk", "config": "shrunk",
                           "traffic": "tiny", "chips": 1})
    return harness.run_cell("shrunk", 2**32 + 15, 0.05, False, bench=b,
                            mix=tiny_mix("zipf"), devices=jax.devices()[:1],
                            float_dtype=float_dtype, log=lambda m: None)


def test_shrunk_run_is_correct(bench, tmp_path):
    res = _run_shrunk(bench, tmp_path)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0


def test_shrunk_bf16_control_fails_on_float_gap(bench, tmp_path):
    res = _run_shrunk(bench, tmp_path, float_dtype="bfloat16")
    assert not res["correct"]
    assert "float_gap" in failing(res)
