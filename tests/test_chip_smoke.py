"""chip_smoke.py off the chip: it refuses to report without a TPU, refuses
to run outside a checkout, and its phases hold on CPU at a tiny size (the
same checks the chip run makes at spec scale, minus the kernel's
``tpu_custom_call``, which only the chip's compiler emits)."""

import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import chip_smoke
from repro.txn.tpcc import TPCCScale

ROOT = Path(chip_smoke.__file__).resolve().parent


def _run(script: Path, cwd: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_smoke_fails_without_tpu():
    out = _run(ROOT / "chip_smoke.py", ROOT)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr


def test_smoke_fails_outside_checkout(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    out = _run(tmp_path / "chip_smoke.py", tmp_path)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


@pytest.fixture
def tiny_smoke(monkeypatch):
    monkeypatch.setattr(chip_smoke, "BATCH_PER_SHARD", 16)
    monkeypatch.setitem(chip_smoke.ESCROW_RUN, "batch_per_shard", 16)
    monkeypatch.setitem(chip_smoke.ESCROW_RUN, "n_batches", 6)
    monkeypatch.setitem(chip_smoke.ESCROW_RUN, "merge_every", 2)
    monkeypatch.setitem(chip_smoke.MERGE_RUN, "batch_per_shard", 16)
    monkeypatch.setitem(chip_smoke.MERGE_RUN, "n_batches", 4)
    monkeypatch.setitem(chip_smoke.MERGE_RUN, "merge_every", 2)
    return lambda w: TPCCScale(n_warehouses=w, districts=2, customers=8,
                               n_items=64, order_capacity=256, max_lines=15)


def test_smoke_phases_on_cpu(tiny_smoke, capsys):
    """Escrow kernel-vs-scan bit-identity + strict audit, then the merge
    regime's twelve criteria, through the script's own phase functions."""
    chip_smoke.escrow_phase(tiny_smoke(2), 1)
    chip_smoke.merge_phase(tiny_smoke(2))
    out = capsys.readouterr().out
    assert "integer tables bit-identical" in out
    assert "consistency 12/12" in out
