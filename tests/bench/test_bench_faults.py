"""A run whose timed path is broken underneath reads ``correct`` false:
a step that leaves the tables unchanged, half of each batch left out, an
answer altered where it is produced, and (four chips) the exchange between
chips left out."""

import os
import subprocess
import sys

import jax
import pytest

from benchlib import failing, run_tiny
from repro.txn import tpcc
from repro.txn.executor import FusedExecutor

copy = lambda t: jax.tree.map(lambda x: x.copy(), t)


def unchanged_state(orig):
    def megastep(self, state, ring, counters, *rest):
        before = copy(state)
        out = orig(self, state, ring, counters, *rest)
        return (before,) + tuple(out[1:])
    return megastep


def half_batch(orig):
    def megastep(self, state, ring, counters, *rest):
        *esc, chunk = rest
        half = chunk.neworder.w.shape[1] // 2
        cut = chunk._replace(neworder=jax.tree.map(
            lambda x: x[:, :half], chunk.neworder))
        return orig(self, state, ring, counters, *esc, cut)
    return megastep


@pytest.mark.parametrize("config,method", [
    ("tiny-merge", "megastep"), ("tiny-escrow", "megastep_escrow")])
@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch"])
def test_broken_megastep_is_caught(monkeypatch, config, method, fault):
    orig = getattr(FusedExecutor, method)
    wrap = unchanged_state(orig) if fault == "unchanged_state" \
        else half_batch(orig)
    monkeypatch.setattr(FusedExecutor, method, wrap)
    res = run_tiny(config)
    assert not res["correct"] and failing(res)


@pytest.mark.parametrize("config", ["tiny-merge", "tiny-escrow"])
def test_altered_answer_is_caught(monkeypatch, config):
    """Every order line's item id is shifted by one as New-Order writes
    it: stock and order lines go to the wrong item."""
    for name in ("apply_neworder", "_neworder_fused_effects"):
        orig = getattr(tpcc, name)

        def altered(state, batch, *a, _orig=orig, **k):
            n = state.s_quantity.shape[1]
            return _orig(state, batch._replace(
                i_id=(batch.i_id + 1) % n), *a, **k)
        monkeypatch.setattr(tpcc, name, altered)
    res = run_tiny(config)
    assert not res["correct"] and failing(res)


@pytest.mark.parametrize("config", ["tiny-merge", "tiny-escrow"])
def test_initial_population_left_out_is_caught(monkeypatch, config):
    """The program starts from empty ORDER, NEW-ORDER, ORDER-LINE and
    HISTORY tables where the deployment holds TPC-C's initial rows."""
    from bench import harness
    monkeypatch.setattr(harness, "load_initial", lambda state, *a, **k: state)
    res = run_tiny(config)
    assert not res["correct"] and failing(res)


EXCHANGE = r"""
import sys
sys.path[:0] = ["src", "."]
sys.path.insert(0, "tests/bench")
import jax
from repro.txn.executor import FusedExecutor
from benchlib import run_tiny

assert len(jax.devices()) == 4
sound = run_tiny("tiny-escrow", chips=4)
assert sound["correct"], sound["checks"]

def no_exchange(self, state, ring, esc, alive=None):
    # no chip's outbox reaches another: the drain gathers empty rings
    ring = ring._replace(valid=ring.valid & False)
    return FusedExecutor._orig(self, state, ring, esc, alive)

FusedExecutor._orig = FusedExecutor.drain_refresh
FusedExecutor.drain_refresh = no_exchange
broken = run_tiny("tiny-escrow", chips=4)
print("BROKEN", broken["correct"], broken["checks"])
assert not broken["correct"]
print("EXCHANGE-OK")
"""


def test_missing_exchange_between_chips_is_caught():
    from bench import spec
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", EXCHANGE], cwd=spec.ROOT,
                       env=env, capture_output=True, text=True, timeout=600)
    assert "EXCHANGE-OK" in p.stdout, p.stdout[-2000:] + p.stderr[-3000:]
