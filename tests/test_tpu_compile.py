"""The main-path Pallas kernels through the chip's compiler (Mosaic).

Interpret mode (tests/test_escrow_admission.py, test_megastep_kernel.py)
pins the kernels' semantics; it never checks tiling, SMEM/VMEM placement or
the VMEM budget. Here both kernels compile with ``interpret=False`` for a
described v5e chip (no chip attached) at the chip smoke's spec shapes: one
chip's ``WAREHOUSES`` local warehouses, and one shard of the four-chip run
(whose hot set covers all 4 x ``WAREHOUSES`` warehouses). The megastep
streams its stock slabs, so its VMEM ceiling is the whole-resident
availability vector: it compiles at the most local warehouses its VMEM
budget takes, and one more is refused.

The topology is described inside a fixture, never at import, and every
chip-compiler test lives in this one file.
"""

import math

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from chip_smoke import BATCH_PER_SHARD, WAREHOUSES
from repro.kernels.escrow_admit import escrow_admit_kernel
from repro.kernels.txn_megastep import (MAX_VMEM_BYTES, megastep_vmem_bytes,
                                        txn_megastep_kernel)
from repro.txn.tpcc import TPCCScale, default_hot_items


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec_args(one_chip, w_local: int, n_chips: int):
    """Kernel argument shapes of one shard of the spec-scale sparse escrow
    engine: ``w_local`` warehouses per chip, a hot set over all of them."""
    scale = TPCCScale.spec_scale(w_local * n_chips)
    B, L, I = BATCH_PER_SHARD, scale.max_lines, scale.n_items
    A = scale.n_warehouses * default_hot_items(scale) + w_local * I + 1
    f = lambda shape, dt=jnp.int32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    admit = (f((A,)), f((B, L)), f((B, L)), f((B, L), jnp.bool_),
             f((B,), jnp.bool_), f((B,)), f((1,)))
    effects = (f((B,)), f((B, L)), f((B, L), jnp.bool_),
               f((B, L), jnp.bool_), f((B,)), f((B, L), jnp.float32))
    sizes = dict(n_keys=w_local * scale.districts, n_cells=w_local * I)
    return admit, effects, sizes


def _nbytes(tree) -> int:
    return sum(math.prod(x.shape) * x.dtype.itemsize
               for x in jax.tree.leaves(tree))


def _assert_kernel_compiled(lowered):
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    args, outs = _nbytes(lowered.args_info), _nbytes(lowered.out_info)
    # HBM holds the arguments and results themselves, up to tile padding
    # (< 1%), and at most one padded [rows, 128] copy of a result as temp
    assert args <= mem.argument_size_in_bytes <= 1.01 * args
    assert outs <= mem.output_size_in_bytes <= 1.01 * outs
    assert mem.temp_size_in_bytes <= 1.01 * outs
    return mem


@pytest.mark.parametrize("n_chips", [1, 4])
def test_escrow_admit_kernel_compiles_for_v5e(one_chip, n_chips):
    admit, _, _ = _spec_args(one_chip, WAREHOUSES, n_chips)
    _assert_kernel_compiled(escrow_admit_kernel.lower(*admit))


@pytest.mark.parametrize("n_chips", [1, 4])
def test_txn_megastep_kernel_compiles_for_v5e(one_chip, n_chips):
    admit, effects, sizes = _spec_args(one_chip, WAREHOUSES, n_chips)
    mem = _assert_kernel_compiled(
        txn_megastep_kernel.lower(*admit, *effects, **sizes))
    # the four [rows, 128] VMEM residents (avail + three stock slabs)
    # return whole: at least 16 bytes per local stock cell
    assert mem.output_size_in_bytes >= 16 * sizes["n_cells"]


def _vmem_ceiling_warehouses() -> int:
    """The most local warehouses whose megastep fits ``MAX_VMEM_BYTES``."""
    def fits(w):
        scale = TPCCScale.spec_scale(w)
        n_avail = w * (default_hot_items(scale) + scale.n_items) + 1
        return megastep_vmem_bytes(n_avail) <= MAX_VMEM_BYTES
    w = 1
    while fits(w + 1):
        w += 1
    return w


def test_txn_megastep_refused_past_warehouse_limit(one_chip):
    """The VMEM ceiling is real on both sides: the chip's compiler takes
    the megastep at the most local warehouses the budget holds (hundreds:
    the slabs stream, only avail stays whole), and one local warehouse
    more is refused for VMEM."""
    top = _vmem_ceiling_warehouses()
    assert top >= 256
    admit, effects, sizes = _spec_args(one_chip, top, 1)
    _assert_kernel_compiled(
        txn_megastep_kernel.lower(*admit, *effects, **sizes))
    admit, effects, sizes = _spec_args(one_chip, top + 1, 1)
    with pytest.raises(Exception, match="(?i)vmem"):
        txn_megastep_kernel.lower(*admit, *effects, **sizes).compile()
