"""Ahead-of-time compiles of the serving kernels for a TPU v5e.

Nothing runs on a chip here: the TPU compiler that ships with libtpu
compiles for a *described* v5e (``topologies.get_topology_desc``) and
refuses what the chip's compiler would refuse — unaligned lane offsets,
block shapes that are not lane-legal, more scoped VMEM than a kernel may
use.  Interpret-mode tests (test_kernels.py) cannot see any of that.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and under pytest-xdist every worker imports every test file.  Keep every
such compile in this one file, so one worker loads the library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import paper_tasks
from repro.kernels import autotune
from repro.kernels.lut_cascade import lut_cascade_pallas
from repro.kernels.lut_gather import lut_lookup_pallas

V5E = "TPU v5 lite"
BATCH = 1024
TABLE_II = ("nid", "jsc_cernbox", "jsc_openml", "mnist")


@pytest.fixture(scope="module")
def topo():
    # without this the compiler writes its logs under the temp directory
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    assert topo.devices[0].device_kind == V5E
    return SingleDeviceSharding(topo.devices[0])


def _plan_buffers(task):
    """The fused plan's layers, a zero ``amat`` and tables holding each
    layer's top code (a compile needs shapes and constants, not trained
    values; the top code packs the table words as drawn tables do)."""
    cfg = paper_tasks.task_config(task)
    layers = tuple(autotune.config_layers(cfg))
    total = sum(u for _, u, *_ in layers)
    amat = np.zeros((max(p for p, *_ in layers), total), np.float32)
    tables = np.zeros((total, max(e for _, _, e, *_ in layers)), np.int32)
    for (_, units, _, off, *_), spec in zip(layers, cfg.layers):
        tables[off:off + units] = 2 ** spec.bits - 1
    return layers, amat, tables


def _compile_cascade(task, one_chip, tuning):
    layers, amat, tables = _plan_buffers(task)

    def run(codes):
        return lut_cascade_pallas(codes, amat, tables, layers=layers,
                                  block_b=tuning.block_b, mode=tuning.mode,
                                  unit_tile=tuning.unit_tile,
                                  interpret=False)

    codes = jax.ShapeDtypeStruct((BATCH, layers[0][0]), jnp.int32,
                                 sharding=one_chip)
    return jax.jit(run).lower(codes).compile().as_text()


@pytest.mark.parametrize("task", TABLE_II)
def test_fused_cascade_compiles_at_picked_tuning(task, one_chip):
    """The tuning the planner stamps for a v5e compiles there."""
    layers = autotune.config_layers(paper_tasks.task_config(task))
    tuning = autotune.pick_tuning(layers, device=V5E)
    assert tuning.device_kind == V5E
    assert "tpu_custom_call" in _compile_cascade(task, one_chip, tuning)


@pytest.mark.parametrize("task", TABLE_II)
def test_vmem_model_bounds_compiler_allocation(task, one_chip, monkeypatch):
    """The kernel compiles with its scoped-VMEM limit set to what
    ``lut_cascade.vmem_bytes`` counts for the picked tuning at the
    plan's packed table words: the model is an upper bound, lane padding
    included, and the unpacked count the autotuner filters on bounds it."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from repro.kernels.lut_cascade import slot_bits, vmem_bytes

    layers, _, tables = _plan_buffers(task)
    tuning = autotune.pick_tuning(layers, device=V5E)
    tiling = dict(mode=tuning.mode, block_b=tuning.block_b,
                  unit_tile=tuning.unit_tile)
    limit = vmem_bytes(layers, slots=slot_bits(tables, layers), **tiling)
    assert limit <= vmem_bytes(layers, **tiling)
    assert limit <= autotune.DEVICE_MODELS[V5E]["vmem_bytes"]
    pallas_call = pl.pallas_call

    def limited(*args, **kw):
        kw["compiler_params"] = pltpu.CompilerParams(vmem_limit_bytes=limit)
        return pallas_call(*args, **kw)

    monkeypatch.setattr(pl, "pallas_call", limited)
    jax.clear_caches()          # the kernel's trace must see the limit
    try:
        assert "tpu_custom_call" in _compile_cascade(task, one_chip, tuning)
    finally:
        monkeypatch.undo()
        jax.clear_caches()


def test_mnist_streamed_cascade_compiles(one_chip):
    """mnist's packed buffers outgrow VMEM, so it streams; the streamed
    kernel's per-phase lane offsets must be provably aligned."""
    layers = autotune.config_layers(paper_tasks.task_config("mnist"))
    tuning = autotune.pick_tuning(layers, device=V5E)
    assert tuning.mode == "streamed"
    for block_b in (64, tuning.block_b):
        t = autotune.KernelTuning(mode="streamed", block_b=block_b,
                                  unit_tile=autotune.UNIT_TILE_CANDIDATES[0])
        assert "tpu_custom_call" in _compile_cascade("mnist", one_chip, t)


@pytest.mark.parametrize("units,entries", [(2160, 64), (320, 256)])
def test_per_layer_pallas_lookup_compiles(units, entries, one_chip):
    """The per-layer ``pallas`` backend's lookup at mnist's first layer
    and jsc_cernbox's widest table."""
    table = jax.ShapeDtypeStruct((units, entries), jnp.int32,
                                 sharding=one_chip)
    addr = jax.ShapeDtypeStruct((BATCH, units), jnp.int32,
                                sharding=one_chip)
    fn = jax.jit(lambda t, a: lut_lookup_pallas(t, a, interpret=False))
    assert "tpu_custom_call" in fn.lower(table, addr).compile().as_text()
