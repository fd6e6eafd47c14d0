"""Production mesh construction.

Axis convention (DESIGN.md §7):
  pod   — data-center-network boundary; pure DP (gradient all-reduce only)
  data  — intra-pod FSDP/DP axis
  model — tensor-parallel axis

Defined as functions (never module-level constants) so importing this module
never touches jax device state — smoke tests must keep seeing 1 CPU device.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    # Auto axes: XLA's partitioner places the intermediates, as the code
    # built on these meshes expects (jax.make_mesh defaults to Explicit)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # test hook: REPRO_MESH_SHAPE="2,4" (single) / "2,2,2" (multi) lets CI
    # exercise the identical dry-run path with few placeholder devices.
    import os
    override = os.environ.get(
        "REPRO_MESH_SHAPE_MULTI" if multi_pod else "REPRO_MESH_SHAPE")
    if override:
        shape = tuple(int(x) for x in override.split(","))
        assert len(shape) == len(axes), (shape, axes)
    return _auto_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    return _auto_mesh(shape, axes)


def make_host_mesh() -> Mesh:
    """1x1 (data, model) mesh on whatever single device is present —
    used by smoke tests and CPU examples so the same pjit code paths run."""
    return _auto_mesh((1, 1), ("data", "model"))


def make_serving_mesh(devices: int = 0) -> Mesh:
    """1-D ``("data",)`` mesh over the first ``devices`` host devices (all
    of them when 0) — LUT serving placement (DESIGN.md §3) is pure batch
    data-parallelism, so the mesh is a flat DP axis."""
    import numpy as np
    devs = jax.devices()
    n = devices or len(devs)
    if n > len(devs):
        raise ValueError(f"asked for {n} devices, have {len(devs)}")
    return Mesh(np.asarray(devs[:n]), ("data",))


def mesh_devices(mesh: Mesh) -> int:
    n = 1
    for a in mesh.axis_names:
        n *= mesh.shape[a]
    return n
