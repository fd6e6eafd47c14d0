"""Device-mesh placement for lookup backends (DESIGN.md §3).

Planning (``backend.plan``) decides buffer layout; *placement* decides
where the planned cascade runs.  A :class:`Placement` names a
``jax.sharding`` mesh and a strategy, and :func:`place` wraps any
backend's ``run`` so the same :class:`~repro.backends.ExecutionPlan`
executes sharded:

  * ``batch`` — the universal strategy: the batch axis is sharded over the
    mesh's data-parallel axes with ``shard_map`` and every device runs the
    full cascade on its rows.  Rows are independent, so codes are
    bit-identical to the unsharded plan for *every* backend (including the
    fused Pallas cascade, which XLA's SPMD partitioner could not split on
    its own — ``shard_map`` hands each device its local batch shard and
    the kernel never knows).  Ragged batches are zero-padded to the shard
    count (zero rows are valid addresses) and sliced back.

  * ``units`` — for layers whose ``units`` axis dwarfs the batch: each
    device owns a contiguous slice of every layer's units (tables and
    mappings sharded row-wise, padded to the shard count) and codes are
    ``all_gather``-ed at layer boundaries so the next layer's mapping can
    read any previous unit.  Only backends that execute layer-by-layer
    support this (``supports_unit_sharding``); the fused cascade does not
    — its whole point is that layer boundaries never materialize.

``auto`` resolves to ``batch``.  The strategy produces a callable with the
same signature as ``backend.run(plan, ·)`` minus the plan, so
``PlannedExecutor`` treats placed and unplaced execution identically.
"""
from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.dist.sharding import dp_axes

if TYPE_CHECKING:  # pragma: no cover - typing only
    from repro.backends.base import ExecutionPlan, LookupBackend

STRATEGIES = ("auto", "batch", "units")


@dataclasses.dataclass(frozen=True)
class Placement:
    """Where a planned cascade executes: a mesh + a sharding strategy.

    ``axes`` names the mesh axes the sharded dimension (batch rows or
    layer units) is split over; ``None`` picks the mesh's data-parallel
    axes (``pod``/``data``, DESIGN.md §7) and falls back to every mesh
    axis for single-purpose serving meshes with other names.
    """

    mesh: Mesh
    strategy: str = "auto"
    axes: Optional[Tuple[str, ...]] = None

    def __post_init__(self):
        """Validate the strategy name and that ``axes`` exist on the mesh."""
        if self.strategy not in STRATEGIES:
            raise ValueError(
                f"unknown placement strategy {self.strategy!r}; "
                f"one of {STRATEGIES}")
        for a in self.axes or ():
            if a not in self.mesh.axis_names:
                raise ValueError(
                    f"placement axis {a!r} not in mesh axes "
                    f"{self.mesh.axis_names}")

    def resolved_strategy(self) -> str:
        """The concrete strategy (``auto`` resolves to ``batch``)."""
        return "batch" if self.strategy == "auto" else self.strategy

    def resolved_axes(self) -> Tuple[str, ...]:
        """The mesh axes the sharded dimension is split over."""
        if self.axes:
            return tuple(self.axes)
        dp = dp_axes(self.mesh)
        return dp if dp else tuple(self.mesh.axis_names)

    def num_shards(self) -> int:
        """Total shard count (product of the resolved axes' sizes)."""
        n = 1
        for a in self.resolved_axes():
            n *= self.mesh.shape[a]
        return n

    def cache_key(self) -> tuple:
        """Hashable identity for executor caching (meshes are not stable
        dict keys across reconstruction; device ids + layout are)."""
        return (self.resolved_strategy(), self.resolved_axes(),
                tuple(self.mesh.axis_names),
                tuple(self.mesh.shape[a] for a in self.mesh.axis_names),
                tuple(d.id for d in self.mesh.devices.flat))

    def input_sharding(self):
        """The ``NamedSharding`` batch inputs should carry INTO the placed
        cascade (dim 0 split over the resolved axes).

        Feeding an input committed to device 0 into the jitted sharded
        cascade makes XLA reshard it inside every call — on the profiled
        nid config that resharding cost ~6 ms/call and inverted the mesh
        scaling curve (1.75M rows/s unsharded -> 613k at mesh=2).  A
        ``jax.device_put`` onto this sharding BEFORE the call moves the
        same bytes host->shards directly (~0.07 ms) and makes sharded
        throughput scale monotonically; ``PlannedExecutor`` does exactly
        that for divisible batches.
        """
        from jax.sharding import NamedSharding
        return NamedSharding(self.mesh, P(self.resolved_axes()))


def place(backend: "LookupBackend", plan: "ExecutionPlan",
          placement: Placement) -> Callable:
    """Wrap ``backend.run(plan, ·)`` for execution under ``placement``.

    Returns ``run(codes) -> codes`` over *global* arrays: callers (the
    jitted ``PlannedExecutor`` cascade) never see the mesh.
    """
    strategy = placement.resolved_strategy()
    if strategy == "batch":
        return _batch_sharded(backend, plan, placement)
    if not getattr(backend, "supports_unit_sharding", False):
        raise ValueError(
            f"backend {backend.name!r} does not support unit sharding "
            "(it has no per-layer boundaries to all-gather at); use "
            "strategy='batch'")
    return backend.unit_sharded_runner(
        plan, placement.mesh, placement.resolved_axes())


def _batch_sharded(backend: "LookupBackend", plan: "ExecutionPlan",
                   placement: Placement) -> Callable:
    mesh, axes = placement.mesh, placement.resolved_axes()
    n = placement.num_shards()
    spec = P(axes)
    # check_vma=False: the replication checker has no rule for
    # pallas_call; bit-identity tests cover correctness instead
    local = jax.shard_map(lambda c: backend.run(plan, c), mesh=mesh,
                          in_specs=spec, out_specs=spec, check_vma=False)

    def run(codes):
        b = codes.shape[0]
        pad = (-b) % n
        if pad:  # zero rows are valid addresses; sliced off below
            codes = jnp.pad(codes, ((0, pad), (0, 0)))
        out = local(codes)
        return out[:b] if pad else out

    return run


def unit_shard_buffers(layers, get_table, get_mapping, n: int):
    """Pad every layer's unit axis to a multiple of ``n`` shards.

    Shared by unit-sharding implementations: returns the interleaved
    ``[table_0, mapping_0, table_1, ...]`` buffer list whose unit axes all
    divide ``n`` (assemble layers get their contiguous mapping
    materialized so every layer is uniform).  Padded table rows are zeros
    and padded mapping rows point at input 0 — their outputs are sliced
    off after every all-gather.
    """
    bufs = []
    for l, lm in enumerate(layers):
        units, fan_in = lm["units"], lm["fan_in"]
        table = np.asarray(get_table(l))
        if lm["assemble"]:
            mapping = np.arange(units * fan_in,
                                dtype=np.int32).reshape(units, fan_in)
        else:
            mapping = np.asarray(get_mapping(l), np.int32)
        pu = (-units) % n
        bufs.append(np.pad(table, ((0, pu), (0, 0))))
        bufs.append(np.pad(mapping, ((0, pu), (0, 0))))
    return bufs
