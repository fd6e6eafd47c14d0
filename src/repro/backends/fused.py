"""The fused cascade backend: whole network, one launch, autotuned.

Planning packs the folded network into the constant buffers the
``kernels.lut_cascade`` implementations want (plan schema v2,
``plan_format="fused-packed-v2"``):

  * ``amat [max_prev, total_units] f32`` — per-layer address-formation
    matrices (mapping gather + bit-packing folded into one matmul each;
    assemble layers become the contiguous mapping).
  * ``tables [total_units, max_entries]`` — every layer's table, packed
    row-wise at the same unit offsets, narrowed to int8/int16 when the
    largest output bit-width allows (codes are unsigned, < 2^beta).
  * ``map_<l> [units, fan_in] int32`` — the raw per-layer mappings
    (non-assemble layers only), new in v2: the XLA flat-gather path
    gathers codes directly instead of forming addresses by matmul.

v2 ``meta`` additions: 7-wide layer tuples ``(prev, units, entries, off,
fan_in, in_bits, assemble)`` and a ``tuning`` block — the persisted
:class:`~repro.kernels.autotune.KernelTuning` that picks the
implementation and tile shape at run time (docs/KERNELS.md §5).  v1 plans
restored from old ``.npz`` artifacts are upgraded in place by
:meth:`FusedCascadeBackend.migrate_plan`: buffers are reused verbatim
(predictions stay bit-identical), the v2 metadata is rebuilt from the
network config, and the tuning block defaults.

Exactness constraint: addresses accumulate in f32 on the MXU, so every
layer needs ``in_bits * fan_in <= 24`` (integers below 2^24 are exact in
f32).  The paper's configs max out at 12; planning raises otherwise.
"""
from __future__ import annotations

import copy
import dataclasses
from typing import Any, Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from repro.backends.base import (BackendCapabilities, ExecutionPlan,
                                 LookupBackend, require_mappings)
from repro.backends.registry import register
from repro.kernels import autotune

MAX_ADDR_BITS = 24
PLAN_SCHEMA = 2


def _table_dtype(max_bits: int) -> np.dtype:
    """Narrowest signed dtype that holds codes of ``max_bits`` bits."""
    if max_bits <= 7:
        return np.dtype(np.int8)
    if max_bits <= 15:
        return np.dtype(np.int16)
    return np.dtype(np.int32)


def _layer_meta_v2(cfg, tables) -> List[List[int]]:
    """The v2 7-wide layer tuples from a config + concrete tables."""
    layers, off = [], 0
    for l, spec in enumerate(cfg.layers):
        layers.append([cfg.prev_width(l), spec.units,
                       int(np.asarray(tables[l]).shape[1]), off,
                       spec.fan_in, cfg.in_bits(l), int(spec.assemble)])
        off += spec.units
    return layers


@register("fused")
class FusedCascadeBackend(LookupBackend):
    """Single-launch whole-cascade execution with a persisted tuning."""

    name = "fused"
    plan_format = "fused-packed-v2"

    def capabilities(self) -> BackendCapabilities:
        """Describe the fused backend for sweeps and decision tables."""
        # unit_shardable stays False: the fused kernel's whole point is
        # that layer boundaries never materialize, so there is nowhere to
        # all-gather; mesh execution uses batch sharding (placement.py).
        return BackendCapabilities(
            name=self.name, fused=True, needs_pallas=True,
            description="whole-network cascade in one launch; bit-packed "
                        "tables, matmul address formation, autotuned "
                        "resident/streamed Pallas tiling on TPU and a "
                        "flat-gather XLA path elsewhere")

    def plan(self, net) -> ExecutionPlan:
        """Pack the folded ``net`` into the v2 fused plan.

        Validates the f32-exactness bound, packs ``amat``/``tables``/
        ``map_<l>``, and stamps the roofline-model tuning for the current
        device kind (``autotune.default_tuning``) into ``meta["tuning"]``.
        """
        require_mappings(net, "fused.plan")
        cfg = net.cfg
        # validate BEFORE allocating: one over-wide layer would otherwise
        # size the packed buffers at 2^addr_bits columns (GiBs) first
        for l, spec in enumerate(cfg.layers):
            if cfg.in_bits(l) * spec.fan_in > MAX_ADDR_BITS:
                raise ValueError(
                    f"fused.plan: layer {l} address width "
                    f"{cfg.in_bits(l) * spec.fan_in}b exceeds the f32-exact "
                    f"limit ({MAX_ADDR_BITS}b); use a per-layer backend")
        layers = _layer_meta_v2(cfg, net.tables)
        total_units = sum(lm[1] for lm in layers)
        max_prev = max(lm[0] for lm in layers)
        max_entries = max(lm[2] for lm in layers)
        max_bits = max(spec.bits for spec in cfg.layers)

        amat = np.zeros((max_prev, total_units), np.float32)
        tables = np.zeros((total_units, max_entries),
                          _table_dtype(max_bits))
        buffers: Dict[str, np.ndarray] = {"amat": amat, "tables": tables}
        for l, spec in enumerate(cfg.layers):
            prev, units, _, off, fan_in, bits, _ = layers[l]
            if spec.assemble:
                mapping = np.arange(prev, dtype=np.int64).reshape(
                    units, fan_in)
            else:
                mapping = np.asarray(net.mappings[l], np.int64)
                buffers[f"map_{l}"] = mapping.astype(np.int32)
            # addr = codes @ A with A[p, u] = sum_f 2^{bits(F-1-f)}[map=p];
            # add.at accumulates duplicate fan-in indices correctly.
            weights = 2.0 ** (bits * np.arange(fan_in - 1, -1, -1))
            for f in range(fan_in):
                np.add.at(amat, (mapping[:, f], off + np.arange(units)),
                          weights[f])
            table = np.asarray(net.tables[l])
            tables[off:off + units, :table.shape[1]] = table

        tuning = autotune.default_tuning(layers,
                                         table_dtype=tables.dtype.name)
        meta: Dict[str, Any] = {
            "schema": PLAN_SCHEMA,
            "layers": layers,
            "table_dtype": tables.dtype.name,
            "vmem_bytes": int(amat.nbytes + tables.nbytes),
            "input_span": 2 ** cfg.in_bits(0),
            "tuning": tuning.to_meta(),
        }
        return ExecutionPlan(backend=self.name, meta=meta, buffers=buffers)

    def migrate_plan(self, plan: ExecutionPlan,
                     net) -> Optional[ExecutionPlan]:
        """Upgrade a v1 ``fused-packed`` plan to the v2 schema in place.

        The v1 ``amat``/``tables`` buffers are kept verbatim (so restored
        artifacts predict bit-identically), the 4-wide layer tuples are
        extended from the network config, the per-layer mapping buffers
        are added from ``net.mappings``, and the tuning block defaults.
        Returns ``None`` (forcing a fresh re-plan) when the plan is not a
        recognizable v1 fused plan or its buffers do not match ``net``.
        """
        if plan.meta.get("plan_format") != "fused-packed-v1":
            return None
        if not {"amat", "tables"} <= set(plan.buffers):
            return None
        cfg = net.cfg
        layers = _layer_meta_v2(cfg, net.tables)
        old = [list(map(int, lm)) for lm in plan.meta.get("layers", [])]
        if old != [lm[:4] for lm in layers]:
            return None  # different network: let planning start over
        total_units = sum(lm[1] for lm in layers)
        max_prev = max(lm[0] for lm in layers)
        max_entries = max(lm[2] for lm in layers)
        amat, tables = plan.buffers["amat"], plan.buffers["tables"]
        if (amat.shape != (max_prev, total_units)
                or tables.shape != (total_units, max_entries)):
            return None
        buffers = dict(plan.buffers)
        for l, spec in enumerate(cfg.layers):
            if not spec.assemble:
                buffers[f"map_{l}"] = np.asarray(net.mappings[l], np.int32)
        tuning = autotune.default_tuning(layers,
                                         table_dtype=tables.dtype.name)
        meta = dict(plan.meta)
        meta.update(schema=PLAN_SCHEMA, layers=layers,
                    input_span=2 ** cfg.in_bits(0),
                    tuning=tuning.to_meta(),
                    plan_format=self.plan_format)
        return ExecutionPlan(backend=self.name, meta=meta, buffers=buffers)

    def autotune_plan(self, plan: ExecutionPlan, *, rows: int = 2048,
                      reps: int = 3, seed: int = 0,
                      candidates=None) -> ExecutionPlan:
        """Measurement-driven tuning: time the roofline-ranked candidate
        grid on synthetic codes and stamp the winner into a copy of
        ``plan`` (``tuning.source == "measured"``).

        The returned plan replaces the original in
        ``CompiledLUTNetwork._plans`` when called through
        ``benchmarks``/operator tooling, and persists through ``save``.
        """
        import jax

        layers = [tuple(map(int, lm)) for lm in plan.meta["layers"]]
        if candidates is None:
            candidates = autotune.measurement_grid(
                layers, table_dtype=plan.meta["table_dtype"])
        # what is timed here is this device's choice (see device_tuning)
        kind = autotune.device_kind()
        candidates = [dataclasses.replace(c, device_kind=kind)
                      for c in candidates]
        span = int(plan.meta.get("input_span", 2))
        codes = jnp.asarray(np.random.RandomState(seed).randint(
            0, span, size=(rows, layers[0][0])), jnp.int32)

        def factory(tuning: autotune.KernelTuning):
            trial = copy.copy(plan)
            trial.meta = dict(plan.meta, tuning=tuning.to_meta())
            run = jax.jit(lambda c: self.run(trial, c))
            return lambda: jax.block_until_ready(run(codes))

        winner, report = autotune.measure_tuning(factory, candidates,
                                                 reps=reps)
        out = copy.copy(plan)
        out.meta = dict(plan.meta, tuning=winner.to_meta(),
                        tuning_report=report)
        return out

    def device_tuning(self, plan: ExecutionPlan) -> autotune.KernelTuning:
        """The tuning ``plan`` runs with on this process's device.

        A tuning picked for another ``device_kind`` (a plan made on a CPU
        host and served from a TPU) is re-picked for this device, and the
        new choice is written back into ``plan.meta`` so a later ``save``
        records what ran."""
        tuning = autotune.KernelTuning.from_meta(plan.meta.get("tuning"))
        fresh = autotune.for_device(tuning, plan.meta["layers"],
                                    table_dtype=plan.meta.get("table_dtype"))
        if fresh is not tuning:
            plan.meta["tuning"] = fresh.to_meta()
        return fresh

    def run(self, plan: ExecutionPlan, codes: Any):
        """Execute the cascade with the plan's tuning for this device (the
        ``ops.lut_cascade`` dispatcher picks Pallas vs XLA from it)."""
        from repro.kernels import ops
        tuning = self.device_tuning(plan)
        layers = tuple(tuple(int(v) for v in l) for l in plan.meta["layers"])
        # the XLA path needs a mapping for every non-assemble layer; fall
        # back to Pallas-only dispatch when any is missing (foreign plan)
        mappings = None
        if (all(len(l) >= 7 for l in layers)
                and all(l[6] or f"map_{i}" in plan.buffers
                        for i, l in enumerate(layers))):
            mappings = tuple(
                jnp.asarray(plan.buffers[f"map_{l}"], jnp.int32)
                if f"map_{l}" in plan.buffers else None
                for l in range(len(layers)))
        return ops.lut_cascade(jnp.asarray(codes, jnp.int32),
                               plan.buffers["amat"], plan.buffers["tables"],
                               layers=layers, mappings=mappings,
                               tuning=tuning)
