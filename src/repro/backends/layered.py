"""Per-layer backends: N dispatches, one ``ops.lut_lookup`` per layer.

These adapt the pre-PR-2 execution strategy ('take' / 'onehot' / 'pallas'
impl strings) to the :class:`LookupBackend` contract, so the strings keep
working everywhere through the registry.  The plan is a straight extraction
of the folded network's per-layer tables + mappings; ``run`` replays the
cascade exactly as ``folding.folded_apply_codes`` always has, so these
remain the bit-exactness oracles for the fused backend.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.backends.base import (BackendCapabilities, ExecutionPlan,
                                 LookupBackend, require_mappings)
from repro.backends.registry import register


class LayeredBackend(LookupBackend):
    """Cascade executed layer-by-layer via ``kernels.ops.lut_lookup``."""

    plan_format = "layered-v1"
    persist_plan = False  # plan is a verbatim copy of the base arrays
    supports_unit_sharding = True  # per-layer boundaries to all-gather at

    def __init__(self, impl: str):
        """``impl`` is the ``ops.lut_lookup`` kernel name; also the
        registry name this backend serves under."""
        self._impl = impl
        self.name = impl

    def capabilities(self) -> BackendCapabilities:
        """Describe this per-layer execution strategy for sweeps."""
        desc = {
            "take": "vectorized table[u, addr] gather (pure jnp oracle)",
            "onehot": "one-hot x table MXU matmul in pure jnp",
            "pallas": "VMEM-tiled one-hot matmul kernel, one launch/layer",
        }[self._impl]
        return BackendCapabilities(name=self.name, fused=False,
                                   needs_pallas=self._impl == "pallas",
                                   description=desc, unit_shardable=True)

    def plan(self, net) -> ExecutionPlan:
        """Verbatim extraction of the per-layer tables + mappings (no
        repacking; that is why these plans are not persisted)."""
        require_mappings(net, f"{self.name}.plan")
        cfg = net.cfg
        layers = []
        buffers: Dict[str, np.ndarray] = {}
        for l, spec in enumerate(cfg.layers):
            layers.append({"units": spec.units, "fan_in": spec.fan_in,
                           "bits": cfg.in_bits(l), "assemble": spec.assemble})
            buffers[f"table_{l}"] = np.asarray(net.tables[l], np.int32)
            if not spec.assemble:
                buffers[f"mapping_{l}"] = np.asarray(net.mappings[l],
                                                     np.int32)
        return ExecutionPlan(backend=self.name,
                             meta={"impl": self._impl, "layers": layers},
                             buffers=buffers)

    def run(self, plan: ExecutionPlan, codes: Any):
        """Replay the cascade layer by layer: mapping gather ->
        ``quant.pack_address`` -> one ``ops.lut_lookup`` per layer."""
        from repro.core import quant
        from repro.kernels import ops
        codes = jnp.asarray(codes)
        for l, lm in enumerate(plan.meta["layers"]):
            if lm["assemble"]:
                ci = codes.reshape(codes.shape[0], lm["units"], lm["fan_in"])
            else:
                ci = codes[:, jnp.asarray(plan.buffers[f"mapping_{l}"])]
            addr = quant.pack_address(ci, lm["bits"], lm["fan_in"])
            codes = ops.lut_lookup(jnp.asarray(plan.buffers[f"table_{l}"]),
                                   addr, impl=plan.meta["impl"])
        return codes

    def unit_sharded_runner(self, plan: ExecutionPlan, mesh, axes):
        """Units-sharded cascade: each device owns a row-slice of every
        layer's table/mapping, computes its slice of the layer's codes,
        and the full code vector is re-assembled by ``all_gather`` at the
        layer boundary (the next layer's mapping may read any unit).

        The final layer skips the in-kernel gather: ``shard_map``
        concatenates the local slices via ``out_specs=P(None, axes)``,
        which sidesteps replication checks on the output.
        """
        from jax.sharding import PartitionSpec as P

        from repro.backends.placement import unit_shard_buffers
        from repro.core import quant
        from repro.kernels import ops

        layers = plan.meta["layers"]
        impl = plan.meta["impl"]
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        bufs = unit_shard_buffers(
            layers, lambda l: plan.buffers[f"table_{l}"],
            lambda l: plan.buffers[f"mapping_{l}"], n)
        meta = tuple((lm["units"], lm["fan_in"], lm["bits"])
                     for lm in layers)
        ax = tuple(axes)

        def local(codes, *shards):
            for li, (units, fan_in, bits) in enumerate(meta):
                table, mapping = shards[2 * li], shards[2 * li + 1]
                ci = codes[:, mapping]               # [B, up, F] local gather
                addr = quant.pack_address(ci, bits, fan_in)
                out = ops.lut_lookup(table, addr, impl=impl)   # [B, up]
                if li == len(meta) - 1:
                    return out                       # assembled by out_specs
                codes = jax.lax.all_gather(
                    out, ax, axis=1, tiled=True)[:, :units]
            return codes  # pragma: no cover - loop always returns

        sharded = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(),) + (P(ax, None),) * len(bufs),
            out_specs=P(None, ax), check_vma=False)
        n_out = meta[-1][0]
        consts = tuple(jnp.asarray(b) for b in bufs)

        def run(codes):
            return sharded(codes, *consts)[:, :n_out]

        return run


register("take", lambda: LayeredBackend("take"))
register("onehot", lambda: LayeredBackend("onehot"))
register("pallas", lambda: LayeredBackend("pallas"))
