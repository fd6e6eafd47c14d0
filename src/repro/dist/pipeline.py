"""Pipeline parallelism: GPipe-style microbatch schedule over a mesh axis.

``make_pipelined_fn`` splits a stack of identical layers across ``n_stages``
devices along ``axis_name`` and streams microbatches through them: stage
``s`` processes microbatch ``m`` at tick ``m + s``, passing activations to
the right neighbor with ``ppermute``.  The schedule runs
``M + n_stages - 1`` ticks for ``M`` microbatches (the classic bubble).
Weights are sharded by stage (layers_per_stage each); activations for one
microbatch are what crosses the wire per tick.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Array = jax.Array


def make_pipelined_fn(layer_fn: Callable[[Array, Array], Array], mesh: Mesh,
                      *, axis_name: str, n_stages: int,
                      layers_per_stage: int):
    """Build ``fn(ws, xs) -> ys``.

    ``ws``: [n_stages * layers_per_stage, ...] stacked layer weights
    (sharded by stage); ``xs``: [n_micro, ...] microbatches (replicated);
    ``ys``: [n_micro, ...] outputs after all layers, replicated.
    """

    def stage_body(ws_local: Array, xs: Array) -> Array:
        s = jax.lax.axis_index(axis_name)
        n_micro = xs.shape[0]
        out = jnp.zeros_like(xs)
        recv = jnp.zeros_like(xs[0])
        fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        for t in range(n_micro + n_stages - 1):
            # stage 0 reads a fresh microbatch; later stages read the wire
            feed = xs[min(t, n_micro - 1)]
            inp = jnp.where(s == 0, feed, recv)
            h = inp
            for i in range(layers_per_stage):
                h = layer_fn(ws_local[i], h)
            m_last = t - (n_stages - 1)
            if 0 <= m_last < n_micro:  # static: t and n_stages are python
                out = jnp.where(s == n_stages - 1, out.at[m_last].set(h),
                                out)
            recv = jax.lax.ppermute(h, axis_name, fwd)
        # only the last stage holds results; broadcast to every shard
        out = jax.lax.psum(
            jnp.where(s == n_stages - 1, out, jnp.zeros_like(out)),
            axis_name)
        return out

    fn = jax.shard_map(stage_body, mesh=mesh,
                       in_specs=(P(axis_name), P()), out_specs=P(),
                       check_vma=False)
    return jax.jit(fn)
