"""Pallas TPU kernel: batched per-layer L-LUT lookup.

The paper's folded inference is a cascade of table lookups.  On an FPGA the
lookup is free soft logic; on TPU a naive row-gather of tiny table rows is
HBM-latency-bound.  This kernel keeps a tile of the (transposed) table in
VMEM and resolves every address of a ``[block_b, 128]`` tile with a select
scan over the table's entry rows:

    out[b, u] = table[u, e]  for the one entry e == addr[b, u]

Units sit on lanes and entries on sublanes, so every block is lane-dense
(``128``-wide) and no operand is padded.  The grid tiles (batch, units);
each step holds one (addr tile, table tile) pair in VMEM.  The fused
cascade (``lut_cascade``) uses the same lookup inside its launch.

Validated in interpret mode against ``ref.lut_lookup_ref`` (exact integer
equality) by tests/test_kernels.py and compiled for a TPU v5e by
tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.lut_cascade import LANE, SUBLANE, _lookup

Array = jax.Array


def _lut_kernel(addr_ref, table_ref, out_ref, *, n_entries: int):
    out_ref[...] = _lookup(table_ref, addr_ref[...], n_entries)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def lut_lookup_pallas(table: Array, addr: Array, *, block_b: int = 256,
                      interpret: bool = True) -> Array:
    """table: [units, entries] int32, addr: [batch, units] int32.

    VMEM per grid step is the double-buffered ``[block_b, 128]`` address
    and output tiles plus the ``[entries, 128]`` table tile: at 4096
    entries and the default ``block_b`` about 4.5 MiB.
    """
    batch, units = addr.shape
    entries = table.shape[-1]
    block_b = min(block_b, max(SUBLANE, 1 << (batch - 1).bit_length()))
    pb = (-batch) % block_b
    pu = (-units) % LANE
    addr_p = jnp.pad(addr.astype(jnp.int32), ((0, pb), (0, pu)))
    table_t = jnp.pad(table.astype(jnp.int32), ((0, pu), (0, 0))).T
    bb, uu = addr_p.shape

    out = pl.pallas_call(
        functools.partial(_lut_kernel, n_entries=entries),
        grid=(bb // block_b, uu // LANE),
        in_specs=[
            pl.BlockSpec((block_b, LANE), lambda i, j: (i, j)),
            pl.BlockSpec((entries, LANE), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_b, LANE), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bb, uu), jnp.int32),
        interpret=interpret,
    )(addr_p, table_t)
    return out[:batch, :units]
