"""The work a folded network requires, from its configuration alone.

Counted from the ``AssembleConfig`` fields, never from how a kernel does
it, so a change that swaps the address matmul for a gather, or the select
scan for a mux tree, is judged against the same work:

* operations: per unit and row, ``fan_in`` multiply-adds (2 operations
  each) to pack the address, plus one table read;
* bytes: every table entry at the width of its code (``bits`` of its
  layer) once per batch of the cell's batch size, plus each row's input
  and output codes at their bit widths.
"""
from __future__ import annotations

from bench.lutnet import layer_shapes


def ops_per_row(cfg: dict) -> int:
    """Operations one row needs."""
    return sum(s["units"] * (2 * s["fan_in"] + 1) for s in layer_shapes(cfg))


def table_bytes(cfg: dict) -> float:
    """Bytes of every table entry at its code width."""
    return sum(s["units"] * s["entries"] * s["bits"]
               for s in layer_shapes(cfg)) / 8


def io_bytes_per_row(cfg: dict) -> float:
    """Bytes of one row's input and output codes at their bit widths."""
    last = layer_shapes(cfg)[-1]
    return (int(cfg["in_features"]) * int(cfg["input_bits"])
            + last["units"] * last["bits"]) / 8


def required(cfg: dict, rows: float, batch: int):
    """(operations, bytes) that ``rows`` rows need, served in batches of
    ``batch`` rows."""
    return (ops_per_row(cfg) * rows,
            table_bytes(cfg) * rows / batch + io_bytes_per_row(cfg) * rows)


def required_seconds(cfg: dict, rows: float, batch: int, peaks: dict
                     ) -> float:
    """The least time the chip could take: the larger of operations over
    peak int8 operations and bytes over HBM bandwidth."""
    ops, nbytes = required(cfg, rows, batch)
    return max(ops / peaks["int8_ops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
