"""The folded network a cell serves, drawn from a seed.

A folded NeuraLUT-Assemble network is its L-LUT tables, the mapping
layers' input choices and two boundary scales.  The benchmark draws the
tables and mappings itself, on the device in one jitted call, from the
configuration's ``network_seed``, and hands the same arrays to the
program (as a ``CompiledLUTNetwork``) and to the plain reference
(``bench/reference.py``), so the reference takes nothing that the program
made.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np


def layer_shapes(cfg: dict) -> List[dict]:
    """Per layer: input width, units, fan-in, input and output code bits,
    and the table's entry count ``2 ** (in_bits * fan_in)``."""
    out, prev, in_bits = [], int(cfg["in_features"]), int(cfg["input_bits"])
    for spec in cfg["layers"]:
        fan_in = int(spec["fan_in"])
        out.append({"prev": prev, "units": int(spec["units"]),
                    "fan_in": fan_in, "in_bits": in_bits,
                    "bits": int(spec["bits"]),
                    "entries": 2 ** (in_bits * fan_in),
                    "assemble": bool(spec["assemble"])})
        prev, in_bits = int(spec["units"]), int(spec["bits"])
    return out


def key_data(seed: int) -> np.ndarray:
    """Raw threefry key data for a seed of any size (``PRNGKey`` keeps
    only the low 32 bits of a wider seed)."""
    return np.random.SeedSequence(int(seed) % 2**64).generate_state(
        2, np.uint32)


def make_arrays(cfg: dict, seed: int
                ) -> Tuple[List[np.ndarray], List[Optional[np.ndarray]]]:
    """Tables ``[units, entries]`` and mappings ``[units, fan_in]`` (None
    for assemble layers) as int32 host arrays, drawn on the device."""
    import jax
    import jax.numpy as jnp

    shapes = layer_shapes(cfg)

    @jax.jit
    def draw(raw):
        keys = jax.random.split(jax.random.wrap_key_data(raw), 2 * len(shapes))
        tables, maps = [], []
        for l, s in enumerate(shapes):
            tables.append(jax.random.randint(
                keys[2 * l], (s["units"], s["entries"]), 0, 2 ** s["bits"],
                jnp.int32))
            maps.append(None if s["assemble"] else jax.random.randint(
                keys[2 * l + 1], (s["units"], s["fan_in"]), 0, s["prev"],
                jnp.int32))
        return tables, maps

    tables, maps = jax.device_get(draw(jnp.asarray(key_data(seed))))
    return ([np.asarray(t) for t in tables],
            [None if m is None else np.asarray(m) for m in maps])


def network_config(cfg: dict) -> dict:
    """The ``AssembleConfig`` fields of a configuration file."""
    keys = ("in_features", "input_bits", "layers", "subnet_width",
            "subnet_depth", "skip_step", "tree_skips", "input_signed",
            "poly_degree")
    return {k: cfg[k] for k in keys}
