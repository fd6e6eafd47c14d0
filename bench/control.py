"""The control: the plain reference computed one precision below the one
the configuration states, put in the program's place.  Its readings must
fail the cell's limits, or the comparison cannot tell a wrong answer.

    python3 bench/control.py --workload mnist.bulk --seeds 1,2,3 --seconds 10

For each seed it draws the network and the inputs a cell's window would
send (the open loop's rows, the closed loop's pool, the bulk ring), answers
them with the control (``reference.control_tables``: every table one
integer type narrower) and compares the answers with the reference exactly
as ``bench/run.py`` compares the program's.  One JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

if __name__ == "__main__":
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[0:1] = [_root, os.path.join(_root, "src")]

import numpy as np  # noqa: E402

from bench import lutnet, reference, run  # noqa: E402


def cell_inputs(cfg: dict, mix: dict, seed: int, seconds: float
                ) -> np.ndarray:
    """The input rows a window of the cell answers from."""
    if mix["entry"] == "fleet":
        return run.fleet_inputs(cfg, mix, seconds, seed)[1]
    import jax

    return np.concatenate(jax.device_get(run.make_ring(cfg, mix, seed)))


def readings(cfg: dict, mix: dict, seed: int, seconds: float) -> dict:
    """The control's compared numbers on one seed, with their limits."""
    tables, maps = lutnet.make_arrays(cfg, int(cfg["network_seed"]))
    x = cell_inputs(cfg, mix, seed, seconds)
    ref = reference.forward(cfg, tables, maps, x)
    low = reference.forward(cfg, reference.control_tables(cfg, tables),
                            maps, x)
    got = reference.compare(cfg, *low, *ref)
    return {"seed": seed, "rows": len(x),
            "control_bits": reference.stored_bits(cfg) // 2,
            **{k: {"value": v, "limit": run.LIMITS[k]}
               for k, v in got.items()},
            "fails": any(v > run.LIMITS[k] for k, v in got.items())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    _, cell, cfg, mix = run.load_cell(run.ROOT, args.workload)
    try:
        run.devices(int(cell["chips"]))
    except run.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(cfg, mix, seed, args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
