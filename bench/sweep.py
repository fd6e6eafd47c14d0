"""Find the knee of an open-loop cell: offer a list of rates in turn to one
fleet, in one process, and print one JSON line per rate.

    python3 bench/sweep.py --workload jsc_openml.trigger --seed 7 \\
        --seconds 3 --rates 2000,4000,8000

At each rate the open loop runs for ``--seconds``; the line gives the rows
offered and answered per second, how far the last answer came after the
window (``overrun_ms``: a queue that grew), the request latency p50/p99
and how late the generator sent (p99).  The knee is the highest rate at
which the answers keep up: no overrun beyond a block's round trip and a
p99 that has not left its plateau.  The rate a cell's mix names is set
from it by hand; the benchmark never searches for one.
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

from bench import arrivals, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second")
    args = ap.parse_args(argv)
    spec, cell, cfg, mix = run.load_cell(run.ROOT, args.workload)
    if mix["loop"] != "open":
        raise SystemExit(f"{args.workload}: not an open loop")
    try:
        run.devices(int(cell["chips"]))
    except run.NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import use_persistent_cache

    use_persistent_cache()
    net, tables, maps = run.build_network(cfg)
    rates = [float(r) for r in args.rates.split(",")]
    scheds = [arrivals.open_schedule(mix, args.seconds, args.seed, rate)
              for rate in rates]
    x = arrivals.input_rows(cfg, max(s.total_rows for s in scheds),
                            args.seed)
    fleet = run.make_fleet(cfg, mix, net, tables, maps, x)
    spans = run.Spans(None)
    for rate, sched in zip(rates, scheds):
        out = run.open_loop(fleet, "cell", x, sched, args.seconds, spans)
        lat = out["latencies_s"]
        print(json.dumps({
            "rate_per_s": rate,
            "rows_offered_per_s": sched.total_rows / args.seconds,
            "rows_answered_per_s": out["rows_answered"] / out["window_s"],
            "overrun_ms": (out["window_s"] - args.seconds) * 1e3,
            "latency_p50_ms": float(np.percentile(lat, 50)) * 1e3,
            "latency_p99_ms": float(np.percentile(lat, 99)) * 1e3,
            "generator_late_p99_ms":
                float(np.percentile(out["late_s"], 99)) * 1e3,
            "failed": out["failed"], "longest_call_s": out["longest_s"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
