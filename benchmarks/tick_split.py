"""Split a chip benchmark cell's window into the program's own spans.

    PYTHONPATH=src python3 -m benchmarks.tick_split --workload <cell> \
        --seed <n> --seconds <s>

Runs one cell of ``BENCHMARK.json`` as ``bench/run.py --trace 1`` does
(same set-up, loop, profiler and correctness check), with the program's
spans and counters (``repro.tracing``) on for the measured window only,
and prints one JSON line:

* ``split_us_per_block``: per block the fleet dispatched, ``sched``
  (self time of ``fleet.tick`` and ``fleet.drain``), ``fill``, ``launch``
  (``engine.put`` + ``engine.launch``), ``fetch`` (``engine.wait`` +
  ``engine.fetch``), ``scatter``, and their ``sum`` beside the harness's
  own ``tick_us_per_block`` of the same run;
* ``queue_wait_us_per_row`` and ``enqueue_us_per_row`` (self time of
  ``engine.enqueue`` per admitted row);
* ``compiles_in_window``: jaxpr traces plus backend compiles (0 is a
  reading);
* ``idle_by_span_s``: each device-idle interval in the window put down to
  the innermost program span over it, ``other`` where none is;
* ``idle_share`` of the device in the window, ``program`` (the raw
  snapshot) and ``correct``.

The bulk cells dispatch no fleet blocks: they give only the compiles and
``idle_by_span_s``.  The span names are listed in PERF.md §3.  It exits 2
off-TPU, like ``bench/run.py``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import tempfile
from typing import Dict, List, Sequence, Tuple

from bench import run as brun
from bench import trace as btrace

OTHER = "other"


class ProgramSpans(brun.Spans):
    """The harness's spans, with ``repro.tracing`` on from empty totals
    for the window; :attr:`program` holds what it recorded."""

    program = None

    @contextlib.contextmanager
    def window(self):
        from repro import tracing

        tracing.reset()
        tracing.enable()
        try:
            with super().window():
                yield
        finally:
            self.program = tracing.snapshot()
            tracing.disable()


def program_spans(path: str, names) -> List[list]:
    """The host spans named in ``names`` in the profiler's ``.xplane.pb``,
    as ``[name, start_ns, dur_ns]``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return [[e.name, float(e.start_ns), float(e.duration_ns)]
            for plane in data.planes if plane.name.startswith("/host:")
            for ln in plane.lines for e in ln.events if e.name in names]


def innermost(spans: Sequence[Sequence]) -> List[Tuple[float, float, str]]:
    """Cut nested ``[name, start, dur]`` spans into disjoint ``(start,
    end, name)`` pieces, each named by the innermost span over it."""
    pieces: List[Tuple[float, float, str]] = []
    stack: List[Tuple[str, float]] = []     # (name, end), innermost last
    t = 0.0

    def close_until(limit: float) -> None:
        nonlocal t
        while stack and stack[-1][1] <= limit:
            name, end = stack.pop()
            if end > t:
                pieces.append((t, end, name))
                t = end

    for name, s, d in sorted(spans, key=lambda x: (x[1], -x[2])):
        close_until(s)
        if stack and s > t:
            pieces.append((t, s, stack[-1][0]))
        stack.append((name, s + d))
        t = max(t, s)
    close_until(float("inf"))
    return pieces


def idle_by_span(events: dict, spans: Sequence[Sequence]) -> Dict[str, float]:
    """Device-idle seconds in the window (``bench/trace.py`` events), each
    idle interval put down to the innermost program span over it
    (``other`` where none is), averaged over the device planes that ran
    anything, largest first."""
    wins = [(s, s + d) for n, s, d in events["host"] if n == btrace.WINDOW]
    device = events["device"]
    if not wins or not device:
        return {}
    lo, hi = wins[0]
    pieces = innermost(spans)
    out: Dict[str, float] = {}
    for evs in device.values():
        merged = btrace._clip(btrace.union([(s, s + d) for _, s, d in evs]),
                              lo, hi)
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        idle = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        # both lists are sorted and disjoint: walk them together
        j = 0
        for a, b in idle:
            covered = 0.0
            while j < len(pieces) and pieces[j][1] <= a:
                j += 1
            k = j
            while k < len(pieces) and pieces[k][0] < b:
                ps, pe, name = pieces[k]
                part = min(pe, b) - max(ps, a)
                if part > 0:
                    out[name] = out.get(name, 0.0) + part
                    covered += part
                k += 1
            out[OTHER] = out.get(OTHER, 0.0) + (b - a) - covered
    n = len(device)
    return {k: v / n * 1e-9 for k, v in
            sorted(out.items(), key=lambda kv: -kv[1])}


def split(program: dict, blocks: int, rows_admitted: int) -> dict:
    """The per-block and per-row readings of one window's snapshot; the
    fleet's pieces are left out when no block was dispatched."""
    spans, counters = program["spans"], program["counters"]

    def secs(*names, self_time=False):
        return sum(spans.get(n, (0, 0.0, 0.0))[2 if self_time else 1]
                   for n in names)

    out = {"compiles_in_window": counters.get("compile.traces", 0)
           + counters.get("compile.backend", 0)}
    if blocks:
        per = {"sched": secs("fleet.tick", "fleet.drain", self_time=True),
               "fill": secs("engine.fill"),
               "launch": secs("engine.put", "engine.launch"),
               "fetch": secs("engine.wait", "engine.fetch"),
               "scatter": secs("engine.scatter")}
        per = {k: v / blocks * 1e6 for k, v in per.items()}
        per["sum"] = sum(per.values())
        out["split_us_per_block"] = per
    if counters.get("queue.rows"):
        out["queue_wait_us_per_row"] = (counters["queue.wait_s"]
                                        / counters["queue.rows"] * 1e6)
    if rows_admitted and "engine.enqueue" in spans:
        out["enqueue_us_per_row"] = (secs("engine.enqueue", self_time=True)
                                     / rows_admitted * 1e6)
    return out


def run_split(spec: dict, cell: dict, cfg: dict, mix: dict, *, seed: int,
              seconds: float, require_tpu: bool = True) -> dict:
    """Set up, run traced with the program's spans on, and check one
    cell; return the readings."""
    import jax

    from repro.launch.compile_cache import use_persistent_cache

    if require_tpu:
        brun.devices(int(cell["chips"]))
    use_persistent_cache()
    net, tables, maps = brun.build_network(cfg)
    with tempfile.TemporaryDirectory() as tmp:
        spans = ProgramSpans(tmp)
        if mix["entry"] == "fleet":
            out = brun.serve_fleet(cfg, mix, net, tables, maps, seed,
                                   seconds, spans)
        else:
            out = brun.serve_bulk(cfg, mix, net, seed, seconds, spans)
        path = brun._xplane(tmp)
        events = btrace.load_xplane(path)
        prog = program_spans(path, set(spans.program["spans"]))
    del net
    checks, rows_checked = brun.check(cfg, tables, maps, out)
    correct = (all(checks[k] <= brun.LIMITS[k] for k in brun.LIMITS)
               and rows_checked > 0 and out["failed"] == 0)
    blocks = out["fleet"]["ticks"] if out["fleet"] else 0
    result = {"workload": cell["name"], "seed": seed, "correct": correct,
              "device": jax.devices()[0].device_kind}
    result.update(split(spans.program, blocks, out["rows_admitted"]))
    ctx = {"spans": spans.totals, "fleet": out["fleet"],
           "rows_admitted": out["rows_admitted"]}
    for name in ("tick_us_per_block", "submit_us_per_row"):
        result[name] = brun.reader(name)(ctx)
    result["idle_by_span_s"] = idle_by_span(events, prog)
    result["idle_share"] = btrace.reduce(events)["idle_share"]
    result["program"] = spans.program
    return result


def main(argv=None) -> int:
    """Run the cell named on the command line; print its readings."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    spec, cell, cfg, mix = brun.load_cell(brun.ROOT, args.workload)
    try:
        result = run_split(spec, cell, cfg, mix, seed=args.seed,
                           seconds=args.seconds)
    except brun.NoChip as e:
        print(f"tick_split: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
