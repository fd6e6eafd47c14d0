"""From a profiler trace to device busy time, idle share and breakdown.

The harness brackets its measured window with a host span named
``window`` and each call into the program with a host span named after
what the host is doing (``SPANS``); ``jax.profiler.TraceAnnotation``
writes them into the same trace, on the device trace's clock.

:func:`load_xplane` turns the profiler's ``.xplane.pb`` into plain events,
``{"device": {plane: [[name, start_ns, dur_ns], ...]}, "host": [...]}``,
and :func:`reduce` works on that form only, so a small recorded fixture
(``bench/tests/trace_fixture.json``) checks it without a chip.
"""
from __future__ import annotations

import bisect
from typing import Dict, List, Sequence, Tuple

WINDOW = "window"
SPANS = ("submit", "tick", "drain", "wait_due", "executor_call", "sync")
# the line of a device plane that holds one event per executed XLA op
OPS_LINE = "XLA Ops"


def load_xplane(path: str) -> dict:
    """Device op events per device plane and the harness's host spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device: Dict[str, List[list]] = {}
    host: List[list] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == OPS_LINE] or lines
            # an op event is named by its whole HLO instruction; keep
            # the instruction's name ("%_streamed_call.1 = ..." ->
            # "_streamed_call.1")
            evs = [[e.name.split(" = ")[0].lstrip("%"), float(e.start_ns),
                    float(e.duration_ns)] for ln in ops for e in ln.events]
            if evs:
                device[plane.name] = evs
        elif plane.name.startswith("/host:"):
            host.extend([e.name, float(e.start_ns), float(e.duration_ns)]
                        for ln in plane.lines for e in ln.events
                        if e.name == WINDOW or e.name in SPANS)
    return {"device": device, "host": host}


def union(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(intervals, lo: float, hi: float) -> List[List[float]]:
    return [[max(a, lo), min(b, hi)] for a, b in intervals
            if b > lo and a < hi]


def _label(spans, starts, a: float, b: float) -> str:
    """The host span that covers most of ``[a, b]``.  The harness's spans
    follow one another and never nest, so the walk back stops at the
    first span that ended before ``a``."""
    best, best_cover = "other", 0.0
    for i in range(bisect.bisect_left(starts, b) - 1, -1, -1):
        name, s, e = spans[i]
        if e <= a:
            break
        cover = min(e, b) - max(s, a)
        if cover > best_cover:
            best, best_cover = name, cover
    return best


def reduce(events: dict, top: int = 10) -> dict:
    """Busy and idle time of the device within the window.

    ``busy_s`` is the union of the device's op intervals inside the
    window, averaged over the device planes that ran anything;
    ``window_s`` the window's length.  ``device_ops`` are the ``top`` ops
    by summed time; ``idle_gaps`` the ``top`` longest gaps in the busy
    union, each named by the host span that covers most of it, and
    ``idle_gaps_at_s`` where each starts in the window."""
    host = events["host"]
    wins = [(s, s + d) for n, s, d in host if n == WINDOW]
    device = events["device"]
    if wins:
        lo, hi = wins[0]
    else:
        allev = [(s, s + d) for evs in device.values() for _, s, d in evs]
        lo, hi = (min(a for a, _ in allev), max(b for _, b in allev)) \
            if allev else (0.0, 0.0)
    spans = sorted(([n, s, s + d] for n, s, d in host if n in SPANS),
                   key=lambda x: x[1])
    starts = [s for _, s, _ in spans]
    busy, ops, gaps = [], {}, []
    for evs in device.values():
        merged = _clip(union([(s, s + d) for _, s, d in evs]), lo, hi)
        busy.append(sum(b - a for a, b in merged))
        for name, s, d in evs:
            if s + d > lo and s < hi:
                ops[name] = ops.get(name, 0.0) + d
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, a))
    gaps.sort(reverse=True)
    idle = [[_label(spans, starts, a, a + g), g * 1e-9]
            for g, a in gaps[:top]]
    idle_at = [(a - lo) * 1e-9 for _, a in gaps[:top]]
    window_s = (hi - lo) * 1e-9
    busy_s = (sum(busy) / len(busy)) * 1e-9 if busy else 0.0
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": 1.0 - busy_s / window_s if window_s else 1.0,
            "device_ops": [[n, t * 1e-9] for n, t in
                           sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": idle, "idle_gaps_at_s": idle_at}
