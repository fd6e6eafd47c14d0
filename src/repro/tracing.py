"""Program spans and counters at the fleet and engine boundaries.

Off by default.  Off, :func:`span` returns one shared
``contextlib.nullcontext()`` and :func:`count` returns at once: no clock
is read and no per-row work is done.  :func:`enable` turns both on for
the whole process (the serving path is single-threaded, like the fleet):

* each span opens a ``jax.profiler.TraceAnnotation`` of its name, so it
  lands on the device trace's clock whenever the profiler runs, and adds
  to in-memory totals per name: count, total seconds and self seconds
  (total less the time covered by spans opened inside it);
* each counter adds to a total per name;
* a ``jax.monitoring`` listener counts jaxpr traces into
  ``compile.traces`` and backend compiles (cache hits included) into
  ``compile.backend``.

Time is ``time.perf_counter``, never the fault injector's skewable
clock.  Totals stay in memory until :func:`snapshot` reads them:

    tracing.reset(); tracing.enable()
    ...                                   # the window
    got = tracing.snapshot(); tracing.disable()
    got["spans"]["engine.fetch"]          # [count, total_s, self_s]
    got["counters"]["queue.rows"]

The span and counter names, and the metric that reads each, are listed
in PERF.md §3.
"""
from __future__ import annotations

import contextlib
from time import perf_counter
from typing import Dict, List

import jax
from jax.profiler import TraceAnnotation

_NULL = contextlib.nullcontext()
_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.traces",
    "/jax/core/compile/backend_compile_duration": "compile.backend",
}

_on = False
_spans: Dict[str, List[float]] = {}
_counters: Dict[str, float] = {}
# the spans open now, innermost last: each gathers its children's time
_open: List["_Span"] = []


class _Span:
    __slots__ = ("name", "note", "t0", "inner")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.note = TraceAnnotation(self.name)
        self.note.__enter__()
        self.inner = 0.0
        _open.append(self)
        self.t0 = perf_counter()
        return self

    def __exit__(self, *exc):
        dt = perf_counter() - self.t0
        _open.pop()
        if _open:
            _open[-1].inner += dt
        tot = _spans.get(self.name)
        if tot is None:
            tot = _spans[self.name] = [0, 0.0, 0.0]
        tot[0] += 1
        tot[1] += dt
        tot[2] += dt - self.inner
        self.note.__exit__(*exc)
        return False


def enabled() -> bool:
    """Whether spans and counters record."""
    return _on


def span(name: str):
    """A context manager spanning one piece of the serving path."""
    return _Span(name) if _on else _NULL


def count(name: str, n: float = 1) -> None:
    """Add ``n`` to counter ``name`` (nothing when off)."""
    if _on:
        _counters[name] = _counters.get(name, 0) + n


def _on_compile(event: str, duration: float, **_kw) -> None:
    name = _COMPILE_EVENTS.get(event)
    if name is not None:
        count(name)


def enable() -> None:
    """Record spans, counters and compiles from now on."""
    global _on
    if not _on:
        jax.monitoring.register_event_duration_secs_listener(_on_compile)
        _on = True


def disable() -> None:
    """Stop recording; what was recorded stays until :func:`reset`."""
    global _on
    if _on:
        jax.monitoring.unregister_event_duration_listener(_on_compile)
        _on = False


def reset() -> None:
    """Forget every total."""
    _spans.clear()
    _counters.clear()


def snapshot() -> dict:
    """``{"spans": {name: [count, total_s, self_s]}, "counters": {name:
    value}}``, copied."""
    return {"spans": {k: list(v) for k, v in _spans.items()},
            "counters": dict(_counters)}
