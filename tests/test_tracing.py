"""Program spans and counters (``repro.tracing``).

Off, a span is one shared null context and nothing is recorded; on,
nested spans give self time, a fleet gives one of each engine span per
dispatched block, compiles are counted, and a dispatch that raises still
closes its spans.
"""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import pipeline, tracing
from repro.configs import paper_tasks
from repro.core import assemble
from repro.serve import LUTFleet, make_reference
from repro.serve.lut_engine import LUTEngine

ENGINE_SPANS = ("engine.fill", "engine.put", "engine.launch", "engine.wait",
                "engine.fetch", "engine.scatter")


@pytest.fixture
def traced():
    """Tracing on, from empty totals; off and empty again afterwards."""
    tracing.reset()
    tracing.enable()
    try:
        yield
    finally:
        tracing.disable()
        tracing.reset()


@pytest.fixture(scope="module")
def net():
    cfg = paper_tasks.reduced("jsc")
    return pipeline.compile_network(
        assemble.init(jax.random.PRNGKey(3), cfg), cfg)


def _rows(net, n, seed):
    rng = np.random.default_rng(seed)
    return rng.uniform(-1.0, 1.0, (n, net.cfg.in_features)).astype(np.float32)


def test_off_records_nothing():
    tracing.reset()
    assert not tracing.enabled()
    a = tracing.span("engine.fill")
    assert a is tracing.span("fleet.tick")
    assert isinstance(a, contextlib.nullcontext)
    with a:
        tracing.count("queue.rows", 5)
    assert tracing.snapshot() == {"spans": {}, "counters": {}}


def test_nested_spans_give_self_time(traced, monkeypatch):
    # the clock reads: outer in, inner in/out, inner in/out, outer out
    reads = iter([0.0, 1.0, 4.0, 5.0, 6.0, 10.0])
    monkeypatch.setattr(tracing, "perf_counter", lambda: next(reads))
    with tracing.span("outer"):
        for _ in range(2):
            with tracing.span("inner"):
                pass
    tracing.count("n", 2)
    tracing.count("n")
    got = tracing.snapshot()
    assert got["spans"] == {"outer": [1, 10.0, 6.0], "inner": [2, 4.0, 4.0]}
    assert got["counters"] == {"n": 3}


def test_fleet_gives_one_engine_span_each_per_block(net, traced):
    fleet = LUTFleet(block=16, depth=2)
    fleet.register("jsc", net, reference=make_reference(net, n=16))
    tracing.reset()
    ticks0 = fleet.stats("jsc").ticks
    sizes = (5, 12, 30, 1)
    for i, n in enumerate(sizes):
        fleet.submit_many("jsc", _rows(net, n, seed=i))
        fleet.tick()
    fleet.pump()
    got = tracing.snapshot()
    blocks = fleet.stats("jsc").ticks - ticks0
    assert blocks >= 4
    for name in ENGINE_SPANS:
        assert got["spans"][name][0] == blocks, name
    assert got["spans"]["fleet.submit"][0] == len(sizes)
    assert got["spans"]["engine.enqueue"][0] == len(sizes)
    assert got["counters"]["queue.rows"] == sum(sizes)
    assert got["counters"]["queue.wait_s"] > 0
    # the fleet's spans hold the engine's: their self time is the rest
    for name in ("fleet.tick", "fleet.drain", "fleet.submit"):
        count, total, self_s = got["spans"][name]
        assert count > 0 and 0 <= self_s <= total


def test_compiles_are_counted(traced):
    x = jnp.arange(7.0)
    f = jax.jit(lambda v: v * 3.0 - 1.0)
    f(x).block_until_ready()
    first = tracing.snapshot()["counters"]
    assert first.get("compile.traces", 0) + first.get("compile.backend",
                                                      0) >= 1
    tracing.reset()
    f(x).block_until_ready()
    assert tracing.snapshot()["counters"] == {}
    tracing.disable()
    jax.jit(lambda v: v + 2.0)(x).block_until_ready()
    assert tracing.snapshot()["counters"] == {}


def test_dispatch_that_raises_closes_its_spans(net, traced, monkeypatch):
    eng = LUTEngine(net, block=8, depth=2)

    def boom(xb):
        raise RuntimeError("executor down")

    monkeypatch.setattr(eng, "_fwd", boom)
    eng.submit_many(_rows(net, 5, seed=9))
    with pytest.raises(RuntimeError, match="executor down"):
        eng.dispatch_block()
    spans = tracing.snapshot()["spans"]
    for name in ("engine.fill", "engine.put", "engine.launch"):
        assert spans[name][0] == 1, name
    assert tracing._open == []
    assert len(eng.queue) == 5 and eng.inflight == 0
    monkeypatch.undo()
    assert len(eng.dispatch_block()) == 5
    assert len(eng.retire_oldest()) == 5
    assert tracing.snapshot()["spans"]["engine.fetch"][0] == 1
