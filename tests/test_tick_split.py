"""``benchmarks/tick_split.py``: the program's spans read back from one
window, on CPU at small sizes."""
from __future__ import annotations

import math
import os

import pytest

from bench import run as brun
from benchmarks import tick_split
from repro import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _cache_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    yield
    tracing.reset()


def _program(counters):
    return {"spans": {"fleet.tick": [10, 0.05, 0.004],
                      "fleet.drain": [2, 0.01, 0.001],
                      "fleet.submit": [4, 0.003, 0.001],
                      "engine.enqueue": [4, 0.002, 0.002],
                      "engine.fill": [10, 0.003, 0.003],
                      "engine.put": [10, 0.001, 0.001],
                      "engine.launch": [10, 0.006, 0.006],
                      "engine.wait": [10, 0.0005, 0.0005],
                      "engine.fetch": [10, 0.0075, 0.0075],
                      "engine.scatter": [10, 0.002, 0.002]},
            "counters": counters}


@pytest.mark.parametrize("key,want", [
    ("sched", 500.0),       # self 4 ms + 1 ms over 10 blocks
    ("fill", 300.0),
    ("launch", 700.0),      # put 1 ms + launch 6 ms
    ("fetch", 800.0),       # wait 0.5 ms + fetch 7.5 ms
    ("scatter", 200.0),
    ("sum", 2500.0),
])
def test_split_per_block_on_a_synthetic_program(key, want):
    got = tick_split.split(_program({}), blocks=10, rows_admitted=1000)
    assert got["split_us_per_block"][key] == pytest.approx(want)


def test_split_per_row_and_compiles():
    got = tick_split.split(
        _program({"queue.wait_s": 0.5, "queue.rows": 1000,
                  "compile.traces": 1, "compile.backend": 2}),
        blocks=10, rows_admitted=1000)
    assert got["queue_wait_us_per_row"] == pytest.approx(500.0)
    assert got["enqueue_us_per_row"] == pytest.approx(2.0)
    assert got["compiles_in_window"] == 3
    # no compile reads 0; no block dispatched leaves the fleet's pieces out
    bulk = tick_split.split({"spans": {}, "counters": {}}, blocks=0,
                            rows_admitted=1000)
    assert bulk == {"compiles_in_window": 0}


def test_idle_by_span_on_nested_spans():
    events = {"device": {"/device:TPU:0": [["k", 100.0, 50.0],
                                           ["k", 400.0, 100.0],
                                           ["k", 1200.0, 50.0]]},
              "host": [["window", 0.0, 1000.0], ["tick", 0.0, 600.0]]}
    spans = [["fleet.tick", 0.0, 600.0], ["engine.fill", 50.0, 100.0],
             ["engine.fetch", 300.0, 150.0], ["fleet.submit", 700.0, 100.0]]
    # idle [0,100] [150,400] [500,1000]; innermost pieces: tick [0,50],
    # fill [50,150], tick [150,300], fetch [300,450], tick [450,600],
    # submit [700,800], nothing elsewhere
    got = tick_split.idle_by_span(events, spans)
    assert got == pytest.approx({"fleet.tick": 300e-9, "engine.fill": 50e-9,
                                 "engine.fetch": 100e-9,
                                 "fleet.submit": 100e-9, "other": 300e-9})
    assert sum(got.values()) == pytest.approx(850e-9)
    assert list(got.values()) == sorted(got.values(), reverse=True)
    assert tick_split.idle_by_span(events, []) == {
        "other": pytest.approx(850e-9)}


@pytest.mark.parametrize("name,over", [
    ("jsc_openml.trigger", {"rate_per_s": 200}),
    ("mnist.serve", {"clients": 4}),
])
def test_traced_fleet_window_reads_every_piece(name, over):
    spec, cell, cfg, mix = brun.load_cell(ROOT, name)
    res = tick_split.run_split(spec, cell, cfg, dict(mix, **over),
                               seed=2**34 + 5, seconds=0.5,
                               require_tpu=False)
    assert res["correct"]
    per = res["split_us_per_block"]
    assert set(per) == {"sched", "fill", "launch", "fetch", "scatter", "sum"}
    assert all(math.isfinite(v) and v > 0 for v in per.values())
    # the pieces lie inside the harness's tick and drain spans
    assert per["sum"] <= res["tick_us_per_block"]
    for k in ("queue_wait_us_per_row", "enqueue_us_per_row",
              "compiles_in_window", "submit_us_per_row"):
        assert math.isfinite(res[k]), k
    spans = res["program"]["spans"]
    # the closed loop's window can end with blocks still in flight
    fills, fetches = spans["engine.fill"][0], spans["engine.fetch"][0]
    assert 0 < fetches <= fills <= fetches + mix["fleet"]["depth"]
    # a CPU trace has no device plane to find idle time on
    assert isinstance(res["idle_by_span_s"], dict)
