"""CPU tests of the chip benchmark (``bench/``).

    PYTHONPATH=src python -m pytest -q bench/tests

They check the yardstick without a chip: the required-work counts, the
traffic generator, the trace reduction on a recorded fixture, the
reference against the program's own backends, that the control and
planted faults in the timed path read as not correct, the no-chip exit,
and that ``BENCHMARK.json`` keeps the benchmark contract.
"""
from __future__ import annotations

import json
import math
import os
import re
import subprocess
import sys
import time

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import arrivals, lutnet, reference, run, trace, work  # noqa: E402

SPEC = run.load_json(ROOT, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def config(name: str) -> dict:
    return run.load_json(BENCH, "configs", name + ".json")


def table_ii(task: str) -> dict:
    """A Table-II design as a configuration dict, from the program."""
    from repro import pipeline
    from repro.configs import paper_tasks

    d = pipeline.config_to_dict(paper_tasks.task_config(task))
    return dict(d, in_log_scale=0.0, out_log_scale=0.0,
                input_range=[-1.0, 1.0])


@pytest.fixture(autouse=True)
def _cache_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))


# ---------------------------------------------------------------------------
# required work
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("task,ops,table_bits,io_bits", [
    # jsc_openml: 320 units of fan-in 1, then 315 of fan-in 2; 64-entry
    # tables, 3-bit codes except the 5 logits at 8 bits; 16 x 6-bit in
    ("jsc_openml", 320 * 3 + 315 * 5,
     320 * 64 * 3 + 310 * 64 * 3 + 5 * 64 * 8, 16 * 6 + 5 * 8),
    # nid: 60 units of fan-in 6, then 33 of fan-in 3; 64-entry tables of
    # 2-bit codes; 593 one-bit inputs, one 2-bit output
    ("nid", 60 * 13 + 33 * 7, 93 * 64 * 2, 593 + 2),
])
def test_work_matches_hand_counts(task, ops, table_bits, io_bits):
    cfg = table_ii(task)
    assert work.ops_per_row(cfg) == ops
    assert work.table_bytes(cfg) == table_bits / 8
    assert work.io_bytes_per_row(cfg) == io_bits / 8
    got_ops, got_bytes = work.required(cfg, 1024, 256)
    assert got_ops == 1024 * ops
    assert got_bytes == 4 * table_bits / 8 + 1024 * io_bits / 8


def test_required_seconds_is_the_larger_bound():
    cfg = table_ii("jsc_openml")
    peaks = run.load_peaks("TPU v5 lite")
    ops, nbytes = work.required(cfg, 1e6, 1024)
    assert work.required_seconds(cfg, 1e6, 1024, peaks) == max(
        ops / 393e12, nbytes / 819e9)


def test_unknown_device_kind_has_no_peaks():
    with pytest.raises(KeyError):
        run.load_peaks("cpu")


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------

def test_open_schedule_is_seeded():
    mix = run.load_json(BENCH, "traffic", "trigger.json")
    a = arrivals.open_schedule(mix, 4.0, 2**40 + 3, rate_per_s=2000)
    b = arrivals.open_schedule(mix, 4.0, 2**40 + 3, rate_per_s=2000)
    c = arrivals.open_schedule(mix, 4.0, 3, rate_per_s=2000)
    for f in ("due_s", "rows", "starts", "keep"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))
    assert not np.array_equal(a.due_s, c.due_s)
    # another seed: the same gaps, in another order
    np.testing.assert_allclose(np.sort(np.diff(a.due_s, prepend=0)),
                               np.sort(np.diff(c.due_s, prepend=0)))
    assert np.all(np.diff(a.due_s) >= 0) and a.due_s[-1] < 4.0
    assert len(a.due_s) / 4.0 == pytest.approx(2000, rel=0.01)
    np.testing.assert_array_equal(a.rows, mix["rows"])
    np.testing.assert_array_equal(a.starts, np.arange(len(a.rows)) * 12)
    x1 = arrivals.input_rows(config("jsc_openml"), 100, 5)
    x2 = arrivals.input_rows(config("jsc_openml"), 100, 5)
    np.testing.assert_array_equal(x1, x2)
    assert not np.array_equal(x1, arrivals.input_rows(
        config("jsc_openml"), 100, 6))


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------

def test_trace_reduction_on_fixture():
    with open(os.path.join(BENCH, "tests", "trace_fixture.json")) as f:
        events = json.load(f)
    red = trace.reduce(events)
    # busy: union of [100,220] [300,320] [500,650] inside [0, 1000]
    assert red["busy_s"] == pytest.approx(290e-9)
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["idle_share"] == pytest.approx(0.71)
    assert [n for n, _ in red["idle_gaps"]] == ["tick", "wait_due", "tick",
                                                "drain"]
    assert [g for _, g in red["idle_gaps"]] == pytest.approx(
        [350e-9, 180e-9, 100e-9, 80e-9])
    assert red["device_ops"][0] == ["lut_cascade", pytest.approx(200e-9)]
    assert "after_window" not in dict(red["device_ops"])


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [[0, 3], [5, 9]]


# ---------------------------------------------------------------------------
# reference, control
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["jsc_openml", "mnist"])
def test_configs_are_table_ii(name):
    from repro import pipeline
    from repro.configs import paper_tasks

    cfg = config(name)
    assert cfg["reduced"] == []
    assert lutnet.network_config(cfg) == pipeline.config_to_dict(
        paper_tasks.task_config(cfg["factory"]))


@pytest.mark.parametrize("name", ["jsc_openml", "mnist"])
def test_reference_matches_program_backends(name):
    cfg = config(name)
    net, tables, maps = run.build_network(dict(cfg, network_seed=2**35 + 1))
    x = arrivals.input_rows(cfg, 300, 4)
    codes, logits = reference.forward(cfg, tables, maps, x)
    for backend in ("take", "fused"):
        got_c, got_l = net.compile_backend(backend).codes_and_logits(x)
        np.testing.assert_array_equal(np.asarray(got_c), codes)
        np.testing.assert_allclose(np.asarray(got_l), logits, rtol=1e-6)
    assert reference.compare(cfg, codes, logits, codes, logits) == {
        "rows_wrong": 0.0, "logit_gap": 0.0}


def test_network_is_seeded():
    cfg = config("jsc_openml")
    t1, m1 = lutnet.make_arrays(cfg, 2**40 + 1)
    t2, m2 = lutnet.make_arrays(cfg, 2**40 + 1)
    t3, _ = lutnet.make_arrays(cfg, 1)
    assert all(np.array_equal(a, b) for a, b in zip(t1, t2))
    assert not np.array_equal(t1[0], t3[0])
    for t, s in zip(t1, lutnet.layer_shapes(cfg)):
        assert t.shape == (s["units"], s["entries"])
        assert t.min() >= 0 and t.max() < 2 ** s["bits"]


@pytest.mark.parametrize("name,bits", [("jsc_openml", 8), ("mnist", 4)])
def test_control_fails_the_limits(name, bits):
    from bench import control

    cfg = config(name)
    assert reference.stored_bits(cfg) // 2 == bits
    mix = run.load_json(BENCH, "traffic", "trigger.json" if name ==
                        "jsc_openml" else "serve.json")
    got = control.readings(cfg, mix, 12, 0.2)
    assert got["fails"]
    assert got["rows_wrong"]["value"] > 0.3 * got["rows"]
    assert got["logit_gap"]["value"] >= 1.0


# ---------------------------------------------------------------------------
# the harness end to end, sound and with the timed path broken
# ---------------------------------------------------------------------------

def _run(name, **mix_over):
    spec, cell, cfg, mix = run.load_cell(ROOT, name)
    res = run.run_cell(spec, cell, cfg, dict(mix, **mix_over), seed=2**33 + 7,
                       seconds=0.5, trace=False, t_start=time.perf_counter(),
                       require_tpu=False)
    assert set(res) >= {"correct", "attempted", "failed", "metrics",
                        "device", "checks"}
    assert list(res)[-2:] == ["checks", "_info"]
    return res


# every cell, at sizes a CPU test run holds
CELLS = [("jsc_openml.trigger", {"rate_per_s": 200}),
         ("mnist.serve", {"clients": 4}),
         ("mnist.bulk", {"batch_rows": 256}),
         ("jsc_openml.bulk", {"batch_rows": 2048})]


@pytest.mark.parametrize("name,over", CELLS)
def test_sound_run_is_correct(name, over):
    res = _run(name, **over)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["_info"]["rows_checked"] > 0
    want = {m["name"] for m in run.cell_metrics(SPEC, name, "end_to_end")}
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for m in res["metrics"].values())


def _half_left_out(orig):
    def broken(self, x):
        codes, logits = orig(self, x)
        return codes.at[1::2].set(0), logits.at[1::2].set(0.0)
    return broken


def _answer_altered(orig):
    def broken(self, x):
        codes, logits = orig(self, x)
        return codes.at[0, 0].add(1), logits.at[0, 0].add(0.125)
    return broken


@pytest.mark.parametrize("fault", [_half_left_out, _answer_altered])
@pytest.mark.parametrize("name,over", CELLS)
def test_broken_timed_path_is_not_correct(name, over, fault, monkeypatch):
    from repro.pipeline import PlannedExecutor

    monkeypatch.setattr(PlannedExecutor, "codes_and_logits",
                        fault(PlannedExecutor.codes_and_logits))
    res = _run(name, **over)
    assert not res["correct"]
    assert res["checks"]["rows_wrong"]["value"] > 0


def test_no_chip_exits_nonzero_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "jsc_openml.trigger", "--seed", "1", "--seconds",
                        "1", "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"metrics"' not in p.stdout
    assert "no TPU" in p.stderr


def test_without_the_program_exits_nonzero(tmp_path):
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "mnist.bulk", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"metrics"' not in p.stdout


# ---------------------------------------------------------------------------
# BENCHMARK.json keeps the contract
# ---------------------------------------------------------------------------

def test_benchmark_json_keys_and_names():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    for entry in SPEC["configs"] + SPEC["workloads"] + metrics:
        assert NAME.match(entry["name"]), entry["name"]
    for m in metrics:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for group in (SPEC["configs"], SPEC["workloads"], metrics):
        assert len({e["name"] for e in group}) == len(group)
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert c["file"].startswith("bench/")
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])


def test_every_cell_reports_what_its_metrics_need():
    cells = {w["name"]: w for w in SPEC["workloads"]}
    configs = {c["name"] for c in SPEC["configs"]}
    for w in cells.values():
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] == 1
        assert len(w["why"]) <= 200
        assert os.path.exists(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
        e2e = {m["name"] for m in run.cell_metrics(SPEC, w["name"],
                                                   "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert run.cell_metrics(SPEC, w["name"], "per_layer")
    assert {w["config"] for w in cells.values()} == configs
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        for w in m["workloads"]:
            assert w in cells
            assert m["moves"] in {x["name"] for x in run.cell_metrics(
                SPEC, w, "end_to_end")}
        assert callable(run.reader(m["name"]))
        if "_roofline" in m["name"]:
            assert m["unit"] == "%"


def test_readers_return_nothing_when_there_is_nothing_to_read():
    empty = {"cfg": config("mnist"), "peaks": run.load_peaks("TPU v5 lite"),
             "spans": {}, "trace": None, "fleet": None, "window_s": 0.0,
             "rows_admitted": 0, "rows_answered": 0, "batch_rows": 256}
    for m in SPEC["per_layer"]:
        assert run.reader(m["name"])(empty) is None, m["name"]


def test_roofline_and_mfu_from_a_trace():
    cfg = config("mnist")
    peaks = run.load_peaks("TPU v5 lite")
    ctx = {"cfg": cfg, "peaks": peaks, "spans": {},
           "trace": {"busy_s": 1.0, "window_s": 2.0}, "fleet": None,
           "window_s": 2.0, "rows_admitted": 10**6, "rows_answered": 10**6,
           "batch_rows": 8192}
    roof = run.reader("lut_cascade_roofline")(ctx)
    need = work.required_seconds(cfg, 10**6, 8192, peaks)
    assert roof == pytest.approx(100 * need)
    mfu = run.reader("mfu_lut_pct")(ctx)
    assert mfu == pytest.approx(100 * work.ops_per_row(cfg) * 10**6
                                / (2.0 * 393e12))
    assert run.reader("device_idle_pct")(ctx) == pytest.approx(50.0)
    assert 0 < roof < 100 and 0 < mfu < 100
    assert math.isfinite(roof)
