"""JSC CERNBox (arXiv:2504.00592 Table II) as the benchmark serves it.

The design's 8-bit signed inputs, 4-bit activations and 8-bit logits give
every table 256 entries (64 in every other Table-II design but nid's
first layer) and int16 tables.  These tests hold the configuration file
to the program's own Table-II factory, the program's backends and its
resident Pallas kernel to the plain reference (``bench/reference.py``),
the required-work counts to hand counts, and the ``jsc_cernbox.bulk``
cell's comparison to what it must tell apart, all on the CPU.
"""
from __future__ import annotations

import math
import os
import time

import jax.numpy as jnp
import numpy as np
import pytest

from bench import arrivals, control, lutnet, reference, run, work

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "jsc_cernbox.bulk"


def config() -> dict:
    return run.load_json(ROOT, "bench", "configs", "jsc_cernbox.json")


@pytest.fixture(autouse=True)
def _cache_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))


def _edge_rows(cfg: dict, n: int, seed: int) -> np.ndarray:
    """``n`` drawn rows, then rows at the code range's ends (-128, 127),
    at the rounding points next to them and beyond the clip."""
    x = arrivals.input_rows(cfg, n, seed)
    edges = np.array([-128.0, 127.0, -128.4, 127.4, -128.6, 127.6,
                      -144.0, 144.0, -1000.0, 1000.0, 0.0, -0.5],
                     np.float32)
    rows = np.resize(edges, (len(edges), cfg["in_features"]))
    rows = np.stack([np.roll(r, i) for i, r in enumerate(rows)])
    return np.concatenate([x, rows.astype(np.float32)])


def test_config_is_table_ii():
    from repro import pipeline
    from repro.configs import paper_tasks

    cfg = config()
    assert cfg["factory"] == "jsc_cernbox" and cfg["reduced"] == []
    assert lutnet.network_config(cfg) == pipeline.config_to_dict(
        paper_tasks.jsc_cernbox())
    assert [s["entries"] for s in lutnet.layer_shapes(cfg)] == [256] * 7
    assert reference.stored_bits(cfg) == 16
    # the signed 8-bit code range plus a one-eighth margin, in input steps
    assert cfg["in_log_scale"] == 0.0
    assert cfg["input_range"] == [-144.0, 144.0]
    assert cfg["out_log_scale"] == pytest.approx(math.log(1 / 8), abs=1e-15)


def test_backends_match_reference_at_published_widths():
    cfg = config()
    net, tables, maps = run.build_network(dict(cfg, network_seed=2**35 + 3))
    x = _edge_rows(cfg, 300, 8)
    codes, logits = reference.forward(cfg, tables, maps, x)
    # the edge rows reach the first layer's lowest and highest addresses
    h = np.clip(np.round(x), -128, 127) + 128
    assert h.min() == 0 and h.max() == 255
    for backend in ("take", "fused"):
        got_c, got_l = net.compile_backend(backend).codes_and_logits(x)
        np.testing.assert_array_equal(np.asarray(got_c), codes)
        # float32 rounding of one multiply; a wrong code is a whole step
        np.testing.assert_allclose(np.asarray(got_l), logits, rtol=1e-6)


def test_resident_kernel_matches_reference():
    """The resident Pallas kernel (interpret mode) over the 256-entry
    tables, from the quantized codes, equals the reference's codes."""
    from repro.kernels.lut_cascade import address_passes, lut_cascade_pallas

    cfg = config()
    net, tables, maps = run.build_network(dict(cfg, network_seed=2**35 + 5))
    plan = net.compile_backend("fused").plan
    layers = tuple(tuple(int(v) for v in lm) for lm in plan.meta["layers"])
    assert address_passes(plan.buffers["amat"], layers) == 1
    x = _edge_rows(cfg, 28, 9)
    codes_in = (np.clip(np.round(x), -128, 127) + 128).astype(np.int32)
    got = lut_cascade_pallas(
        jnp.asarray(codes_in), jnp.asarray(plan.buffers["amat"]),
        jnp.asarray(plan.buffers["tables"]), layers=layers, block_b=16,
        mode="resident", interpret=True)
    np.testing.assert_array_equal(
        np.asarray(got), reference.forward(cfg, tables, maps, x)[0])


def test_work_matches_hand_counts():
    cfg = config()
    # 320 units of fan-in 1, then 315 of fan-in 2
    assert work.ops_per_row(cfg) == 2535 == 320 * 3 + 315 * 5
    assert work.table_bytes(cfg) == (320 * 256 * 4 + 310 * 256 * 4
                                     + 5 * 256 * 8) / 8
    assert work.io_bytes_per_row(cfg) == (16 * 8 + 5 * 8) / 8


def test_control_fails_the_limits():
    cfg = config()
    mix = run.load_json(ROOT, "bench", "traffic", "bulk_64k.json")
    got = control.readings(cfg, dict(mix, batch_rows=2048), 12, 0.2)
    assert got["control_bits"] == 8
    assert got["fails"]
    assert got["rows_wrong"]["value"] > 0.3 * got["rows"]
    assert got["logit_gap"]["value"] >= 1.0


def _answer_altered(orig):
    def broken(self, x):
        codes, logits = orig(self, x)
        return codes.at[0, 0].add(1), logits.at[0, 0].add(0.125)
    return broken


@pytest.mark.parametrize("broken", [False, True], ids=["sound", "altered"])
def test_cell_run_is_correct_only_when_sound(broken, monkeypatch):
    from repro.pipeline import PlannedExecutor

    if broken:
        monkeypatch.setattr(PlannedExecutor, "codes_and_logits",
                            _answer_altered(PlannedExecutor.codes_and_logits))
    spec, cell, cfg, mix = run.load_cell(ROOT, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "jsc_cernbox", "bulk_64k", 1)
    res = run.run_cell(spec, cell, cfg, dict(mix, batch_rows=2048),
                       seed=2**33 + 11, seconds=0.5, trace=False,
                       t_start=time.perf_counter(), require_tpu=False)
    assert res["correct"] is not broken, res["checks"]
    assert (res["checks"]["rows_wrong"]["value"] > 0) is broken
    assert res["_info"]["rows_checked"] > 0
    assert set(res["metrics"]) == {"rows_per_s.bulk", "setup_s"}
