"""The kernel-autotuner contract (docs/KERNELS.md §5, docs/PERF_TUNING.md).

Three surfaces:

  * the roofline model — ``default_tuning``/``pick_tuning`` produce sane,
    VMEM-feasible choices, and stream when the cascade cannot sit
    resident;
  * measurement-driven tuning — ``measure_tuning`` picks the observed
    winner and ``FusedCascadeBackend.autotune_plan`` stamps it into a
    plan WITHOUT changing what the cascade returns;
  * persistence — tunings survive ``save``/``load`` inside the artifact,
    and v1 fused plans restored from old ``.npz`` files are migrated in
    place (defaulted tuning, buffers reused verbatim, predictions
    bit-identical).
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro import backends, pipeline
from repro.backends.base import ExecutionPlan
from repro.configs import paper_tasks
from repro.core import assemble
from repro.kernels import autotune
from repro.kernels.autotune import KernelTuning
from repro.pipeline import CompiledLUTNetwork


def _compiled(task="nid", seed=0):
    cfg = paper_tasks.reduced(task)
    params = assemble.init(jax.random.PRNGKey(seed), cfg)
    return pipeline.compile_network(params, cfg)


# ---------------------------------------------------------------------------
# roofline model
# ---------------------------------------------------------------------------

def test_default_tuning_is_sane_and_feasible():
    layers = autotune.config_layers(paper_tasks.reduced("nid"))
    t = autotune.default_tuning(layers)
    assert t.source == "default"
    assert t.impl is None                      # auto: Pallas on TPU, XLA off
    assert t.mode in ("resident", "streamed")
    assert t.block_b in autotune.BLOCK_B_CANDIDATES
    assert t.unit_tile in autotune.UNIT_TILE_CANDIDATES


def test_roofline_candidates_cover_grid_and_mark_vmem():
    layers = autotune.config_layers(paper_tasks.reduced("jsc"))
    rows = autotune.roofline_candidates(layers, device="TPU v5 lite")
    n_expected = len(autotune.BLOCK_B_CANDIDATES) * (
        1 + len(autotune.UNIT_TILE_CANDIDATES))
    assert len(rows) == n_expected
    for r in rows:
        assert r["bound"] in ("compute", "memory")
        assert r["t_us"] == pytest.approx(
            max(r["t_compute_us"], r["t_memory_us"]))
        assert isinstance(r["fits_vmem"], bool) and r["vmem_bytes"] > 0


def test_pick_tuning_streams_when_tables_exceed_vmem():
    """A cascade whose packed tables dwarf the CPU model's VMEM budget
    must not pick a resident candidate that cannot fit."""
    # one layer, 2^14 entries x 4096 units x 4B = 256 MiB resident
    layers = [(4096 * 7, 4096, 2 ** 14, 0, 7, 2, 1)]
    t = autotune.pick_tuning(layers, device="cpu")
    assert t.mode == "streamed"
    rows = autotune.roofline_candidates(layers, device="cpu")
    assert not any(r["fits_vmem"] for r in rows if r["mode"] == "resident")


@pytest.mark.parametrize("task,mode", [
    ("mnist", "streamed"), ("jsc_openml", "resident"),
    ("jsc_cernbox", "resident")])
def test_v5e_picks_of_the_bench_designs(task, mode):
    """The v5e tiling of every benchmark design: the model sees shapes,
    not tables, so packed table words leave it as it was, with the
    select scan's tiles held to the measured ``scan_bytes`` (128 rows)
    and one lane tile a streamed phase."""
    layers = autotune.config_layers(paper_tasks.task_config(task))
    for batch in (256, 4096, 8192, 65536):
        t = autotune.pick_tuning(layers, batch=batch, device="TPU v5 lite")
        assert (t.mode, t.block_b, t.unit_tile) == (mode, 128, 128)


def test_device_kind_raises_on_unknown_kind():
    """The device table is keyed by JAX's device_kind; a device without a
    row gets an error, never another device's peaks or VMEM size."""
    assert autotune.device_kind() == jax.devices()[0].device_kind
    assert autotune.device_kind("TPU v5 lite") == "TPU v5 lite"
    layers = autotune.config_layers(paper_tasks.reduced("nid"))
    for kind in ("tpu", "gpu", "TPU v9 imaginary"):
        with pytest.raises(ValueError, match="no device model"):
            autotune.device_kind(kind)
        with pytest.raises(ValueError, match="no device model"):
            autotune.pick_tuning(layers, device=kind)
    v5e = autotune.DEVICE_MODELS["TPU v5 lite"]
    assert v5e["vmem_bytes"] == 16 * 2 ** 20 and v5e["source"]


def test_foreign_device_tuning_is_repicked():
    """A plan whose tuning was picked for another device kind runs with
    (and records) this device's pick, with unchanged codes."""
    compiled = _compiled()
    x = np.asarray(jax.random.uniform(jax.random.PRNGKey(2),
                                      (65, compiled.cfg.in_features),
                                      minval=-1.0, maxval=1.0))
    ref = np.asarray(compiled.predict_codes(x, backend="take"))
    plan = compiled.compile_backend("fused").plan
    here = KernelTuning.from_meta(plan.meta["tuning"])
    assert here.device_kind == jax.devices()[0].device_kind
    plan.meta["tuning"] = KernelTuning(
        impl="pallas", mode="streamed", block_b=64, source="measured",
        device_kind="TPU v5 lite").to_meta()
    compiled._executors.clear()
    np.testing.assert_array_equal(
        np.asarray(compiled.predict_codes(x, backend="fused")), ref)
    repicked = KernelTuning.from_meta(compiled._plans["fused"].meta["tuning"])
    assert repicked == autotune.pick_tuning(
        plan.meta["layers"], device=here.device_kind,
        table_dtype=plan.meta["table_dtype"])


def test_kernel_tuning_meta_round_trip():
    t = KernelTuning(impl="xla", mode="streamed", block_b=128, unit_tile=256,
                     table_dtype="int8", source="measured",
                     device_kind="TPU v5 lite")
    assert KernelTuning.from_meta(t.to_meta()) == t
    assert KernelTuning.from_meta(None) == KernelTuning()
    # unknown keys from a newer schema are dropped, not fatal
    assert KernelTuning.from_meta(
        {"mode": "streamed", "from_the_future": 1}).mode == "streamed"


def test_choice_table_covers_all_tasks_and_devices():
    doc = autotune.choice_table(devices=("cpu", "TPU v5 lite"))
    tasks = {c["task"] for c in doc["choices"]}
    assert tasks == set(paper_tasks.TASKS)
    assert all(c["tuning"]["block_b"] in autotune.BLOCK_B_CANDIDATES
               for c in doc["choices"])


# ---------------------------------------------------------------------------
# measurement-driven tuning
# ---------------------------------------------------------------------------

def test_measure_tuning_picks_the_observed_winner():
    import time as _time
    fast = KernelTuning(mode="resident", block_b=256)
    slow = KernelTuning(mode="streamed", block_b=64)

    def factory(t):
        delay = 0.0 if t == fast else 0.005
        return lambda: _time.sleep(delay)

    winner, report = autotune.measure_tuning(factory, [slow, fast], reps=2)
    assert winner == dataclasses.replace(fast, source="measured")
    assert len(report) == 2 and all(r["best_s"] >= 0 for r in report)


def test_autotune_plan_stamps_winner_without_changing_codes():
    compiled = _compiled()
    fused = backends.get("fused")
    plan = compiled.compile_backend("fused").plan
    tuned = fused.autotune_plan(plan, rows=256, reps=1,
                                candidates=[KernelTuning(impl="xla"),
                                            KernelTuning(impl="xla",
                                                         block_b=64)])
    t = KernelTuning.from_meta(tuned.meta["tuning"])
    assert t.source == "measured"
    assert len(tuned.meta["tuning_report"]) == 2
    # the original plan object is untouched (copy-on-tune)
    assert KernelTuning.from_meta(plan.meta["tuning"]).source != "measured"
    cin = np.random.default_rng(0).integers(
        0, plan.meta["input_span"],
        (33, compiled.cfg.in_features)).astype(np.int32)
    np.testing.assert_array_equal(np.asarray(fused.run(tuned, cin)),
                                  np.asarray(fused.run(plan, cin)))


# ---------------------------------------------------------------------------
# persistence + migration
# ---------------------------------------------------------------------------

def test_tuned_plan_round_trips_through_artifact(tmp_path):
    compiled = _compiled()
    x = np.asarray(jax.random.uniform(jax.random.PRNGKey(1),
                                      (65, compiled.cfg.in_features),
                                      minval=-1.0, maxval=1.0))
    ref = np.asarray(compiled.predict_codes(x, backend="take"))
    fused = backends.get("fused")
    compiled._plans["fused"] = fused.autotune_plan(
        compiled.compile_backend("fused").plan, rows=256, reps=1,
        candidates=[KernelTuning(impl="xla", block_b=128)])
    compiled._executors.clear()  # executor cache predates the tuned plan

    path = tmp_path / "tuned.npz"
    compiled.save(str(path))
    reloaded = CompiledLUTNetwork.load(str(path))
    t = KernelTuning.from_meta(reloaded._plans["fused"].meta["tuning"])
    assert t == KernelTuning(impl="xla", block_b=128, source="measured",
                             device_kind="cpu")
    for be in backends.available():
        np.testing.assert_array_equal(
            np.asarray(reloaded.predict_codes(x, backend=be)), ref,
            err_msg=f"tuned artifact/{be}")


def _downgrade_to_v1(plan: ExecutionPlan) -> ExecutionPlan:
    """A faithful v1 fused plan: 4-wide layers, no maps, no tuning."""
    meta = {
        "plan_format": "fused-packed-v1",
        "layers": [list(lm[:4]) for lm in plan.meta["layers"]],
        "table_dtype": plan.meta["table_dtype"],
        "vmem_bytes": plan.meta["vmem_bytes"],
    }
    buffers = {"amat": plan.buffers["amat"].copy(),
               "tables": plan.buffers["tables"].copy()}
    return ExecutionPlan(backend="fused", meta=meta, buffers=buffers)


def test_v1_plan_migrates_with_defaulted_tuning_bit_identical(tmp_path):
    compiled = _compiled(seed=3)
    x = np.asarray(jax.random.uniform(jax.random.PRNGKey(4),
                                      (65, compiled.cfg.in_features),
                                      minval=-1.0, maxval=1.0))
    ref = np.asarray(compiled.predict_codes(x, backend="take"))
    v2 = compiled.compile_backend("fused").plan
    v1 = _downgrade_to_v1(v2)

    # inject the old-format plan as if restored from a pre-bump artifact
    compiled._plans["fused"] = v1
    compiled._executors.clear()
    np.testing.assert_array_equal(
        np.asarray(compiled.predict_codes(x, backend="fused")), ref)

    migrated = compiled._plans["fused"]
    assert migrated.meta["plan_format"] == "fused-packed-v2"
    t = KernelTuning.from_meta(migrated.meta["tuning"])
    assert t.source == "default"
    # buffers reused verbatim: bit-identity is structural, not re-derived
    np.testing.assert_array_equal(migrated.buffers["amat"],
                                  v1.buffers["amat"])
    np.testing.assert_array_equal(migrated.buffers["tables"],
                                  v1.buffers["tables"])
    assert all(f"map_{l}" in migrated.buffers
               for l, lm in enumerate(migrated.meta["layers"]) if not lm[6])


def test_unrecognizable_plan_forces_fresh_replan():
    compiled = _compiled()
    fused = backends.get("fused")
    net = compiled.folded()
    v2 = compiled.compile_backend("fused").plan
    # wrong format string -> not migratable
    alien = ExecutionPlan(backend="fused",
                          meta={"plan_format": "somebody-elses-layout"},
                          buffers={})
    assert fused.migrate_plan(alien, net) is None
    # right format, wrong network shape -> None (migration must not guess)
    v1 = _downgrade_to_v1(v2)
    v1.meta["layers"][0][1] += 1
    assert fused.migrate_plan(v1, net) is None
