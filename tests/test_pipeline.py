"""Unified toolflow API: CompiledLUTNetwork artifact + Toolflow driver +
LUT serving engine.

Covers the PR-1 acceptance contract: the artifact is self-contained (folded
inference after ``.load()`` in a fresh process needs no training params and
is bit-exact with ``assemble.apply_codes``), and the staged driver matches
the manual three-phase flow.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import pipeline, tracing
from repro.configs import paper_tasks
from repro.core import assemble
from repro.data import synthetic
from repro.pipeline import CompiledLUTNetwork, Toolflow

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

TASKS = ("mnist", "jsc", "nid")


def _rand_inputs(cfg, n, seed):
    return jax.random.uniform(jax.random.PRNGKey(seed),
                              (n, cfg.in_features), minval=-1.0, maxval=1.0)


# ---------------------------------------------------------------------------
# the artifact
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("task", TASKS)
def test_compiled_network_save_load_bit_exact(task, tmp_path):
    """save -> load round-trip is bit-exact with assemble.apply_codes on
    random inputs for every reduced() task."""
    cfg = paper_tasks.reduced(task)
    params = assemble.init(jax.random.PRNGKey(1), cfg)
    x = _rand_inputs(cfg, 64, seed=2)
    ref_codes = np.asarray(assemble.apply_codes(params, cfg, x))

    compiled = pipeline.compile_network(params, cfg)
    np.testing.assert_array_equal(
        np.asarray(compiled.predict_codes(x)), ref_codes)

    path = compiled.save(str(tmp_path / f"{task}.npz"))
    loaded = CompiledLUTNetwork.load(path)
    assert loaded.cfg == cfg
    np.testing.assert_array_equal(
        np.asarray(loaded.predict_codes(x)), ref_codes)


def test_compiled_network_backends_agree():
    cfg = paper_tasks.reduced("nid")
    params = assemble.init(jax.random.PRNGKey(3), cfg)
    compiled = pipeline.compile_network(params, cfg)
    x = _rand_inputs(cfg, 32, seed=4)
    take = np.asarray(compiled.predict_codes(x, backend="take"))
    for backend in ("onehot", "pallas"):
        np.testing.assert_array_equal(
            np.asarray(compiled.predict_codes(x, backend=backend)), take)


def test_compiled_network_predict_matches_model_forward():
    cfg = paper_tasks.reduced("jsc")
    params = assemble.init(jax.random.PRNGKey(5), cfg)
    compiled = pipeline.compile_network(params, cfg)
    x = _rand_inputs(cfg, 32, seed=6)
    ref, _ = assemble.apply(params, cfg, x, training=False)
    np.testing.assert_allclose(np.asarray(compiled.predict(x)),
                               np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_loaded_artifact_fresh_process_needs_no_params(tmp_path):
    """The acceptance criterion, literally: a fresh python process loads
    the .npz and reproduces assemble.apply_codes bit-exactly, with the
    training modules never imported."""
    cfg = paper_tasks.reduced("nid")
    params = assemble.init(jax.random.PRNGKey(7), cfg)
    x = _rand_inputs(cfg, 48, seed=8)
    ref_codes = np.asarray(assemble.apply_codes(params, cfg, x))
    art = pipeline.compile_network(params, cfg).save(
        str(tmp_path / "art.npz"))
    np.save(tmp_path / "x.npy", np.asarray(x))
    np.save(tmp_path / "ref.npy", ref_codes)

    code = textwrap.dedent(f"""
        import sys
        import numpy as np
        from repro.pipeline import CompiledLUTNetwork
        net = CompiledLUTNetwork.load({art!r})
        x = np.load({str(tmp_path / 'x.npy')!r})
        ref = np.load({str(tmp_path / 'ref.npy')!r})
        got = np.asarray(net.predict_codes(x))
        np.testing.assert_array_equal(got, ref)
        assert "repro.train" not in sys.modules  # no training code touched
        print("FRESH-OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "FRESH-OK" in out.stdout


def test_artifact_hw_report_and_verilog():
    cfg = paper_tasks.reduced("nid")
    params = assemble.init(jax.random.PRNGKey(9), cfg)
    compiled = pipeline.compile_network(params, cfg)
    rep = compiled.hw_report(pipeline_every=3)
    assert rep.luts > 0 and rep.latency_ns > 0
    v = compiled.to_verilog(pipeline_every=3)
    assert "module neuralut_assemble" in v
    # learned (non-contiguous) mapping wiring comes from the artifact itself
    assert v.count("case (") == sum(l.units for l in cfg.layers)


# ---------------------------------------------------------------------------
# the driver
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def nid_data():
    return synthetic.load("nid", n_train=4096, n_test=1024)


def test_toolflow_matches_manual_three_phase_flow(nid_data):
    """Toolflow end-to-end reaches >= the accuracy of the manual flow (it
    runs the identical phases, so accuracies must agree exactly)."""
    from repro.core import pruning
    from repro.train import lut_trainer
    cfg = paper_tasks.reduced("nid")
    data = nid_data

    dense = lut_trainer.train(cfg, data, dense=True, lasso=1e-4, steps=100)
    mappings = pruning.select_mappings(dense.params, cfg)
    sparse = lut_trainer.train(cfg, data, mappings=mappings, steps=150,
                               sgdr_t0=80)
    manual_acc = lut_trainer.accuracy(cfg, sparse.params, data,
                                      max_eval=1024)

    flow = Toolflow(cfg, pretrain_steps=100, retrain_steps=150, lasso=1e-4,
                    sgdr_t0=80)
    compiled = flow.run(data)
    flow_acc = flow.accuracy(max_eval=1024)
    assert flow_acc >= manual_acc - 1e-9, (flow_acc, manual_acc)
    assert flow_acc > 0.7  # clearly above 0.5 chance

    # folded == quantized (the artifact serves the same function)
    x = jnp.asarray(data.x_test[:256])
    np.testing.assert_array_equal(
        np.asarray(compiled.predict_codes(x)),
        np.asarray(assemble.apply_codes(flow.params, cfg, x)))
    assert set(flow.stages) == {"pretrain", "prune", "retrain", "compile"}
    assert flow.stages["prune"].metrics["coverage"]


def test_toolflow_stage_order_enforced(nid_data):
    cfg = paper_tasks.reduced("nid")
    with pytest.raises(RuntimeError, match="pretrain"):
        Toolflow(cfg).prune()
    with pytest.raises(RuntimeError, match="retrain"):
        Toolflow(cfg).compile()


def test_toolflow_random_mapping_ablation(nid_data):
    """retrain without prune == the paper's w/o-Learned-Mappings ablation."""
    cfg = paper_tasks.reduced("nid")
    flow = Toolflow(cfg, retrain_steps=40).retrain(nid_data)
    assert flow.params is not None
    assert flow.stages["retrain"].metrics["learned_mappings"] is False


def test_toolflow_state_roundtrip(nid_data, tmp_path):
    """save_state/load_state resumes mid-flow: a flow saved after prune
    retrains in a 'new process' to the same params as the uninterrupted
    one (deterministic seeds)."""
    cfg = paper_tasks.reduced("nid")
    flow = Toolflow(cfg, pretrain_steps=40, retrain_steps=30, lasso=1e-4)
    flow.pretrain(nid_data).prune()
    path = flow.save_state(str(tmp_path / "flow.npz"))

    resumed = Toolflow.load_state(path)
    assert resumed.cfg == cfg
    for a, b in zip(flow.mappings, resumed.mappings):
        if a is None:
            assert b is None
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    resumed.retrain(nid_data)
    flow.retrain()
    for a, b in zip(jax.tree.leaves(flow.params),
                    jax.tree.leaves(resumed.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# the LUT serving engine
# ---------------------------------------------------------------------------

def test_lut_engine_matches_predict():
    from repro.serve.lut_engine import LUTEngine
    cfg = paper_tasks.reduced("nid")
    params = assemble.init(jax.random.PRNGKey(11), cfg)
    compiled = pipeline.compile_network(params, cfg)
    x = np.asarray(_rand_inputs(cfg, 100, seed=12))

    eng = LUTEngine(compiled, block=32)
    logits = eng.run(x)
    np.testing.assert_allclose(logits, np.asarray(compiled.predict(x)),
                               rtol=1e-6, atol=1e-6)
    # 100 rows / block 32 -> 4 ticks, last one padded by 28 rows
    assert eng.stats.ticks == 4
    assert eng.stats.rows_padded == 28
    assert eng.stats.requests == 100


def test_lut_engine_incremental_submit():
    from repro.serve.lut_engine import LUTEngine
    cfg = paper_tasks.reduced("jsc")
    params = assemble.init(jax.random.PRNGKey(13), cfg)
    compiled = pipeline.compile_network(params, cfg)
    eng = LUTEngine(compiled, block=8)
    x = np.asarray(_rand_inputs(cfg, 5, seed=14))
    reqs = [eng.submit(row) for row in x]
    assert not any(r.done for r in reqs)
    assert eng.tick() == 5
    assert all(r.done for r in reqs)
    ref = np.asarray(compiled.predict_codes(jnp.asarray(x)))
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(r.codes, ref[i])
    assert eng.tick() == 0  # empty queue is a no-op


def test_lut_engine_async_double_buffered_matches_sync():
    """depth=2 overlaps dispatch with device compute; results, ordering
    and padding stats are identical to the synchronous engine."""
    from repro.serve.lut_engine import LUTEngine
    cfg = paper_tasks.reduced("nid")
    params = assemble.init(jax.random.PRNGKey(15), cfg)
    compiled = pipeline.compile_network(params, cfg)
    x = np.asarray(_rand_inputs(cfg, 100, seed=16))

    sync = LUTEngine(compiled, block=32, depth=1)
    async_ = LUTEngine(compiled, block=32, depth=2)
    want = sync.run(x)
    # per-tick wall time is the tracer's engine.tick span
    tracing.reset()
    tracing.enable()
    try:
        got = async_.run(x)
        spans = tracing.snapshot()["spans"]
    finally:
        tracing.disable()
        tracing.reset()
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    assert async_.stats.ticks == sync.stats.ticks == 4
    assert async_.stats.rows_padded == sync.stats.rows_padded == 28
    assert async_.inflight == 0          # drained
    n_ticks, total_s, self_s = spans["engine.tick"]
    assert n_ticks == 4 and total_s >= self_s > 0
    # two blocks retire inside ticks, two in the drain
    assert spans["engine.fetch"][0] == 4


def test_lut_engine_async_completion_trails_dispatch():
    """With depth=2 a tick dispatches without waiting: the first block's
    requests are not done until a later tick (or drain) retires it."""
    from repro.serve.lut_engine import LUTEngine
    cfg = paper_tasks.reduced("jsc")
    params = assemble.init(jax.random.PRNGKey(17), cfg)
    compiled = pipeline.compile_network(params, cfg)
    eng = LUTEngine(compiled, block=4, depth=2)
    x = np.asarray(_rand_inputs(cfg, 12, seed=18))
    reqs = [eng.submit(row) for row in x]

    assert eng.tick() == 0               # block 0 dispatched, in flight
    assert eng.inflight == 1 and not reqs[0].done
    assert eng.tick() == 4               # block 1 dispatched, block 0 retired
    assert reqs[0].done and not reqs[4].done
    assert eng.tick() == 4               # block 2 dispatched, block 1 retired
    assert eng.drain() == 4              # the only unconditional wait
    assert all(r.done for r in reqs)
    ref = np.asarray(compiled.predict_codes(jnp.asarray(x)))
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(r.codes, ref[i])


def test_lut_engine_block_and_backend_are_read_only():
    """The documented footgun — mutating engine.backend/engine.block after
    construction silently did nothing — now raises instead."""
    from repro.serve.lut_engine import LUTEngine
    cfg = paper_tasks.reduced("nid")
    params = assemble.init(jax.random.PRNGKey(19), cfg)
    compiled = pipeline.compile_network(params, cfg)
    eng = LUTEngine(compiled, block=16)
    assert eng.block == 16 and eng.backend == compiled.backend
    with pytest.raises(AttributeError, match="fixed at construction"):
        eng.block = 64
    with pytest.raises(AttributeError, match="fixed at construction"):
        eng.backend = "fused"
    with pytest.raises(ValueError, match="depth"):
        LUTEngine(compiled, depth=0)
