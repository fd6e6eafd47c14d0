"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp
oracles.  LUT lookup must be bit-exact; float kernels allclose.

Property-based (hypothesis) variants live in test_properties.py, guarded by
``pytest.importorskip`` — hypothesis is a dev dependency.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.lut_gather import lut_lookup_pallas
from repro.kernels.subnet_mlp import unit_affine_pallas


# ---------------------------------------------------------------------------
# lut_gather
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("batch,units,entries", [
    (1, 1, 2), (7, 3, 16), (64, 10, 64), (33, 17, 256), (128, 5, 1024),
])
def test_lut_lookup_pallas_exact(batch, units, entries):
    k1, k2 = jax.random.split(jax.random.PRNGKey(batch * units))
    table = jax.random.randint(k1, (units, entries), 0, 255, dtype=jnp.int32)
    addr = jax.random.randint(k2, (batch, units), 0, entries,
                              dtype=jnp.int32)
    out = lut_lookup_pallas(table, addr, interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ref.lut_lookup_ref(table, addr)))


@pytest.mark.parametrize("batch,units,entries", [
    (1, 1, 2), (33, 7, 64), (50, 12, 256),
])
def test_lut_lookup_impls_agree_fixed(batch, units, entries):
    """All three lookup backends agree bit-exactly; the randomized sweep is
    in test_properties.py."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(batch))
    table = jax.random.randint(k1, (units, entries), 0, 2 ** 8,
                               dtype=jnp.int32)
    addr = jax.random.randint(k2, (batch, units), 0, entries,
                              dtype=jnp.int32)
    a = ops.lut_lookup(table, addr, impl="take")
    b = ops.lut_lookup(table, addr, impl="onehot")
    c = ops.lut_lookup(table, addr, impl="pallas")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_pallas_interpret_flag_takes_effect_after_first_trace():
    """set_pallas_interpret must not be defeated by an earlier trace of the
    same shapes (the interpret mode is a static arg, so flips retrace)."""
    if ops.on_tpu():
        pytest.skip("compiled Pallas is valid on TPU; nothing to observe")
    table = jnp.arange(32, dtype=jnp.int32).reshape(2, 16)
    addr = jnp.ones((4, 2), jnp.int32)
    out = ops.lut_lookup(table, addr, impl="pallas")  # traces interpret=True
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ref.lut_lookup_ref(table, addr)))
    ops.set_pallas_interpret(False)
    try:
        # compiled Pallas is unsupported on CPU: the flip must be honored
        # (a stale interpret=True executable would silently succeed)
        with pytest.raises(Exception, match="[Ii]nterpret"):
            ops.lut_lookup(table, addr, impl="pallas")
    finally:
        ops.set_pallas_interpret(None)


# ---------------------------------------------------------------------------
# subnet_mlp
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("batch,units,din,dout", [
    (4, 3, 6, 16), (130, 21, 4, 8), (16, 64, 12, 1),
])
def test_unit_affine_pallas(batch, units, din, dout, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(ks[0], (batch, units, din), dtype)
    w = jax.random.normal(ks[1], (units, din, dout), dtype)
    b = jax.random.normal(ks[2], (units, dout), dtype)
    for act in (False, True):
        y = unit_affine_pallas(x, w, b, activate=act, interpret=True)
        y_ref = ref.unit_affine_ref(x, w, b, activate=act)
        tol = 1e-5 if dtype == jnp.float32 else 3e-2
        np.testing.assert_allclose(
            np.asarray(y, np.float32), np.asarray(y_ref, np.float32),
            rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("sq,skv,causal,window", [
    (64, 64, True, None),
    (64, 64, False, None),
    (100, 100, True, 32),
    (1, 96, True, None),       # decode
    (1, 96, True, 24),         # SWA decode
])
def test_flash_attention_pallas(hq, hkv, sq, skv, causal, window):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    d = 32
    q = jax.random.normal(ks[0], (2, hq, sq, d))
    k = jax.random.normal(ks[1], (2, hkv, skv, d))
    v = jax.random.normal(ks[2], (2, hkv, skv, d))
    q_offset = skv - sq
    out = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 q_offset=q_offset, block_q=32, block_k=32,
                                 interpret=True)
    out_ref = ref.mha_ref(q, k, v, causal=causal, window=window,
                          q_offset=q_offset)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_pallas_matches_model_scan_flash():
    """Pallas kernel == the model stack's scan-based flash (same math)."""
    from repro.models import attention
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    b, hkv, g, s, d = 2, 2, 2, 64, 16
    q = jax.random.normal(ks[0], (b, hkv, g, s, d))
    k = jax.random.normal(ks[1], (b, hkv, s, d))
    v = jax.random.normal(ks[2], (b, hkv, s, d))
    pos = jnp.arange(s, dtype=jnp.int32)
    o_scan = attention.flash_scan(q, k, v, causal=True, window=None,
                                  q_positions=pos, k_positions=pos,
                                  block_k=16)
    q4 = q.reshape(b, hkv * g, s, d)
    o_pallas = flash_attention_pallas(q4, k, v, causal=True, block_q=16,
                                      block_k=16, interpret=True)
    np.testing.assert_allclose(
        np.asarray(o_scan.reshape(b, hkv * g, s, d)), np.asarray(o_pallas),
        rtol=2e-5, atol=2e-5)


def test_flash_gradient_flows():
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    from repro.models import attention
    q = jax.random.normal(ks[0], (1, 2, 2, 32, 8))
    k = jax.random.normal(ks[1], (1, 2, 32, 8))
    v = jax.random.normal(ks[2], (1, 2, 32, 8))
    pos = jnp.arange(32, dtype=jnp.int32)

    def f(q, k, v):
        return jnp.sum(attention.flash_scan(
            q, k, v, causal=True, window=None, q_positions=pos,
            k_positions=pos, block_k=8) ** 2)

    grads = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    for gr in grads:
        assert bool(jnp.isfinite(gr).all())
        assert float(jnp.abs(gr).max()) > 0


# ---------------------------------------------------------------------------
# lut_cascade: every execution path vs the per-layer take oracle
# ---------------------------------------------------------------------------

def _fused_fixture(task="nid", seed=0):
    """(plan, take_plan, cascade pieces) for a random-init paper config."""
    from repro import backends, pipeline
    from repro.configs import paper_tasks
    from repro.core import assemble

    cfg = paper_tasks.reduced(task)
    params = assemble.init(jax.random.PRNGKey(seed), cfg)
    compiled = pipeline.compile_network(params, cfg)
    plan = compiled.compile_backend("fused").plan
    layers = tuple(tuple(int(v) for v in lm) for lm in plan.meta["layers"])
    mappings = tuple(jnp.asarray(plan.buffers[f"map_{l}"], jnp.int32)
                     if f"map_{l}" in plan.buffers else None
                     for l in range(len(layers)))
    codes = jnp.asarray(np.random.RandomState(seed + 1).randint(
        0, plan.meta["input_span"], size=(33, cfg.in_features)), jnp.int32)
    ref_out = np.asarray(
        backends.get("take").run(compiled.compile_backend("take").plan,
                                 codes))
    return plan, layers, mappings, codes, ref_out


@pytest.mark.parametrize("task", ["nid", "jsc"])
def test_lut_cascade_xla_matches_oracle(task):
    from repro.kernels.lut_cascade import lut_cascade_xla

    plan, layers, mappings, codes, ref_out = _fused_fixture(task)
    got = np.asarray(lut_cascade_xla(
        codes, jnp.asarray(plan.buffers["tables"]), mappings, layers=layers))
    np.testing.assert_array_equal(got, ref_out)


def _drawn_fixture(kind, seed=0):
    """(plan, layers, mappings, codes, take oracle's codes) for a small
    network of drawn tables and mappings whose addresses need more than
    one bf16 MXU pass:

    * ``dup_fan_in``: a 3-bit, 4-input mapping layer whose unit 0 reads
      one input at fan-in positions 0 and 3, so its ``amat`` entry is
      2^9 + 2^0 = 513, two bf16 pieces;
    * ``wide_codes``: a 9-bit hidden layer, so the codes fed to the next
      layer take two 8-bit slices."""
    from repro import backends
    from repro.pipeline import CompiledLUTNetwork, config_from_dict

    in_features, input_bits, shapes = {       # (units, fan_in, bits, asm)
        "dup_fan_in": (8, 3, [(8, 4, 2, False), (2, 4, 2, True)]),
        "wide_codes": (8, 2, [(8, 2, 9, False), (4, 1, 2, False)]),
    }[kind]
    specs = [dict(zip(("units", "fan_in", "bits", "assemble"), s))
             for s in shapes]
    cfg = config_from_dict(dict(in_features=in_features,
                                input_bits=input_bits, layers=specs))
    rng = np.random.RandomState(seed)
    tables, maps, prev, in_bits = [], [], in_features, input_bits
    for spec in specs:
        tables.append(rng.randint(0, 2 ** spec["bits"], size=(
            spec["units"], 2 ** (in_bits * spec["fan_in"]))))
        maps.append(None if spec["assemble"] else rng.randint(
            0, prev, size=(spec["units"], spec["fan_in"])))
        prev, in_bits = spec["units"], spec["bits"]
    if kind == "dup_fan_in":
        maps[0][0, 3] = maps[0][0, 0]
    net = CompiledLUTNetwork(cfg, tables, maps, 0.0, 0.0, backend="fused")
    plan = net.compile_backend("fused").plan
    layers = tuple(tuple(int(v) for v in lm) for lm in plan.meta["layers"])
    codes = jnp.asarray(rng.randint(0, 2 ** input_bits,
                                    size=(33, in_features)), jnp.int32)
    ref_out = np.asarray(
        backends.get("take").run(net.compile_backend("take").plan, codes))
    return plan, layers, None, codes, ref_out


_PALLAS_TILINGS = [("resident", 8), ("streamed", 4), ("streamed", 8),
                   ("streamed", 16), ("streamed", 128), ("streamed", 256)]


@pytest.mark.parametrize("net,mode,unit_tile,passes", [
    pytest.param("nid", mode, tile, 1, id=f"{mode}-{tile}")
    for mode, tile in _PALLAS_TILINGS] + [
    pytest.param(net, mode, tile, 2, id=f"{net}-{mode}-{tile}")
    for net in ("dup_fan_in", "wide_codes")
    for mode, tile in (("resident", 8), ("streamed", 8), ("streamed", 128))])
def test_lut_cascade_pallas_modes_match_oracle(net, mode, unit_tile, passes):
    """Resident and streamed Pallas tilings (interpret mode), ragged batch
    (33 is off every block size, forcing the padded tail), bit-exact with
    the address formed in as many one-pass bf16 matmuls as the plan needs:
    one for a paper config, two for a repeated fan-in index or 9-bit
    codes (the ``lut_cascade.address_passes`` counter reads it)."""
    from repro import tracing
    from repro.kernels.lut_cascade import address_passes, lut_cascade_pallas

    plan, layers, mappings, codes, ref_out = (
        _fused_fixture() if net == "nid" else _drawn_fixture(net))
    assert address_passes(plan.buffers["amat"], layers) == passes
    tracing.reset()
    tracing.enable()
    try:
        got = np.asarray(lut_cascade_pallas(
            codes, jnp.asarray(plan.buffers["amat"]),
            jnp.asarray(plan.buffers["tables"]), layers=layers,
            block_b=16, mode=mode, unit_tile=unit_tile, interpret=True))
        counted = tracing.snapshot()["counters"]["lut_cascade.address_passes"]
    finally:
        tracing.disable()
        tracing.reset()
    np.testing.assert_array_equal(got, ref_out)
    assert counted == passes


@pytest.mark.parametrize("name", ["jsc_openml", "mnist", "jsc_cernbox"])
def test_bench_configs_take_one_address_pass(name):
    """Every benchmark network, as the benchmark draws it, forms its
    addresses in a single bf16 MXU pass (jsc_cernbox's 8-bit input codes
    fill the whole bf16-exact slice)."""
    from bench import run as brun
    from repro.kernels.lut_cascade import address_passes

    cfg = brun.load_json(os.path.dirname(brun.__file__), "configs",
                         name + ".json")
    net, _, _ = brun.build_network(cfg)
    plan = net.compile_backend("fused").plan
    assert address_passes(plan.buffers["amat"], plan.meta["layers"]) == 1


def _table_ii_plan(task: str, seed: int = 0):
    """A Table-II design's config and fused plan over drawn tables and
    mappings."""
    from repro.configs import paper_tasks
    from repro.pipeline import CompiledLUTNetwork

    cfg = paper_tasks.task_config(task)
    rng = np.random.RandomState(seed)
    tables, maps = [], []
    for l, spec in enumerate(cfg.layers):
        tables.append(rng.randint(0, 2 ** spec.bits, size=(
            spec.units, 2 ** (cfg.in_bits(l) * spec.fan_in))))
        maps.append(None if spec.assemble else rng.randint(
            0, cfg.prev_width(l), size=(spec.units, spec.fan_in)))
    net = CompiledLUTNetwork(cfg, tables, maps, 0.0, 0.0, backend="fused")
    return cfg, net.compile_backend("fused").plan


@pytest.mark.parametrize("task,steps", [
    ("nid", 320), ("jsc_cernbox", 2560), ("jsc_openml", 640),
    ("mnist", 2688)])
def test_scan_steps_counter_per_table_ii_design(task, steps):
    """``lut_cascade.scan_steps`` counts, once per kernel trace, the
    select-scan steps of one batch tile at the tiling the v5e tuner
    picks: lane chunks x table entries.  jsc_cernbox scans its 256-entry
    tables over the same 10 lane chunks as jsc_openml's 64-entry ones,
    4x the steps, and still forms its addresses in one pass."""
    from repro import tracing
    from repro.kernels import autotune
    from repro.kernels.lut_cascade import lut_cascade_pallas, scan_steps

    cfg, plan = _table_ii_plan(task)
    layers = tuple(tuple(int(v) for v in lm) for lm in plan.meta["layers"])
    t = autotune.pick_tuning(layers, device="TPU v5 lite")
    assert scan_steps(layers, mode=t.mode, unit_tile=t.unit_tile) == steps

    def kernel(codes):
        return lut_cascade_pallas(
            codes, plan.buffers["amat"], plan.buffers["tables"],
            layers=layers, block_b=t.block_b, mode=t.mode,
            unit_tile=t.unit_tile, interpret=True)

    tracing.reset()
    tracing.enable()
    try:
        jax.eval_shape(kernel, jax.ShapeDtypeStruct(
            (t.block_b, cfg.in_features), jnp.int32))
        counted = tracing.snapshot()["counters"]
    finally:
        tracing.disable()
        tracing.reset()
    assert counted["lut_cascade.scan_steps"] == steps
    assert counted["lut_cascade.address_passes"] == 1


@pytest.mark.parametrize("impl", ["xla", "pallas", None])
def test_lut_cascade_dispatch_honors_pinned_impl(impl):
    """ops.lut_cascade must honor tuning.impl (and auto-resolve None)
    with identical results on every route."""
    from repro.kernels.autotune import KernelTuning

    plan, layers, mappings, codes, ref_out = _fused_fixture()
    tuning = KernelTuning(impl=impl, block_b=16)
    got = np.asarray(ops.lut_cascade(
        codes, jnp.asarray(plan.buffers["amat"]),
        jnp.asarray(plan.buffers["tables"]), layers=layers,
        mappings=mappings, tuning=tuning))
    np.testing.assert_array_equal(got, ref_out)


def test_lut_cascade_xla_requires_v2_metadata():
    plan, layers, mappings, codes, _ = _fused_fixture()
    with pytest.raises(ValueError, match="v2|mappings"):
        ops.lut_cascade(codes, jnp.asarray(plan.buffers["amat"]),
                        jnp.asarray(plan.buffers["tables"]),
                        layers=tuple(lm[:4] for lm in layers),
                        mappings=None,
                        tuning={"impl": "xla"})
