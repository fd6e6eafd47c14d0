"""Distribution tests that need multiple devices run in a SUBPROCESS with
xla_force_host_platform_device_count (the main test process must keep
seeing 1 CPU device).  Covers: dry-run path on a reduced mesh, pipeline
parallelism vs single-device reference, compressed psum, sharding specs."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_subprocess(code: str, devices: int = 8, env_extra=None) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.update(env_extra or {})
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_sharding_specs_cover_params():
    """Every param leaf has a spec leaf (tree prefix match) per arch."""
    from repro.configs import lm_archs
    from repro.launch import steps
    for arch in lm_archs.ARCHS:
        cfg = lm_archs.get(arch)
        params = steps.abstract_params(cfg)
        specs = steps.param_spec_tree(cfg)
        # tree_map raises if structures are incompatible
        merged = jax.tree.map(lambda a, s: (a.ndim, s), params, specs,
                              is_leaf=lambda x: hasattr(x, "ndim"))
        for nd, spec in jax.tree.leaves(
                merged, is_leaf=lambda x: isinstance(x, tuple)):
            assert len(spec) <= nd, (arch, nd, spec)


def test_dryrun_reduced_mesh_subprocess():
    """The EXACT dry-run code path on a 2x2(x2) placeholder mesh."""
    out = run_subprocess("""
        import os
        os.environ.setdefault("REPRO_DRYRUN_DEVICES", "8")
        os.environ["REPRO_MESH_SHAPE"] = "2,4"
        os.environ["REPRO_MESH_SHAPE_MULTI"] = "2,2,2"
        from repro.launch.dryrun import run_cell
        import json
        for mesh in ("single", "multi"):
            rec = run_cell("gemma-2b", "train_4k", mesh, verbose=False)
            assert rec["status"] == "ok", rec
            assert rec["collectives"]["total_bytes"] > 0
            print(json.dumps({"mesh": mesh, "ok": True}))
        rec = run_cell("qwen2-72b", "long_500k", "single", verbose=False)
        assert rec["status"] == "skipped"
        rec = run_cell("rwkv6-7b", "decode_32k", "single", verbose=False)
        assert rec["status"] == "ok", rec
        print("DONE")
    """, devices=8, env_extra={"REPRO_DRYRUN_DEVICES": "8"})
    assert "DONE" in out


def test_pipeline_parallel_matches_reference():
    out = run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.dist.pipeline import make_pipelined_fn
        from repro.launch.mesh import make_mesh

        n_stages, layers_per_stage = 4, 2
        L = n_stages * layers_per_stage
        D = 16

        def layer_fn(w, x):
            return jnp.tanh(x @ w)

        ws = jax.random.normal(jax.random.PRNGKey(0), (L, D, D)) * 0.5
        xs = jax.random.normal(jax.random.PRNGKey(1), (6, 2, 8, D))

        # reference: plain scan over all layers per microbatch
        def ref_fwd(x):
            def body(h, w):
                return layer_fn(w, h), None
            h, _ = jax.lax.scan(body, x, ws)
            return h
        ref = jax.vmap(ref_fwd)(xs)

        mesh = make_mesh((n_stages,), ("pipe",))
        fn = make_pipelined_fn(layer_fn, mesh, axis_name="pipe",
                               n_stages=n_stages,
                               layers_per_stage=layers_per_stage)
        with mesh:
            out = fn(ws.reshape(n_stages, layers_per_stage, D, D)
                     .reshape(n_stages * layers_per_stage, D, D), xs)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        print("PIPE-OK")
    """, devices=4)
    assert "PIPE-OK" in out


def test_compressed_psum_subprocess():
    out = run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.dist import compress
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((8,), ("d",))
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
        err = jnp.zeros((8, 64))

        def f(g, e):
            out, ne = compress.compressed_psum({"g": g[0]}, {"g": e[0]}, "d")
            return out["g"][None], ne["g"][None]

        fn = jax.shard_map(f, mesh=mesh, in_specs=(P("d"), P("d")),
                           out_specs=(P("d"), P("d")), check_vma=False)
        out, ne = fn(g, err)
        ref = jnp.mean(g, axis=0)
        # every shard holds the (approximate) mean
        for i in range(8):
            np.testing.assert_allclose(np.asarray(out[i]), np.asarray(ref),
                                       atol=0.05)
        print("PSUM-OK")
    """, devices=8)
    assert "PSUM-OK" in out


def test_elastic_restore_under_new_mesh(tmp_path):
    """Save params unsharded, restore with explicit shardings on a different
    logical mesh (1x1 here; the subprocess covers 2x4)."""
    from repro.ckpt import checkpoint
    from repro.dist import sharding as shd
    from repro.launch import mesh as mesh_mod

    tree = {"w": jnp.arange(16.0).reshape(4, 4)}
    checkpoint.save(str(tmp_path), 1, tree)
    mesh = mesh_mod.make_host_mesh()
    sh = shd.to_shardings(mesh, {"w": jax.sharding.PartitionSpec(
        "data", "model")})
    restored, _ = checkpoint.restore(str(tmp_path), tree, shardings=sh)
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(tree["w"]))
    assert restored["w"].sharding.spec == sh["w"].spec
