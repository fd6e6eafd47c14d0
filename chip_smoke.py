"""Bring-up smoke of the train -> fold -> fleet path on a TPU.

    python chip_smoke.py             # one chip: device, train, persist, serve
    python chip_smoke.py --chips 4   # only: batch-sharded executors, 4 chips

Each phase prints one line with its result and its wall seconds; any
failure exits non-zero.  Only when every phase passed does the last line
of stdout carry ``{"ok": true, "device": {...}}``.  One process drives the
chip: this script starts no child.  Weights are random or trained for a
few steps, all from ``--seed``; widths are the paper's Table-II designs.

Phases (one chip):
  device   the first JAX device is a TPU and Pallas kernels compile
  train    Toolflow on full jsc_openml: finite losses, and the folded
           network equals the quantized forward on held-out rows
  persist  save/load round trip predicts identically
  compile  each tenant's fused executor compiled once (cold or cached)
  serve    one LUTFleet, the four Table-II designs as tenants on the
           fused backend, strict deploys, a few hundred ragged requests,
           every answer equal to the take executor, no
           failure/retry/degrade in FleetStats
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

TENANTS = ("nid", "jsc_cernbox", "jsc_openml", "mnist")
BLOCK = 256


class SmokeFailure(Exception):
    """A phase found a wrong or missing result."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def phase(name: str, fn, *args):
    """Run one phase, print its line, and return its result."""
    t0 = time.perf_counter()
    try:
        result, detail = fn(*args)
    except Exception as e:
        print(f"{name}: FAIL {type(e).__name__}: {e} "
              f"({time.perf_counter() - t0:.2f}s)", flush=True)
        raise
    print(f"{name}: ok {detail} ({time.perf_counter() - t0:.2f}s)",
          flush=True)
    return result


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def device_phase(chips: int):
    import importlib.metadata

    import jax

    from repro.kernels import ops
    from repro.launch.compile_cache import use_persistent_cache

    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU: JAX's first device is {devs[0].platform!r}")
    check(len(devs) >= chips, f"{len(devs)} devices, {chips} needed")
    check(not ops.pallas_interpret(), "Pallas would run in interpret mode")
    cache = use_persistent_cache()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    return info, (f"kind={info['kind']!r} count={info['count']} "
                  f"jax={jax.__version__} "
                  f"libtpu={importlib.metadata.version('libtpu')} "
                  f"compile_cache={cache}")


def train_phase(seed: int):
    import jax
    import numpy as np

    from repro.configs import paper_tasks
    from repro.core import assemble
    from repro.data import synthetic
    from repro.pipeline import Toolflow

    cfg = paper_tasks.jsc_openml()
    data = synthetic.load("jsc_openml", n_train=4096, n_test=1024,
                          seed=seed)
    flow = Toolflow(cfg, pretrain_steps=20, retrain_steps=20, seed=seed)
    net = flow.run(data)
    losses = {s: flow.stages[s].metrics["final_loss"]
              for s in ("pretrain", "retrain")}
    check(all(np.isfinite(v) for v in losses.values()),
          f"non-finite losses {losses}")
    x = np.asarray(data.x_test, np.float32)
    want = np.asarray(jax.jit(assemble.apply_codes, static_argnums=1)(
        flow.params, cfg, x))
    got = np.asarray(net.predict_codes(x, backend="take"))
    bad = int((got != want).any(axis=-1).sum())
    check(bad == 0, f"folded != quantized forward on {bad}/{len(x)} rows")
    return net, (f"losses={ {k: round(float(v), 4) for k, v in losses.items()} }"
                 f" folded==apply_codes on {len(x)} held-out rows")


def persist_phase(net, seed: int):
    import numpy as np

    from repro.pipeline import CompiledLUTNetwork

    x = _rows(net, 257, seed)
    with tempfile.TemporaryDirectory() as d:
        for be in ("take", "fused"):
            net.compile_backend(be)      # plans ride along in the .npz
        loaded = CompiledLUTNetwork.load(net.save(os.path.join(d, "jsc")))
    for be in ("take", "fused"):
        a = np.asarray(net.predict_codes(x, backend=be))
        b = np.asarray(loaded.predict_codes(x, backend=be))
        check(np.array_equal(a, b), f"loaded copy differs on {be}")
    return loaded, f"save/load identical on take and fused ({len(x)} rows)"


def _rows(net, n: int, seed: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    return rng.uniform(-1.0, 1.0, (n, net.cfg.in_features)).astype(
        np.float32)


def init_net(task: str, seed: int):
    """A full-width Table-II design folded from random weights."""
    import jax

    from repro import pipeline
    from repro.configs import paper_tasks
    from repro.core import assemble

    cfg = paper_tasks.task_config(task)
    params = assemble.init(jax.random.PRNGKey(seed), cfg)
    return pipeline.compile_network(params, cfg, backend="fused")


def compile_phase(nets):
    """Build every tenant's fused block program; report seconds to trace
    it, to compile it (a persistent-cache hit when warm) and for the
    first call."""
    import jax
    import numpy as np

    secs = {}
    for task, net in nets.items():
        x = np.zeros((BLOCK, net.cfg.in_features), np.float32)
        t0 = time.perf_counter()
        ex = net.compile_backend("fused")
        lowered = ex._both.lower(jax.ShapeDtypeStruct(x.shape, x.dtype))
        t1 = time.perf_counter()
        lowered.compile()
        t2 = time.perf_counter()
        jax.block_until_ready(ex.codes_and_logits(x))
        t3 = time.perf_counter()
        secs[task] = {"trace": round(t1 - t0, 2),
                      "compile": round(t2 - t1, 2),
                      "first_call": round(t3 - t2, 2)}
    return None, f"fused executor seconds {secs}"


def serve_phase(nets, seed: int):
    import jax
    import numpy as np

    from repro import backends
    from repro.kernels import ops
    from repro.kernels.lut_cascade import address_passes, scan_steps
    from repro.pipeline import CompiledLUTNetwork
    from repro.serve.fleet import LUTFleet

    fleet = LUTFleet(block=BLOCK)
    with tempfile.TemporaryDirectory() as d:
        for task, net in nets.items():
            fleet.register(task, net, backend="fused")
            # v2: the same artifact through save/load, smoke-checked on
            # fused against v1's take reference; a rejection raises
            cand = CompiledLUTNetwork.load(net.save(os.path.join(d, task)))
            cand.backend = "fused"
            ev = fleet.deploy(task, cand, strict=True,
                              reference=fleet.registry.get(task).reference)
            check(ev.ok and ev.to_version == 2, f"{task}: deploy {ev}")

    rng = np.random.default_rng(seed)
    sizes = (1, 3, 8, 33, 100, 257)
    sent = []
    for _ in range(300):
        task = TENANTS[rng.integers(len(TENANTS))]
        x = _rows(nets[task], int(rng.choice(sizes)),
                  int(rng.integers(2 ** 31)))
        reqs, decision = fleet.submit_many(task, x)
        check(decision.accept == len(x), f"{task}: admission {decision}")
        sent.append((task, x, reqs))
        fleet.tick()
    fleet.pump()

    wrong = 0
    for task in TENANTS:
        mine = [(x, reqs) for t, x, reqs in sent if t == task]
        want = _take_codes(fleet.registry.get(task).net,
                           np.concatenate([x for x, _ in mine]))
        got = np.stack([r.codes for _, reqs in mine for r in reqs])
        wrong += int((got != want).any(axis=-1).sum())
    rows = sum(len(x) for _, x, _ in sent)
    check(wrong == 0, f"{wrong}/{rows} rows differ from take")

    fused = backends.get("fused")
    for task in TENANTS:
        st = fleet.stats(task)
        bad = {k: getattr(st, k) for k in ("failures", "retries", "degrades",
                                           "breaker_trips", "deadline_hits",
                                           "shed")
               if getattr(st, k)}
        check(not bad and st.completed == st.requests,
              f"{task}: FleetStats {st.summary()}")
        entry = fleet.registry.get(task)
        plan = entry.net._plans["fused"]
        tuning = fused.device_tuning(plan)
        ex = fleet.registry.executor(task, backend="fused")
        hlo = ex._both.lower(jax.ShapeDtypeStruct(
            (BLOCK, entry.net.cfg.in_features), np.float32)).compile()
        impl = tuning.impl or ("xla" if ops.pallas_interpret() else "pallas")
        layers = plan.meta["layers"]
        steps = scan_steps(layers, mode=tuning.mode,
                           unit_tile=tuning.unit_tile)
        print(f"  tenant {task}: impl={impl} "
              f"mode={tuning.mode} block_b={tuning.block_b} "
              f"unit_tile={tuning.unit_tile} "
              f"device_kind={tuning.device_kind!r} address_passes="
              f"{address_passes(plan.buffers['amat'], layers)} "
              f"scan_steps={steps} "
              f"tpu_custom_call={'tpu_custom_call' in hlo.as_text()} "
              f"completed={st.completed} ticks={st.ticks}", flush=True)
        check("tpu_custom_call" in hlo.as_text(),
              f"{task}: no Pallas kernel in the fused program")
    return None, (f"{len(sent)} requests / {rows} rows over {len(TENANTS)} "
                  f"tenants bit-identical to take; no failure, retry, "
                  f"degrade or breaker trip")


def _take_codes(net, x):
    """The take oracle's codes for ``x``, in zero-padded ``BLOCK``-row
    chunks: one program shape per tenant (one ~8k-row take call per
    tenant measured five times slower on a TPU v5e)."""
    import numpy as np

    ex = net.compile_backend("take")
    xp = np.concatenate([x, np.zeros(((-len(x)) % BLOCK, x.shape[1]),
                                     x.dtype)])
    return np.concatenate([np.asarray(ex.predict_codes(xp[i:i + BLOCK]))
                           for i in range(0, len(xp), BLOCK)])[:len(x)]


def mesh_phase(seed: int, chips: int):
    """take and fused executors batch-sharded over a ``("data",)`` mesh of
    ``chips`` devices, bit-for-bit against the one-chip executor."""
    import jax
    import numpy as np

    from repro.launch.mesh import make_serving_mesh

    mesh = make_serving_mesh(chips)
    for task in TENANTS:
        net = init_net(task, seed)
        for be in ("take", "fused"):
            one = net.compile_backend(be)
            placed = net.compile_backend(be, mesh=mesh)
            for n in (33, 1024):
                x = _rows(net, n, seed + n)
                out = placed.predict_codes(x)
                check(np.array_equal(np.asarray(one.predict_codes(x)),
                                     np.asarray(out)),
                      f"{task}/{be}: {n} rows differ on the mesh")
            if be == "fused":
                hlo = placed._both.lower(jax.ShapeDtypeStruct(
                    x.shape, x.dtype,
                    sharding=placed.placement.input_sharding())).compile()
                check("tpu_custom_call" in hlo.as_text(),
                      f"{task}: no Pallas kernel in the sharded program")
            held = {s.device for s in out.addressable_shards
                    if s.data.shape[0] == 1024 // chips}
            check(held == set(mesh.devices.flat),
                  f"{task}/{be}: shards on {len(held)} of {chips} devices")
            print(f"  {task}/{be}: 1024 and 33 rows identical to one chip; "
                  f"each of {chips} devices holds a {1024 // chips}-row "
                  f"shard", flush=True)
    return None, (f"{len(TENANTS)} tenants x take/fused on a {chips}-way "
                  f"('data',) mesh, bit-identical")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the batch-sharded mesh phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    try:
        dev = phase("device", device_phase, args.chips)
        if args.chips > 1:
            phase("mesh", mesh_phase, args.seed, args.chips)
        else:
            trained = phase("train", train_phase, args.seed)
            trained = phase("persist", persist_phase, trained, args.seed)
            trained.backend = "fused"
            nets = {"nid": init_net("nid", args.seed),
                    "jsc_cernbox": init_net("jsc_cernbox", args.seed),
                    "jsc_openml": trained,
                    "mnist": init_net("mnist", args.seed)}
            phase("compile", compile_phase, nets)
            phase("serve", serve_phase, nets, args.seed)
    except Exception:
        traceback.print_exc()
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
