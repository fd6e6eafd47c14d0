"""Benchmark harness: one function per paper table/figure + kernel
micro-benchmarks + the roofline report.

Prints ``name,us_per_call,derived`` CSV rows (harness contract) followed by
the structured tables.  ``python -m benchmarks.run [--fast] [--only NAME]``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# the throughput sweep's mesh axis needs multiple host devices, which must
# be requested before jax initializes — hence before the import below.
# The sweep runs by default (no --only) and for any --only spelling that
# names it (`--only throughput`, `--only=throughput`).
_argv = sys.argv[1:]
if (not any(a.startswith("--only") for a in _argv)
        or any("throughput" in a for a in _argv)):
    from benchmarks.lut_throughput import ensure_host_devices
    ensure_host_devices()

import jax
import jax.numpy as jnp
import numpy as np


def _time_call(fn, *args, reps: int = 5, warmup: int = 2) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e6  # us


def bench_kernels(rows: list) -> None:
    """Micro-benchmarks: LUT lookup impls + folded vs quantized inference.

    (CPU numbers — structural comparison only; the TPU story is in the
    roofline tables.)"""
    from repro.kernels import ops

    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    table = jax.random.randint(k1, (256, 64), 0, 255, dtype=jnp.int32)
    addr = jax.random.randint(k2, (4096, 256), 0, 64, dtype=jnp.int32)
    for impl in ("take", "onehot", "pallas"):
        us = _time_call(lambda t, a, i=impl: ops.lut_lookup(t, a, impl=i),
                        table, addr)
        rows.append((f"lut_lookup_{impl}", us,
                     "batch=4096 units=256 entries=64"))

    from repro import pipeline
    from repro.configs import paper_tasks
    from repro.core import assemble
    from repro.data import synthetic
    from repro.serve.lut_engine import LUTEngine
    cfg = paper_tasks.reduced("nid")
    data = synthetic.load("nid", n_train=64, n_test=2048)
    params = assemble.init(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(data.x_test[:1024])
    compiled = pipeline.compile_network(params, cfg)
    q_fwd = jax.jit(lambda xx: assemble.apply_codes(params, cfg, xx))
    rows.append(("nid_quantized_forward", _time_call(q_fwd, x), "batch=1024"))
    from repro import backends as lut_backends_reg
    for impl in lut_backends_reg.available():
        us = _time_call(lambda xx, i=impl: compiled.predict_codes(
            xx, backend=i), x)
        rows.append((f"nid_folded_forward_{impl}", us,
                     "batch=1024 (pure table lookups)"))
    eng = LUTEngine(compiled, block=256)
    us = _time_call(lambda xx: eng.run(np.asarray(xx)), x)
    rows.append(("nid_lut_engine", us,
                 "batch=1024 via 256-row micro-batching engine"))


def bench_backends(rows: list, fast: bool) -> None:
    """Registered-backend sweep (writes BENCH_lut_backends.json)."""
    from benchmarks import lut_backends
    t0 = time.time()
    res = lut_backends.sweep(**(lut_backends.FAST_KW if fast else {}))
    lut_backends.write_results(res)
    cell = res["tasks"]["nid"]["cells"][-1]
    rows.append(("lut_backend_sweep", (time.time() - t0) * 1e6,
                 "fused speedup vs take (nid) "
                 f"{cell['speedup_vs_take'].get('fused')}x"))


def bench_throughput(rows: list, fast: bool) -> None:
    """Serving-throughput sweep (writes BENCH_lut_throughput.json)."""
    from benchmarks import lut_throughput
    t0 = time.time()
    res = lut_throughput.sweep(
        **(lut_throughput.FAST_KW if fast else {}))
    lut_throughput.write_results(res)
    big = [c for c in res["engine"] if c["block"] >= 256]
    best = max(big, key=lambda c: c["async_speedup"]) if big else None
    derived = (f"async speedup {best['async_speedup']}x "
               f"({best['backend']}@{best['block']})" if best else "")
    rows.append(("lut_throughput_sweep", (time.time() - t0) * 1e6, derived))


def bench_fleet(rows: list, fast: bool) -> None:
    """Multi-tenant fleet serving sweep (writes BENCH_fleet.json)."""
    from benchmarks import fleet_serving
    t0 = time.time()
    res = fleet_serving.sweep(**(fleet_serving.FAST_KW if fast else {}))
    fleet_serving.write_results(res)
    on = res["online"]
    rows.append(("fleet_serving_sweep", (time.time() - t0) * 1e6,
                 f"online speedup {on['speedup_vs_isolated_sync']}x "
                 f"({on['fleet_blocks']} vs {on['isolated_blocks']} blocks)"))


def bench_search(rows: list, fast: bool) -> None:
    """Assembly-search sweep (writes BENCH_assembly_search.json)."""
    from benchmarks import assembly_search
    t0 = time.time()
    if fast:
        res = assembly_search.sweep()  # smoke budget, 2 reduced tasks
    else:
        res = assembly_search.sweep(
            tasks=("nid_reduced", "jsc_reduced", "mnist_reduced"),
            smoke=False)
    assembly_search.write_results(res)
    derived = "; ".join(
        f"{task}: {t['frontier_points']}pt best_acc={t['best_accuracy']}"
        for task, t in res["tasks"].items())
    rows.append(("assembly_search_sweep", (time.time() - t0) * 1e6, derived))


def bench_stream(rows: list, fast: bool) -> None:
    """Stateful stream serving sweep (writes BENCH_stream.json)."""
    from benchmarks import stream_serving
    t0 = time.time()
    res = stream_serving.sweep(
        **(stream_serving.FAST_KW if fast else {}))
    stream_serving.write_results(res)
    peak = max(res["scaling"], key=lambda p: p["streams"])
    rows.append(("stream_serving_sweep", (time.time() - t0) * 1e6,
                 f"{peak['streams']} streams {peak['steps_per_s']} steps/s "
                 f"p99 {peak['p99_step_us']}us"))


def bench_chaos(rows: list, fast: bool) -> None:
    """Fault-injected chaos soak (writes BENCH_chaos.json)."""
    from benchmarks import chaos_soak
    t0 = time.time()
    res = chaos_soak.sweep(**(chaos_soak.FAST_KW if fast else {}))
    chaos_soak.write_results(res)
    worst = max(res["scenarios"].values(),
                key=lambda sc: sc["recovery_p99_ms"])
    rows.append(("chaos_soak", (time.time() - t0) * 1e6,
                 f"{len(res['scenarios'])} fault classes, worst recovery "
                 f"p99 {worst['recovery_p99_ms']}ms ({worst['name']})"))


def bench_tables(rows: list, fast: bool) -> dict:
    from benchmarks import paper_tables

    out = {}
    t0 = time.time()
    out["table2"] = paper_tables.table2()
    rows.append(("table2_accuracy", (time.time() - t0) * 1e6,
                 json.dumps(out["table2"][0])[:80].replace(",", ";")))
    t0 = time.time()
    out["table3"] = paper_tables.table3()
    rows.append(("table3_pipelining", (time.time() - t0) * 1e6,
                 f"{len(out['table3'])} rows"))
    t0 = time.time()
    out["table4"] = paper_tables.table4()
    rows.append(("table4_area_delay", (time.time() - t0) * 1e6,
                 f"{len(out['table4'])} rows"))
    t0 = time.time()
    out["fig2"] = paper_tables.fig2_assembly_scaling()
    rows.append(("fig2_assembly_scaling", (time.time() - t0) * 1e6,
                 f"max reduction {out['fig2'][-1]['reduction']}x"))
    t0 = time.time()
    out["fig5"] = paper_tables.fig5(seeds=(0,) if fast else (0, 1, 2))
    rows.append(("fig5_ablation", (time.time() - t0) * 1e6,
                 f"{len(out['fig5'])} rows"))
    return out


def bench_roofline(rows: list) -> None:
    from benchmarks import roofline
    table = roofline.build_table()
    ok = [r for r in table if r.get("status") == "ok"]
    rows.append(("roofline_cells", 0.0,
                 f"{len(ok)} analyzed / {len(table)} records"))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--only", default=None,
                    choices=["kernels", "backends", "throughput", "tables",
                             "roofline", "search", "fleet", "stream",
                             "chaos"])
    args = ap.parse_args()

    from repro.launch.compile_cache import use_persistent_cache
    use_persistent_cache()

    rows: list = []
    outputs = {}
    if args.only in (None, "kernels"):
        bench_kernels(rows)
    if args.only in (None, "backends"):
        bench_backends(rows, args.fast)
    if args.only in (None, "throughput"):
        bench_throughput(rows, args.fast)
    if args.only in (None, "search"):
        bench_search(rows, args.fast)
    if args.only in (None, "fleet"):
        bench_fleet(rows, args.fast)
    if args.only in (None, "stream"):
        bench_stream(rows, args.fast)
    if args.only in (None, "chaos"):
        bench_chaos(rows, args.fast)
    if args.only in (None, "tables"):
        outputs.update(bench_tables(rows, args.fast))
    if args.only in (None, "roofline"):
        bench_roofline(rows)

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")

    for name, table in outputs.items():
        print(f"\n=== {name} ===")
        for row in table:
            print(json.dumps(row))

    out_dir = os.path.join(os.path.dirname(__file__), "..", "experiments")
    os.makedirs(out_dir, exist_ok=True)
    if outputs:
        with open(os.path.join(out_dir, "bench_results.json"), "w") as f:
            json.dump(outputs, f, indent=2)

    if args.only in (None, "roofline"):
        from benchmarks import roofline
        roofline.main()


if __name__ == "__main__":
    main()
