"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` names the cell; the cell names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); each per-layer metric is read by
``bench/metrics/<name>.py``, or by ``bench/metrics/<base>.py`` for a name
``<base>.<suffix>``.  Set-up (JAX start, the configuration's network
drawn from its ``network_seed``, planning, compilation through the
persistent cache, warm-up of the cell's own shapes) is timed as
``setup_s``; then the mix, drawn from ``--seed``, runs for ``--seconds``;
then, outside the window, the answers it kept are compared with the plain
reference (``bench/reference.py``).  ``--trace 1`` runs the same window
under the profiler and reports the per-layer metrics instead of the
end-to-end ones.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, in a traced run ``breakdown``, and
last ``checks`` (each compared number with its limit; the same numbers
end stderr).  With no TPU, or fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Optional  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

if __name__ == "__main__":
    # the script's own directory would shadow the stdlib (bench/trace.py)
    sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402

from bench import arrivals, lutnet, reference  # noqa: E402
from bench import trace as btrace  # noqa: E402

# the limit of each compared number (PERF.md §2 gives the readings each
# was set from)
LIMITS = {"rows_wrong": 0.0, "unanswered": 0.0, "logit_gap": 1e-3}
# a back-to-back run keeps at most this many outputs for the check
MAX_KEPT = 64


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def load_json(*parts) -> dict:
    """The JSON document at ``os.path.join(*parts)``."""
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(root: str, name: str):
    """``BENCHMARK.json``, the named cell, its configuration file and its
    traffic mix."""
    spec = load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = load_json(root, cfg_entry["file"])
    mix = load_json(BENCH_DIR, "traffic", cell["traffic"] + ".json")
    return spec, cell, cfg, mix


def cell_metrics(spec: dict, cell: str, kind: str) -> list:
    """The metrics of ``kind`` ("end_to_end" or "per_layer") the cell
    reports."""
    return [m for m in spec[kind]
            if "workloads" not in m or cell in m["workloads"]]


def reader(name: str):
    """The per-layer metric reader for ``name``."""
    for stem in (name, name.split(".")[0]):
        path = os.path.join(BENCH_DIR, "metrics", stem + ".py")
        if os.path.exists(path):
            mod_spec = importlib.util.spec_from_file_location(
                "bench.metrics." + stem.replace(".", "_"), path)
            mod = importlib.util.module_from_spec(mod_spec)
            mod_spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for per-layer metric {name!r}")


def load_peaks(kind: str) -> dict:
    """The peak table row of a device kind; an unknown kind is an error."""
    peaks = load_json(BENCH_DIR, "peaks.json")
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json; known: {sorted(peaks)}")
    return peaks[kind]


def devices(chips: int):
    """JAX's devices, or :class:`NoChip` when they are not ``chips`` TPUs
    or more."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"{len(devs)} chips, the cell asks for {chips}")
    return devs


class Spans:
    """Host spans around calls into the program.

    Untraced, every span is a shared null context.  Traced (``trace_dir``
    given), :meth:`window` runs the profiler over the measured window, and
    each span is a profiler annotation (on the device trace's clock) that
    also adds its host seconds to ``totals``."""

    def __init__(self, trace_dir: Optional[str] = None):
        """Spans that trace into ``trace_dir``, or none when it is None."""
        self.trace_dir = trace_dir
        self.totals = {}
        self._null = contextlib.nullcontext()

    def __call__(self, label: str):
        """A context manager spanning one call into the program."""
        return self._null if self.trace_dir is None else self._span(label)

    @contextlib.contextmanager
    def _span(self, label: str):
        from jax.profiler import TraceAnnotation

        t = time.perf_counter()
        with TraceAnnotation(label):
            yield
        tot = self.totals.setdefault(label, [0.0, 0])
        tot[0] += time.perf_counter() - t
        tot[1] += 1

    @contextlib.contextmanager
    def window(self):
        """Bracket the measured window (and run the profiler over it)."""
        if self.trace_dir is None:
            yield
            return
        import jax

        opts = jax.profiler.ProfileOptions()
        # the harness's annotations only: the Python tracer would time
        # every call of the serving loop and slow it several-fold
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        try:
            with self._span(btrace.WINDOW):
                yield
        finally:
            jax.profiler.stop_trace()


def wait_until(deadline: float) -> None:
    """Sleep until ``deadline`` (perf_counter seconds); spin the last
    quarter millisecond, which a sleep would overshoot."""
    while True:
        left = deadline - time.perf_counter()
        if left <= 0:
            return
        if left > 3e-4:
            time.sleep(left - 2.5e-4)


# ---------------------------------------------------------------------------
# the loops: each runs the window and returns what it measured and kept
# ---------------------------------------------------------------------------

def _answers(reqs) -> tuple:
    """A request's answers as a tuple of row views: unlike the request
    objects, it leaves nothing for the garbage collector to walk."""
    return tuple([r.codes for r in reqs]), tuple([r.logits for r in reqs])


def open_loop(fleet, mid, x, sched, seconds, spans):
    """Send each request of ``sched`` when it falls due; tick while rows
    are queued, drain what is in flight when none are, else wait for the
    next due time.  Ends when every request is answered; a request's
    latency runs from its due time to the end of the tick or drain that
    put its answer in host memory."""
    due, rows = sched.due_s.tolist(), sched.rows.tolist()
    starts, keep = sched.starts.tolist(), sched.keep.tolist()
    n = len(due)
    # preallocated: a growing list of floats would be walked by every
    # garbage collection inside the window
    lat, late = np.full(n, np.nan), np.zeros(n)
    answers = []
    pending = collections.deque()
    failed = admitted = i = 0
    perf = time.perf_counter
    t0 = perf()
    # the longest single call of each kind: a stall of the loop shows here
    longest = {"submit": 0.0, "tick": 0.0, "drain": 0.0}
    while True:
        now = perf() - t0
        if i < n and due[i] <= now:
            with spans("submit"):
                while i < n and due[i] <= now:
                    reqs, dec = fleet.submit_many(
                        mid, x[starts[i]:starts[i] + rows[i]])
                    if dec.accept == rows[i]:
                        pending.append((i, reqs))
                        admitted += rows[i]
                    else:
                        failed += 1
                    late[i] = now - due[i]
                    i += 1
            t_sub = perf() - t0
            longest["submit"] = max(longest["submit"], t_sub - now)
            now = t_sub
        kind = None
        if fleet.queue_depth(mid):
            kind = "tick"
            with spans("tick"):
                fleet.tick()
        elif fleet.inflight:
            kind = "drain"
            with spans("drain"):
                fleet.drain()
        elif i < n:
            with spans("wait_due"):
                wait_until(t0 + due[i])
        t = perf() - t0
        if kind is not None:
            longest[kind] = max(longest[kind], t - now)
        while pending and pending[0][1][-1].done:
            j, reqs = pending.popleft()
            lat[j] = t - due[j]
            if keep[j]:
                answers.append((starts[j], *_answers(reqs)))
        if i >= n and not pending and not fleet.inflight:
            break
    window = perf() - t0
    return {"window_s": window, "attempted": n, "failed": failed,
            "rows_admitted": admitted, "rows_answered": admitted,
            "latencies_s": lat[~np.isnan(lat)], "late_s": late[:i],
            "answers": answers, "outstanding": (), "longest_s": longest}


def closed_loop(fleet, mid, pool, mix, seconds, seed, spans):
    """Keep ``clients`` requests outstanding, each drawn from the pool and
    sent again as soon as one is answered, for ``seconds``."""
    clients = int(mix["clients"])
    rows = int(mix["rows"])
    order = arrivals.closed_order(mix, 1 << 16, seed).tolist()
    keep = arrivals.keep_mask(float(mix["check_share"]), 1 << 16,
                              seed).tolist()
    outstanding = collections.deque()
    answers = []
    sent = failed = answered = 0

    def send():
        nonlocal sent, failed
        p = order[sent % len(order)]
        reqs, dec = fleet.submit_many(mid, pool[p * rows:(p + 1) * rows])
        if dec.accept == rows:
            outstanding.append(
                (p * rows if keep[sent % len(keep)] else -1, reqs))
        else:
            failed += 1
        sent += 1

    perf = time.perf_counter
    t0 = perf()
    with spans("submit"):
        for _ in range(clients):
            send()
    while perf() - t0 < seconds:
        if fleet.queue_depth(mid):
            with spans("tick"):
                fleet.tick()
        else:
            with spans("drain"):
                fleet.drain()
        while outstanding and outstanding[0][1][-1].done:
            start, reqs = outstanding.popleft()
            answered += rows
            if start >= 0:
                answers.append((start, *_answers(reqs)))
        if len(outstanding) < clients:
            with spans("submit"):
                while len(outstanding) < clients:
                    send()
    window = perf() - t0
    return {"window_s": window, "attempted": sent, "failed": failed,
            "rows_admitted": (sent - failed) * rows,
            "rows_answered": answered, "answers": answers,
            "outstanding": outstanding}


def back_to_back(ex, ring, mix, seconds, seed, spans):
    """Dispatch the ring's batches one after another with at most
    ``inflight`` outstanding, and wait for the last at the window's end."""
    import jax

    batch, inflight = int(mix["batch_rows"]), int(mix["inflight"])
    keep = arrivals.keep_mask(float(mix["check_share"]), 1 << 16,
                              seed).tolist()
    outs = collections.deque()
    kept = []
    i = 0
    perf = time.perf_counter
    t0 = perf()
    while perf() - t0 < seconds:
        slot = i % len(ring)
        with spans("executor_call"):
            out = ex.codes_and_logits(ring[slot])
        outs.append(out)
        if keep[i % len(keep)] and len(kept) < MAX_KEPT - 1:
            kept.append((slot, out))
        if len(outs) > inflight:
            with spans("sync"):
                jax.block_until_ready(outs.popleft())
        i += 1
    with spans("sync"):
        jax.block_until_ready(list(outs))
    window = perf() - t0
    kept.append((slot, out))
    return {"window_s": window, "attempted": i, "failed": 0,
            "rows_admitted": i * batch, "rows_answered": i * batch,
            "kept": kept}


# ---------------------------------------------------------------------------
# one cell
# ---------------------------------------------------------------------------

def build_network(cfg: dict):
    """The program's deployment artifact over the configuration's tables
    (drawn from its ``network_seed``), and the tables and mappings
    themselves (for the reference)."""
    from repro.pipeline import CompiledLUTNetwork, config_from_dict

    tables, maps = lutnet.make_arrays(cfg, int(cfg["network_seed"]))
    net = CompiledLUTNetwork(config_from_dict(lutnet.network_config(cfg)),
                             tables, maps, cfg["in_log_scale"],
                             cfg["out_log_scale"], backend="fused")
    return net, tables, maps


def make_ring(cfg: dict, mix: dict, seed: int):
    """The bulk cells' input batches, drawn on the device."""
    import jax
    import jax.numpy as jnp

    lo, hi = cfg["input_range"]
    shape = (int(mix["batch_rows"]), int(cfg["in_features"]))
    n = int(mix["ring"])

    @jax.jit
    def draw(raw):
        key = jax.random.fold_in(jax.random.wrap_key_data(raw), 5)
        return tuple(jax.random.uniform(k, shape, jnp.float32, lo, hi)
                     for k in jax.random.split(key, n))

    return list(draw(jnp.asarray(lutnet.key_data(seed))))


def fleet_inputs(cfg, mix, seconds, seed):
    """The open loop's schedule and rows, or the closed loop's pool."""
    if mix["loop"] == "open":
        sched = arrivals.open_schedule(mix, seconds, seed)
        return sched, arrivals.input_rows(cfg, sched.total_rows, seed)
    return None, arrivals.input_rows(
        cfg, int(mix["pool_requests"]) * int(mix["rows"]), seed)


def make_fleet(cfg, mix, net, tables, maps, x):
    """A fleet serving the network as tenant ``"cell"``, its block shape
    warmed with one full and one ragged block."""
    from repro.serve.fleet import LUTFleet
    from repro.serve.registry import Reference

    fp = mix["fleet"]
    fleet = LUTFleet(block=fp["block"], depth=fp["depth"],
                     min_fill=fp["min_fill"])
    # the deploy anchor comes from the plain reference, so registering
    # builds no oracle executor
    xr = x[:64]
    fleet.register("cell", net, backend="fused", reference=Reference(
        x=xr, codes=reference.forward(cfg, tables, maps, xr)[0]))
    for n in (fp["block"], 3):
        fleet.submit_many("cell", x[:n])
        fleet.pump()
    return fleet


def serve_fleet(cfg, mix, net, tables, maps, seed, seconds, spans):
    """Serve the network through a fleet under the open or closed loop."""
    mid = "cell"
    fp = mix["fleet"]
    sched, x = fleet_inputs(cfg, mix, seconds, seed)
    fleet = make_fleet(cfg, mix, net, tables, maps, x)
    ticks0, padded0 = fleet.stats(mid).ticks, fleet.stats(mid).rows_padded
    t_window = time.perf_counter()
    with spans.window():
        if mix["loop"] == "open":
            out = open_loop(fleet, mid, x, sched, seconds, spans)
        else:
            out = closed_loop(fleet, mid, x, mix, seconds, seed, spans)
    st = fleet.stats(mid)
    out["fleet"] = {"ticks": st.ticks - ticks0,
                    "rows_padded": st.rows_padded - padded0,
                    "block": fp["block"]}
    fleet.pump()
    out["fleet_summary"] = fleet.summary(mid)
    out["batch_rows"] = fp["block"]
    out["t_window"] = t_window
    # the answers kept for the check, against the rows of x they answer;
    # a request still out at the window's end is answered by the pump
    unanswered = 0
    for start, reqs in out.pop("outstanding"):
        if not all(r.done for r in reqs):
            unanswered += 1
        elif start >= 0:
            out["answers"].append((start, *_answers(reqs)))
    out["answers"] = [(start, np.stack(c), np.stack(lg))
                      for start, c, lg in out["answers"]]
    out["inputs"] = x
    out["unanswered"] = unanswered
    return out


def serve_bulk(cfg, mix, net, seed, seconds, spans):
    """Warm the executor on the ring and run batches back to back."""
    import jax

    ex = net.compile_backend("fused")
    ring = make_ring(cfg, mix, seed)
    jax.block_until_ready([ex.codes_and_logits(r) for r in ring])
    t_window = time.perf_counter()
    with spans.window():
        out = back_to_back(ex, ring, mix, seconds, seed, spans)
    kept = out.pop("kept")
    slots = sorted({s for s, _ in kept})
    at = {s: i * int(mix["batch_rows"]) for i, s in enumerate(slots)}
    out["inputs"] = np.concatenate(jax.device_get([ring[s] for s in slots]))
    out["answers"] = [(at[s], np.asarray(c), np.asarray(lg)) for (s, _), (c, lg)
                      in zip(kept, jax.device_get([o for _, o in kept]))]
    out["unanswered"] = 0
    out["fleet"] = None
    out["batch_rows"] = int(mix["batch_rows"])
    out["t_window"] = t_window
    return out


def check(cfg, tables, maps, out) -> dict:
    """Compare the kept answers with the plain reference over the rows
    they answer (computed once per input row)."""
    ref_codes, ref_logits = reference.forward(cfg, tables, maps,
                                              out["inputs"])
    answers = out["answers"]
    if answers:
        idx = np.concatenate([np.arange(s, s + len(c))
                              for s, c, _ in answers])
        checks = reference.compare(
            cfg, np.concatenate([c for _, c, _ in answers]),
            np.concatenate([lg for _, _, lg in answers]),
            ref_codes[idx], ref_logits[idx])
    else:
        idx = ()
        checks = {"rows_wrong": 0.0, "logit_gap": 0.0}
    checks["unanswered"] = float(out["unanswered"])
    return checks, len(idx)


def run_cell(spec: dict, cell: dict, cfg: dict, mix: dict, *, seed: int,
             seconds: float, trace: bool, t_start: float,
             require_tpu: bool = True) -> dict:
    """Set up, run and check one cell; return the result object (with an
    ``_info`` entry of diagnostics that is not part of the line)."""
    import jax

    from repro.launch.compile_cache import use_persistent_cache

    devs = devices(int(cell["chips"])) if require_tpu else jax.devices()
    used = devs[:int(cell["chips"])]
    use_persistent_cache()
    t_devices = time.perf_counter()
    net, tables, maps = build_network(cfg)
    t_network = time.perf_counter()
    with tempfile.TemporaryDirectory() as tmp:
        spans = Spans(tmp if trace else None)
        if mix["entry"] == "fleet":
            out = serve_fleet(cfg, mix, net, tables, maps, seed, seconds,
                              spans)
        elif mix["entry"] == "executor":
            out = serve_bulk(cfg, mix, net, seed, seconds, spans)
        else:
            raise ValueError(f"unknown entry {mix['entry']!r}")
        events = btrace.load_xplane(_xplane(tmp)) if trace else None
    setup_s = out["t_window"] - t_start
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in used)
    del net
    checks, rows_checked = check(cfg, tables, maps, out)
    correct = (all(checks[k] <= LIMITS[k] for k in LIMITS)
               and rows_checked > 0 and out["failed"] == 0)

    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(mem)}
    rows_per_s = out["rows_answered"] / out["window_s"]
    result = {"correct": bool(correct), "attempted": int(out["attempted"]),
              "failed": int(out["failed"])}
    if trace:
        red = btrace.reduce(events)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        ctx = {"cfg": cfg, "peaks": load_peaks(used[0].device_kind),
               "spans": spans.totals, "trace": red, "fleet": out["fleet"],
               "window_s": out["window_s"],
               "rows_admitted": out["rows_admitted"],
               "rows_answered": out["rows_answered"],
               "batch_rows": out["batch_rows"]}
        metrics = {}
        for m in cell_metrics(spec, cell["name"], "per_layer"):
            v = reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        values = {"rows_per_s": rows_per_s, "setup_s": setup_s}
        lat = out.get("latencies_s")
        if lat is not None and len(lat):
            values["latency_p50_ms"] = float(np.percentile(lat, 50)) * 1e3
            values["latency_p90_ms"] = float(np.percentile(lat, 90)) * 1e3
        # a name "<base>.<suffix>" is the quantity <base> split by cells
        metrics = {m["name"]: {"value": float(values[m["name"].split(".")[0]]),
                               "unit": m["unit"]}
                   for m in cell_metrics(spec, cell["name"], "end_to_end")}
    result["metrics"] = metrics
    result["device"] = device
    if trace:
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    result["checks"] = {k: {"value": checks[k], "limit": LIMITS[k]}
                        for k in LIMITS}
    info = {"setup_s": setup_s,
            "setup_split_s": {"start_and_devices": t_devices - t_start,
                              "network": t_network - t_devices,
                              "plan_compile_warm": out["t_window"] - t_network},
            "window_s": out["window_s"],
            "rows_answered": out["rows_answered"], "rows_per_s": rows_per_s,
            "rows_checked": rows_checked,
            "requests_latency_sample": len(out.get("latencies_s", ()))}
    lat = out.get("latencies_s")
    if lat is not None and len(lat):
        info["latency_pct_ms"] = {f"p{q}": float(np.percentile(lat, q)) * 1e3
                                  for q in (50, 90, 95, 99, 99.9)}
    if len(out.get("late_s", ())):
        info["generator_late_p99_ms"] = float(
            np.percentile(out["late_s"], 99)) * 1e3
    if "longest_s" in out:
        info["longest_call_s"] = out["longest_s"]
    if out.get("fleet_summary"):
        info["fleet"] = {k: out["fleet_summary"][k] for k in
                         ("requests", "completed", "ticks", "rows_padded",
                          "failures", "shed")}
    if trace:
        info["span_totals"] = spans.totals
        info["idle_gaps_at_s"] = red["idle_gaps_at_s"]
    result["_info"] = info
    return result


def _xplane(d: str) -> str:
    for dirpath, _, files in os.walk(d):
        for f in files:
            if f.endswith(".xplane.pb"):
                return os.path.join(dirpath, f)
    raise FileNotFoundError(f"no .xplane.pb under {d}")


def main(argv=None) -> int:
    """Run the cell named on the command line; print its result line."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec, cell, cfg, mix = load_cell(ROOT, args.workload)
    try:
        result = run_cell(spec, cell, cfg, mix, seed=args.seed,
                          seconds=args.seconds, trace=bool(args.trace),
                          t_start=T_START)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    info = result.pop("_info")
    print(f"info {json.dumps(info)}", file=sys.stderr)
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
