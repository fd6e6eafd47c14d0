"""Fleet-tier acceptance contract (DESIGN.md §9).

* every tenant's fleet-served codes are bit-identical to its artifact's
  single-engine reference codes, under ragged bursty multi-tenant traffic
  (tests/traffic.py — the reusable generator seeded from the old one-off
  adversarial batch shapes);
* continuous cross-tenant batching: a tenant with 3 queued rows completes
  without waiting for a tenant with 300;
* hot swap: a good deploy versions up with zero dropped requests; a
  CORRUPTED artifact (table rows perturbed) is rejected by the smoke
  check, the incumbent keeps serving, and the rollback lands in the swap
  history;
* LRU executor cache evicts under byte/entry budgets without affecting
  results; admission control sheds/defers per tenant SLO.
"""
import os

import jax
import numpy as np
import pytest

import traffic
from repro import pipeline, tracing
from repro.configs import paper_tasks
from repro.core import assemble
from repro.serve import (AdmissionController, ExecutorCache, FaultInjector,
                         FaultPlan, FaultSpec, LUTFleet, ResiliencePolicy,
                         TenantRegistry, TenantSLO, make_reference,
                         smoke_check)
from repro.serve.lut_engine import LUTEngine, LUTEngineStats

TASKS = ("nid", "jsc", "mnist")


@pytest.fixture(scope="module")
def nets():
    out = {}
    for i, task in enumerate(TASKS):
        cfg = paper_tasks.reduced(task)
        params = assemble.init(jax.random.PRNGKey(i), cfg)
        out[task] = pipeline.compile_network(params, cfg)
    return out


def _rows(net, n, seed):
    rng = np.random.default_rng(seed)
    return rng.uniform(-1.0, 1.0,
                       (n, net.cfg.in_features)).astype(np.float32)


def _fleet(nets, **kw):
    fleet = LUTFleet(**kw)
    for task, net in nets.items():
        fleet.register(task, net, reference=make_reference(net, n=16))
    return fleet


# ---------------------------------------------------------------------------
# serving correctness under ragged multi-tenant traffic
# ---------------------------------------------------------------------------

def test_fleet_bit_identical_under_ragged_trace(nets):
    """The acceptance criterion: fleet-served codes == each artifact's own
    predict_codes, exactly, under a bursty ragged arrival trace."""
    fleet = _fleet(nets, block=64, depth=2)
    trace = traffic.ragged_trace(TASKS, n_events=24, seed=3,
                                 batches=(1, 8, 33), jitter=3)
    inputs = traffic.make_inputs(
        trace, {t: n.cfg.in_features for t, n in nets.items()}, seed=4)
    per_tenant = {t: [] for t in TASKS}
    for ev, xs in zip(trace, inputs):
        reqs, decision = fleet.submit_many(ev.model_id, xs)
        assert decision.admitted_all  # no SLO -> nothing shed
        per_tenant[ev.model_id].append((xs, reqs))
        for _ in range(ev.gap_ticks):
            fleet.tick()
    fleet.pump()

    for task, pairs in per_tenant.items():
        for xs, reqs in pairs:
            assert all(r.done for r in reqs)
            ref = np.asarray(nets[task].predict_codes(xs))
            np.testing.assert_array_equal(
                np.stack([r.codes for r in reqs]), ref, err_msg=task)
        s = fleet.summary(task)
        assert s["completed"] == traffic.rows_per_model(trace)[task]
        assert s["queue_depth"] == 0 and s["version"] == 1
        assert s["p99_request_us"] >= s["p50_request_us"] > 0


def test_small_tenant_not_stalled_by_large_one(nets):
    """Continuous cross-tenant batching: 3 queued rows dispatch alongside
    300, not behind them."""
    fleet = LUTFleet(block=256, depth=2)
    fleet.register("big", nets["nid"])
    fleet.register("small", nets["jsc"])
    big, _ = fleet.submit_many("big", _rows(nets["nid"], 300, seed=5))
    small, _ = fleet.submit_many("small", _rows(nets["jsc"], 3, seed=6))
    fleet.tick()   # both tenants dispatch one block; oldest retires
    assert all(r.done for r in small)        # 3 rows done in ONE tick
    assert fleet.queue_depth("big") > 0      # 300-row tenant still working
    fleet.pump()
    assert all(r.done for r in big)
    np.testing.assert_array_equal(
        np.stack([r.codes for r in small]),
        np.asarray(nets["jsc"].predict_codes(
            np.stack([r.x for r in small]))))


def test_fleet_min_fill_coalesces_into_full_blocks(nets):
    """Batching-delay policy: with min_fill=block a lane holds ragged
    arrivals until a full block accumulates (fewer, fuller dispatches —
    the online headline of benchmarks/fleet_serving.py), and pump()
    flushes the final partial block instead of wedging."""
    net = nets["jsc"]
    fleet = LUTFleet(block=8, depth=1, min_fill=8)
    fleet.register("jsc", net, reference=make_reference(net, n=16))
    first, _ = fleet.submit_many("jsc", _rows(net, 3, seed=21))
    fleet.tick()                          # 3 < min_fill: lane holds
    assert fleet.stats("jsc").ticks == 0
    assert not any(r.done for r in first)
    second, _ = fleet.submit_many("jsc", _rows(net, 5, seed=22))
    fleet.tick()                          # 8 queued == block: dispatch
    s = fleet.stats("jsc")
    assert s.ticks == 1 and s.rows_padded == 0      # one FULL block
    assert all(r.done for r in first + second)
    # the tail below the threshold still completes: pump() flushes it
    tail, _ = fleet.submit_many("jsc", _rows(net, 2, seed=23))
    fleet.pump()
    assert all(r.done for r in tail)
    assert fleet.stats("jsc").ticks == 2
    np.testing.assert_array_equal(
        np.stack([r.codes for r in tail]),
        np.asarray(net.predict_codes(np.stack([r.x for r in tail]))))
    with pytest.raises(ValueError, match="min_fill"):
        LUTFleet(min_fill=0)


def test_traffic_generator_is_deterministic_and_ragged():
    a = traffic.ragged_trace(("m0", "m1"), n_events=30, seed=7)
    b = traffic.ragged_trace(("m0", "m1"), n_events=30, seed=7)
    assert a == b
    assert a != traffic.ragged_trace(("m0", "m1"), n_events=30, seed=8)
    assert len(a) == 30
    assert {ev.model_id for ev in a} == {"m0", "m1"}
    assert len({ev.batch for ev in a}) > 3        # actually ragged
    assert traffic.total_rows(a) == sum(
        traffic.rows_per_model(a).values())
    with pytest.raises(ValueError, match="non-empty"):
        traffic.ragged_trace(())


# ---------------------------------------------------------------------------
# hot swap
# ---------------------------------------------------------------------------

def _corrupt_artifact(net, dir_path):
    """Save the artifact, then perturb every row of the FINAL layer's
    table — valid dtype/shape/range, wrong answers (silent corruption)."""
    good = os.path.join(dir_path, "good.npz")
    net.save(good)
    z = np.load(good)
    arrays = {k: z[k] for k in z.files}
    last = f"table_{len(net.cfg.layers) - 1}"
    # flip the low bit of every entry: stays a valid code (beta >= 1) but
    # changes every lookup result — silent corruption, not a load error
    arrays[last] = (arrays[last] ^ 1).astype(arrays[last].dtype)
    bad = os.path.join(dir_path, "bad.npz")
    np.savez_compressed(bad, **arrays)
    return good, bad


def test_hot_swap_good_deploy_under_load(nets, tmp_path):
    """A passing deploy swaps with zero dropped requests and versions up;
    results before/during/after all match the artifact's reference."""
    net = nets["nid"]
    fleet = LUTFleet(block=16, depth=2)
    ref = make_reference(net, n=16)
    fleet.register("nid", net, reference=ref)
    x = _rows(net, 50, seed=9)
    reqs, _ = fleet.submit_many("nid", x)
    fleet.tick()                              # some blocks now in flight
    path = os.path.join(str(tmp_path), "v2.npz")
    net.save(path)                            # same tables -> must pass
    event = fleet.deploy("nid", path, reference=ref)
    assert event.ok and event.to_version == 2
    more, _ = fleet.submit_many("nid", _rows(net, 20, seed=10))
    fleet.pump()
    assert all(r.done for r in reqs) and all(r.done for r in more)  # 0 drop
    for rs, xs in ((reqs, x), (more, np.stack([r.x for r in more]))):
        np.testing.assert_array_equal(
            np.stack([r.codes for r in rs]),
            np.asarray(net.predict_codes(xs)))
    s = fleet.summary("nid")
    assert s["version"] == 2
    assert s["swap_history"] == [event.summary()]
    assert s["completed"] == 70


def test_hot_swap_rejects_corrupted_artifact(nets, tmp_path):
    """The satellite contract: a corrupted .npz (table rows perturbed) is
    rejected by the bit-identity smoke check, the OLD version keeps
    serving with zero dropped requests, and the swap history records the
    rollback."""
    net = nets["nid"]
    good, bad = _corrupt_artifact(net, str(tmp_path))
    ref = make_reference(net, n=32)
    fleet = LUTFleet(block=16, depth=2)
    fleet.register("nid", good, reference=ref)
    x = _rows(net, 40, seed=11)
    reqs, _ = fleet.submit_many("nid", x)
    fleet.tick()                              # live load during the deploy

    event = fleet.deploy("nid", bad, reference=ref)
    assert not event.ok
    assert "mismatch" in event.reason
    assert event.from_version == event.to_version == 1   # rollback

    more, _ = fleet.submit_many("nid", _rows(net, 15, seed=12))
    fleet.pump()
    assert all(r.done for r in reqs) and all(r.done for r in more)  # 0 drop
    np.testing.assert_array_equal(                 # OLD tables still serve
        np.stack([r.codes for r in reqs]),
        np.asarray(net.predict_codes(x)))
    s = fleet.summary("nid")
    assert s["version"] == 1
    assert s["swap_history"] == [event.summary()]
    assert s["swap_history"][0]["ok"] is False
    # strict mode raises instead of returning the rejection
    with pytest.raises(ValueError, match="rejected"):
        fleet.deploy("nid", bad, reference=ref, strict=True)


def test_hot_swap_racing_quarantine_probes_new_version(nets, tmp_path):
    """Hot swap racing an open incident: a deploy landing while the lane
    is quarantined/mid-failover is adopted, the fresh version probes
    immediately (no cooldown wait), and zero requests are dropped."""
    net = nets["jsc"]
    inj = FaultInjector(FaultPlan(
        [FaultSpec("exception", at=0, scope="jsc")]))
    fleet = LUTFleet(block=16, faults=inj,
                     policy=ResiliencePolicy(breaker_threshold=1,
                                             backoff_base_s=0.0,
                                             breaker_cooldown_s=60.0))
    ref = make_reference(net, n=16)
    fleet.register("jsc", net, reference=ref, backend="onehot")
    x = _rows(net, 24, seed=31)
    reqs, _ = fleet.submit_many("jsc", x)
    fleet.tick()        # injected failure -> trip -> degrade -> half-open
    lane = fleet._lanes["jsc"]
    assert lane.stats.breaker_trips == 1
    assert lane.breaker.state(fleet._now()) != "closed"   # mid-incident

    path = os.path.join(str(tmp_path), "v2.npz")
    net.save(path)
    event = fleet.deploy("jsc", path, reference=ref)
    assert event.ok and event.to_version == 2
    # mid-incident the half-open lane quarantines arrivals (the queued
    # pre-incident rows are the probe) — new traffic offered now is shed
    shed, dec = fleet.submit_many("jsc", _rows(net, 8, seed=40))
    assert dec.accept == 0 and dec.reason == "quarantined" and not shed
    fleet.pump()        # probe succeeds on the new version; breaker closes
    more, dec = fleet.submit_many("jsc", _rows(net, 8, seed=32))
    assert dec.reason == "ok" and len(more) == 8
    fleet.pump()
    # zero drops across the race: every pre-incident row AND every
    # post-deploy row completes, bit-identically
    done = reqs + more
    assert all(r.done for r in done)
    np.testing.assert_array_equal(
        np.stack([r.codes for r in done]),
        np.asarray(net.predict_codes(np.stack([r.x for r in done]))))
    s = fleet.summary("jsc")
    assert s["version"] == 2 and s["breaker"] == "closed"
    assert s["completed"] == 32


def test_corrupt_candidate_during_recovery_rolls_back(nets, tmp_path):
    """A corrupt candidate deployed while the lane is recovering is
    rejected by the smoke check (here corrupted in-flight by the injector's
    registry_load seam), the rollback lands on the SwapEvent, and the
    recovery completes on the incumbent version with zero drops."""
    net = nets["jsc"]
    inj = FaultInjector(FaultPlan([
        FaultSpec("exception", at=0, scope="jsc"),
        FaultSpec("corrupt_artifact", at=0, scope="jsc"),
    ]))
    fleet = LUTFleet(block=16, faults=inj,
                     policy=ResiliencePolicy(breaker_threshold=1,
                                             backoff_base_s=0.0))
    ref = make_reference(net, n=16)
    fleet.register("jsc", net, reference=ref, backend="onehot")
    x = _rows(net, 20, seed=33)
    reqs, _ = fleet.submit_many("jsc", x)
    fleet.tick()        # incident opens: trip + degrade to the fallback

    path = os.path.join(str(tmp_path), "v2.npz")
    net.save(path)      # good bytes; the injector corrupts them at load
    event = fleet.deploy("jsc", path, reference=ref)
    assert inj.fired("corrupt_artifact") == 1
    assert not event.ok and "mismatch" in event.reason
    assert event.from_version == event.to_version == 1    # rollback
    fleet.pump()
    assert all(r.done for r in reqs)                      # zero drops
    np.testing.assert_array_equal(
        np.stack([r.codes for r in reqs]),
        np.asarray(net.predict_codes(x)))
    s = fleet.summary("jsc")
    assert s["version"] == 1 and s["breaker"] == "closed"
    assert s["swap_history"][-1]["ok"] is False


def test_smoke_check_self_mode_catches_backend_divergence(nets):
    from repro.serve import Reference
    ok, reason, n = smoke_check(nets["jsc"], None)
    assert ok and n == 64 and "self-check" in reason
    good = make_reference(nets["jsc"], n=8)
    wrong = Reference(x=good.x, codes=good.codes + 1)
    ok, reason, _ = smoke_check(nets["jsc"], wrong)
    assert not ok and "mismatch" in reason


def test_registry_unknown_model_and_double_register(nets):
    reg = TenantRegistry()
    reg.register("m", nets["nid"], reference=make_reference(nets["nid"]))
    with pytest.raises(ValueError, match="already registered"):
        reg.register("m", nets["nid"])
    with pytest.raises(KeyError, match="unknown model"):
        reg.get("nope")
    ev = reg.deploy("m", nets["nid"])   # no reference -> self-check
    assert ev.ok and reg.get("m").version == 2
    reg.unregister("m")
    assert "m" not in reg


# ---------------------------------------------------------------------------
# executor LRU cache
# ---------------------------------------------------------------------------

def test_executor_cache_lru_eviction_and_correctness(nets):
    """3 tenants through a 2-entry cache: evictions happen, results stay
    bit-identical, and a re-request of an evicted entry is a miss that
    rebuilds (never a wrong executor)."""
    cache = ExecutorCache(max_entries=2)
    fleet = _fleet(nets, block=32, depth=2, cache=cache)
    assert fleet.registry.cache is cache
    for task, net in nets.items():
        x = _rows(net, 10, seed=13)
        reqs, _ = fleet.submit_many(task, x)
        fleet.pump()
        np.testing.assert_array_equal(
            np.stack([r.codes for r in reqs]),
            np.asarray(net.predict_codes(x)), err_msg=task)
    assert len(cache) == 2
    assert cache.stats.misses == 3 and cache.stats.evictions == 1
    # the first tenant's executor was evicted: re-request = miss + rebuild
    fleet.registry.executor(TASKS[0])
    assert cache.stats.misses == 4 and cache.stats.evictions == 2
    # the most recent entry is a hit
    fleet.registry.executor(TASKS[0])
    assert cache.stats.hits == 1
    assert cache.bytes_held > 0


def test_executor_cache_byte_budget(nets):
    cache = ExecutorCache(max_bytes=1)   # everything over budget...
    fleet = _fleet(nets, block=16, cache=cache)
    for task, net in nets.items():
        reqs, _ = fleet.submit_many(task, _rows(net, 4, seed=14))
        fleet.pump()
        assert all(r.done for r in reqs)
    assert len(cache) == 1               # ...but never below one entry
    assert cache.stats.evictions == 2
    with pytest.raises(ValueError, match="max_entries"):
        ExecutorCache(max_entries=0)
    with pytest.raises(ValueError, match="not both"):
        LUTFleet(registry=TenantRegistry(), cache=ExecutorCache())


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------

def test_admission_decisions_pure():
    ctl = AdmissionController()
    assert ctl.decide(n=10, queue_depth=0, p99_us=0.0, slo=None).accept == 10
    slo = TenantSLO(max_queue=8, policy="shed")
    d = ctl.decide(n=10, queue_depth=3, p99_us=0.0, slo=slo)
    assert (d.accept, d.shed, d.defer, d.reason) == (5, 5, 0, "queue")
    d = ctl.decide(n=10, queue_depth=3, p99_us=0.0,
                   slo=TenantSLO(max_queue=8, policy="defer"))
    assert (d.accept, d.shed, d.defer) == (5, 0, 5)
    slo = TenantSLO(p99_budget_us=100.0)
    d = ctl.decide(n=4, queue_depth=0, p99_us=250.0, slo=slo)
    assert (d.accept, d.shed, d.reason) == (0, 4, "p99")
    assert ctl.decide(n=4, queue_depth=0, p99_us=50.0, slo=slo).accept == 4
    assert ctl.may_drain_deferred(queue_depth=0, p99_us=250.0, slo=slo) == 0
    with pytest.raises(ValueError, match="policy"):
        TenantSLO(policy="drop")
    with pytest.raises(ValueError, match="max_queue"):
        TenantSLO(max_queue=0)


def test_fleet_sheds_over_queue_budget(nets):
    net = nets["nid"]
    fleet = LUTFleet(block=16)
    fleet.register("nid", net, slo=TenantSLO(max_queue=50, policy="shed"))
    reqs, decision = fleet.submit_many("nid", _rows(net, 70, seed=15))
    assert (decision.accept, decision.shed) == (50, 20)
    assert len(reqs) == 50
    fleet.pump()
    s = fleet.summary("nid")
    assert s["shed"] == 20 and s["completed"] == 50


def test_fleet_defers_and_drains_when_idle(nets):
    """Deferred rows are absorbed, not lost: they re-enter once the lane
    has headroom and every one completes with correct codes."""
    net = nets["jsc"]
    fleet = LUTFleet(block=8)
    fleet.register("jsc", net, slo=TenantSLO(max_queue=8, policy="defer"))
    x = _rows(net, 20, seed=16)
    reqs, decision = fleet.submit_many("jsc", x)
    assert (decision.accept, decision.defer, decision.shed) == (8, 12, 0)
    assert fleet.queue_depth("jsc") == 20     # queued + deferred
    fleet.pump()
    s = fleet.summary("jsc")
    assert s["deferred"] == 12 and s["shed"] == 0 and s["completed"] == 20
    assert len(reqs) == 8                     # accepted handles returned
    np.testing.assert_array_equal(
        np.stack([r.codes for r in reqs]),
        np.asarray(net.predict_codes(x[:8])))


def test_fleet_p99_backpressure_sheds_new_arrivals(nets):
    net = nets["nid"]
    fleet = LUTFleet(block=16)
    fleet.register("nid", net,
                   slo=TenantSLO(p99_budget_us=1000.0, policy="shed"))
    # inject an over-budget latency window (deterministic stand-in for a
    # genuinely slow device; the controller only reads the percentile)
    fleet.stats("nid").request_latencies_us.extend([5000.0] * 10)
    reqs, decision = fleet.submit_many("nid", _rows(net, 5, seed=17))
    assert decision.reason == "p99" and decision.shed == 5 and not reqs
    fleet.stats("nid").request_latencies_us.clear()
    reqs, decision = fleet.submit_many("nid", _rows(net, 5, seed=18))
    assert decision.admitted_all and len(reqs) == 5
    fleet.pump()


# ---------------------------------------------------------------------------
# stats + engine hooks
# ---------------------------------------------------------------------------

def test_engine_stats_summary_and_empty_latency(nets):
    s = LUTEngineStats()
    assert s.summary() == {"ticks": 0, "requests": 0, "rows_padded": 0}
    # per-tick wall time is the tracer's engine.tick span: nothing is
    # recorded with tracing off, one span per tick with it on
    eng = LUTEngine(nets["nid"], block=16, depth=2)
    eng.submit_many(_rows(nets["nid"], 40, seed=3))
    eng.tick()
    assert tracing.snapshot()["spans"] == {}
    tracing.reset()
    tracing.enable()
    try:
        while eng.queue:
            eng.tick()
        eng.drain()
        spans = tracing.snapshot()["spans"]
    finally:
        tracing.disable()
        tracing.reset()
    assert spans["engine.tick"][0] == 2
    assert spans["engine.fetch"][0] == 3
    assert eng.stats.summary() == {"ticks": 3, "requests": 40,
                                   "rows_padded": 8}


def test_fleet_stats_summary_empty():
    from repro.serve import FleetStats
    s = FleetStats()
    assert s.latency_us(99) == 0.0
    assert s.summary()["p99_request_us"] == 0.0
    assert s.summary()["completed"] == 0


def test_engine_accepts_prebuilt_executor(nets):
    """The fleet hook on LUTEngine: a registry-cached executor is injected
    instead of compiled, and mismatched arguments fail loudly."""
    net = nets["nid"]
    ex = net.compile_backend("take")
    eng = LUTEngine(net, block=8, executor=ex)
    assert eng.backend == "take"
    x = _rows(net, 10, seed=19)
    np.testing.assert_allclose(eng.run(x), np.asarray(net.predict(x)),
                               rtol=1e-6, atol=1e-6)
    with pytest.raises(ValueError, match="backend"):
        LUTEngine(net, backend="fused", executor=ex)
    with pytest.raises(ValueError, match="mesh"):
        LUTEngine(net, mesh=object(), executor=ex)


def test_fleet_input_validation(nets):
    fleet = _fleet(nets, block=8)
    with pytest.raises(KeyError, match="unknown model"):
        fleet.submit_many("nope", np.zeros((1, 4), np.float32))
    with pytest.raises(ValueError, match="in_features"):
        fleet.submit_many("nid", np.zeros((4,), np.float32))
    with pytest.raises(ValueError, match="depth"):
        LUTFleet(depth=0)
    req, decision = fleet.submit("nid",
                                 _rows(nets["nid"], 1, seed=20)[0])
    assert decision.admitted_all and req is not None
    fleet.pump()
    assert req.done
