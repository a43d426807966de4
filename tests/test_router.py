"""Unified Router API: request/decision schema, SLA-aware admission,
queue-aware equivalence with the shifted-store + scalar path, batched
event-loop selection (counting spy), heterogeneous per-request SLA mixes
through both the simulator and the executor, trace arrival validation,
batched-trace equivalence, and the --smoke benchmark harness."""
import json
import os
import subprocess
import sys
from dataclasses import dataclass
from pathlib import Path
from typing import Callable

import numpy as np
import pytest
from hypothesis import example, given, settings, strategies as st

import repro.sim.engine as engine_mod
from repro.core import policy_vec
from repro.core.netmodel import NetworkModel
from repro.core.policy import (DynamicGreedy, ModiPick, PureRandom,
                               RelatedAccurate, RelatedRandom, StaticGreedy,
                               budget)
from repro.core.profiles import ModelProfile, ProfileStore
from repro.core.simulate import Simulator
from repro.core.zoo import TABLE2, make_store, true_profiles
from repro.router import (AdmitAll, DepthCapAdmission, InferenceRequest,
                          Router, SlaAwareAdmission, make_admission,
                          shifted_store)
from repro.serving.executor import PoolExecutor
from repro.sim import (PoissonArrivals, ServingSimulator, TraceArrivals,
                       per_model_replicas, shared_replicas)

REPO = Path(__file__).resolve().parent.parent
NET = NetworkModel(50.0, 25.0)
TRUTH = true_profiles(TABLE2)


def store_from(specs):
    profiles = []
    for i, (acc, mu, sigma) in enumerate(specs):
        p = ModelProfile(name=f"m{i}", accuracy=acc)
        p.mu, p.var, p.n_obs = mu, sigma ** 2, 100
        profiles.append(p)
    return ProfileStore(profiles)


pool_strategy = st.lists(
    st.tuples(st.floats(0.05, 1.0), st.floats(1.0, 200.0),
              st.floats(0.0, 20.0)),
    min_size=1, max_size=12)

waits_strategy = st.lists(st.floats(0.0, 300.0), min_size=12, max_size=12)


# ----------------------------------------------------------------------
# schema / budget breakdown
# ----------------------------------------------------------------------

def test_decision_budget_breakdown():
    store = store_from([(0.9, 50.0, 1.0), (0.5, 10.0, 1.0)])
    waits = {"m0": 30.0, "m1": 0.0}
    router = Router(store, DynamicGreedy(), queue_aware=True)
    req = InferenceRequest(t_sla_ms=250.0, t_input_ms=40.0, rid=7,
                           sla_class="interactive")
    dec = router.route(req, np.random.default_rng(0),
                       w_queue_fn=waits.__getitem__)
    assert dec.admitted
    assert dec.request is req
    assert dec.budget.t_network_ms == 80.0
    assert dec.budget.t_budget_ms == 250.0 - 80.0           # Eq. 1
    assert dec.budget.w_queue_ms == waits[dec.variant]
    assert dec.budget.t_effective_ms == \
        dec.budget.t_budget_ms - dec.budget.w_queue_ms
    # shifted-store selection: m0 (mu 50 + 30 wait = 80) still fits 170
    assert dec.variant == "m0"
    assert not dec.fallback


def test_router_stats_counters():
    store = store_from([(0.9, 50.0, 1.0), (0.5, 10.0, 1.0)])
    router = Router(store, DynamicGreedy())
    rng = np.random.default_rng(0)
    router.route_batch([InferenceRequest(t_sla_ms=300.0, t_input_ms=10.0,
                                         rid=i) for i in range(5)], rng)
    router.route(InferenceRequest(t_sla_ms=300.0, t_input_ms=10.0), rng)
    s = router.stats()
    assert s["n_routed"] == 6 and s["n_admitted"] == 6
    assert s["n_batches"] == 2 and s["n_shed"] == 0
    assert s["mean_batch"] == 3.0


# ----------------------------------------------------------------------
# satellite: queue-aware Router == shifted_store + scalar select_traced
# ----------------------------------------------------------------------

@given(pool_strategy, st.floats(10.0, 500.0), st.floats(0.0, 50.0),
       waits_strategy, st.integers(0, 2**31 - 1))
@settings(max_examples=60, deadline=None)
def test_router_matches_shifted_store_scalar_path(pool, t_budget, threshold,
                                                  waits, seed):
    """For every policy, a queue-aware Router decision with injected
    W_queue is the same trace the scalar ``select_traced`` produces on
    the equivalent shifted store view."""
    waits = {f"m{i}": w for i, w in enumerate(waits[:len(pool)])}
    for make_policy in (lambda: ModiPick(t_threshold=threshold),
                        lambda: DynamicGreedy(),
                        lambda: RelatedRandom(threshold),
                        lambda: RelatedAccurate(threshold),
                        lambda: PureRandom(),
                        lambda: StaticGreedy(t_sla=t_budget + threshold)):
        store = store_from(pool)
        router = Router(store, make_policy(), queue_aware=True)
        dec = router.route(
            InferenceRequest(t_sla_ms=t_budget, t_input_ms=0.0),
            np.random.default_rng(seed), w_queue_fn=waits.__getitem__)
        ref_store = store_from(pool)
        expect = make_policy().select_traced(
            shifted_store(ref_store, waits.__getitem__), t_budget,
            np.random.default_rng(seed))
        assert dec.variant == expect.chosen
        assert dec.trace == expect


# ----------------------------------------------------------------------
# admission control
# ----------------------------------------------------------------------

def test_sla_aware_admission_sheds_when_no_model_viable():
    store = store_from([(0.9, 50.0, 1.0), (0.5, 10.0, 1.0)])
    tab = store.table()
    adm = SlaAwareAdmission()
    req = InferenceRequest(t_sla_ms=200.0, t_input_ms=25.0)  # budget 150
    ok, _ = adm.admit(req, 150.0, tab, {"m0": 149.0, "m1": 200.0}.__getitem__)
    assert ok                                   # m0's wait still fits
    ok, reason = adm.admit(req, 150.0, tab,
                           {"m0": 150.0, "m1": 400.0}.__getitem__)
    assert not ok and "budget" in reason
    # a non-positive budget can never be met: always shed
    ok, _ = adm.admit(req, -5.0, tab, {"m0": 0.0, "m1": 0.0}.__getitem__)
    assert not ok
    # no telemetry -> nothing to shed against
    assert adm.admit(req, -5.0, tab, None) == (True, "")


def test_sla_aware_admission_include_service_time():
    store = store_from([(0.9, 120.0, 1.0), (0.5, 10.0, 1.0)])
    tab = store.table()
    waits = {"m0": 0.0, "m1": 50.0}.__getitem__
    assert SlaAwareAdmission().admit(
        InferenceRequest(100.0, 0.0), 100.0, tab, waits)[0]
    # charging mu(m): m0 needs 120, m1 needs 60 -> m1 still viable at 100
    assert SlaAwareAdmission(include_service_time=True).admit(
        InferenceRequest(100.0, 0.0), 100.0, tab, waits)[0]
    ok, _ = SlaAwareAdmission(include_service_time=True).admit(
        InferenceRequest(55.0, 0.0), 55.0, tab, waits)
    assert not ok


def test_depth_cap_admission():
    store = store_from([(0.9, 50.0, 1.0), (0.5, 10.0, 1.0)])
    tab = store.table()
    adm = DepthCapAdmission(max_depth=2)
    req = InferenceRequest(t_sla_ms=200.0, t_input_ms=0.0)
    assert adm.admit(req, 200.0, tab, None, {"m0": 2, "m1": 1}.__getitem__)[0]
    ok, reason = adm.admit(req, 200.0, tab, None,
                           {"m0": 2, "m1": 5}.__getitem__)
    assert not ok and "depth" in reason
    assert adm.admit(req, 200.0, tab, None, None)[0]  # no depth telemetry


def test_admission_only_router_uses_store_telemetry():
    """A Router with SLA-aware admission but queue-blind selection must
    still fall back to the store's own queue telemetry when no estimator
    is injected — the controller cannot silently become a no-op."""
    store = store_from([(0.9, 50.0, 1.0), (0.5, 10.0, 1.0)])
    router = Router(store, DynamicGreedy(), admission=SlaAwareAdmission())
    rng = np.random.default_rng(0)
    req = InferenceRequest(t_sla_ms=100.0, t_input_ms=0.0)
    assert router.route(req, rng).admitted  # no telemetry yet: waits are 0
    for name in ("m0", "m1"):
        for _ in range(50):
            store.observe_queue(name, 500.0)  # both queues deeply backed up
    dec = router.route(req, rng)
    assert not dec.admitted and "budget" in dec.reject_reason
    assert dec.budget.w_queue_ms > 100.0
    assert isinstance(make_admission("none"), AdmitAll)
    assert isinstance(make_admission("sla_aware", slack_ms=5.0),
                      SlaAwareAdmission)
    assert isinstance(make_admission("depth_cap", max_depth=3),
                      DepthCapAdmission)
    with pytest.raises(ValueError):
        make_admission("bogus")


def test_engine_sla_aware_admission_sheds_under_overload():
    """Queue-blind ModiPick over one overloaded shared replica (every
    model behind the same FIFO, so no idle endpoint keeps requests
    viable): without admission every request completes (late); with
    SLA-aware admission the router sheds doomed requests before
    selection and the survivors' queue waits stay bounded."""
    def run(admission):
        sim = ServingSimulator(TABLE2, NET, shared_replicas(1),
                               seed=9, admission=admission)
        return sim, sim.run(ModiPick(t_threshold=20.0), 250.0, 500,
                            arrivals=PoissonArrivals(60.0))

    _, plain = run(None)
    sim, shed = run(SlaAwareAdmission())
    assert plain.n_rejected == 0
    assert shed.n_rejected > 0
    assert shed.n_completed + shed.n_rejected == 500
    assert all("budget" in r.reject_reason for r in sim.rejected_requests)
    assert shed.mean_queue_wait < plain.mean_queue_wait
    # router telemetry agrees with the engine's accounting
    assert sim.router.n_shed == shed.n_rejected
    assert sim.router.n_admitted == shed.n_completed


def test_executor_sla_aware_admission_sheds():
    rng = np.random.default_rng(0)
    pool = [_FakeVariant("small", 0.5, lambda: rng.normal(10, 1)),
            _FakeVariant("large", 0.9, lambda: rng.normal(80, 4))]
    waits = {"small": 1e6, "large": 1e6}
    ex = PoolExecutor(pool, NetworkModel(15.0, 0.0), DynamicGreedy(),
                      seed=1, admission=SlaAwareAdmission(),
                      w_queue_fn=lambda n: waits[n])
    ex.warm_up(np.zeros((1, 4), np.int32))
    res = ex.execute(np.zeros((1, 4), np.int32), t_sla=200.0)
    assert not res.admitted and not res.met_sla and res.variant == ""
    waits["small"] = 0.0
    res2 = ex.execute(np.zeros((1, 4), np.int32), t_sla=200.0)
    assert res2.admitted
    s = ex.summary()
    assert s["shed"] == 1 and s["n"] == 2


# ----------------------------------------------------------------------
# batched event-loop selection: <= one routing call per event-batch
# ----------------------------------------------------------------------

def _spy_route_batch(monkeypatch):
    """Spy both routing entry points: the array-native batch call and
    the scalar ``route_one`` fast path the engine takes for singleton
    event-batches (``route_batch`` delegates to the batch call too, so
    object-path calls are counted as well)."""
    calls = []
    orig_batch = Router.route_batch_arrays
    orig_one = Router.route_one

    def spy_batch(self, t_sla_ms, t_input_ms, rng, **kw):
        calls.append(len(t_sla_ms))
        return orig_batch(self, t_sla_ms, t_input_ms, rng, **kw)

    def spy_one(self, t_sla_ms, t_input_ms, rng, **kw):
        calls.append(1)
        return orig_one(self, t_sla_ms, t_input_ms, rng, **kw)

    monkeypatch.setattr(Router, "route_batch_arrays", spy_batch)
    monkeypatch.setattr(Router, "route_one", spy_one)
    return calls


def test_simultaneous_arrivals_route_in_one_batch(monkeypatch):
    """50 simultaneous arrivals over a zero-jitter network produce 50
    same-timestamp ENQUEUEs — the engine must issue ONE route_batch call
    for the whole event-batch, not 50 scalar selections."""
    calls = _spy_route_batch(monkeypatch)
    n = 50
    sim = ServingSimulator(TABLE2, NetworkModel(50.0, 0.0),
                           per_model_replicas(TABLE2), seed=2)
    r = sim.run(ModiPick(t_threshold=20.0), 250.0, n,
                arrivals=TraceArrivals([0.0] * n))
    assert calls == [n]
    assert r.n_completed == n
    assert sim.router.stats()["mean_batch"] == n
    assert set(r.model_usage) <= {e.name for e in TABLE2}


def test_staggered_arrivals_route_one_call_per_event_batch(monkeypatch):
    """Continuous arrival times never collide: every event-batch is a
    singleton and the engine issues exactly one route_batch per request
    (the scalar, draw-for-draw-identical path)."""
    calls = _spy_route_batch(monkeypatch)
    n = 40
    sim = ServingSimulator(TABLE2, NET, per_model_replicas(TABLE2), seed=3)
    r = sim.run(ModiPick(t_threshold=20.0), 250.0, n,
                arrivals=PoissonArrivals(20.0))
    assert calls == [1] * n
    assert r.n_completed == n


def test_lookahead_window_groups_nearby_enqueues(monkeypatch):
    """A non-zero batch window speculatively groups ENQUEUEs that land
    within it, cutting the number of route_batch calls below n."""
    calls = _spy_route_batch(monkeypatch)
    n = 60
    sim = ServingSimulator(TABLE2, NetworkModel(50.0, 0.0),
                           per_model_replicas(TABLE2), seed=4,
                           batch_window_ms=5.0)
    r = sim.run(DynamicGreedy(), 400.0, n,
                arrivals=TraceArrivals([0.5 * i for i in range(n)]))
    assert sum(calls) == n
    assert len(calls) < n          # some grouping happened
    assert max(calls) > 1
    assert r.n_completed == n
    # speculative routing must not start service before the uplink lands
    assert all(q.service_start_ms >= q.enqueue_ms - 1e-9
               for q in sim.completed_requests)
    assert all(q.queue_wait_ms >= 0.0 for q in sim.completed_requests)


# ----------------------------------------------------------------------
# heterogeneous per-request SLAs, end to end
# ----------------------------------------------------------------------

def test_heterogeneous_sla_mix_through_simulator():
    """Interactive (120ms) and batch (400ms) requests interleave through
    one engine run: the tight class rides fast models, the loose class
    reaches the accurate heavyweights, and attainment is scored against
    each request's own SLA."""
    sim = ServingSimulator(TABLE2, NET, per_model_replicas(TABLE2), seed=11)
    sla_of = lambda rid: 120.0 if rid % 2 == 0 else 400.0
    r = sim.run(ModiPick(t_threshold=20.0), 250.0, 600,
                arrivals=PoissonArrivals(10.0), sla_for=sla_of)
    assert r.n_completed == 600
    reqs = sim.completed_requests
    assert {q.t_sla_ms for q in reqs} == {120.0, 400.0}
    mu = lambda qs: np.mean([TRUTH[q.model].mu_ms for q in qs])
    acc = lambda qs: np.mean([TRUTH[q.model].top1 for q in qs])
    tight = [q for q in reqs if q.t_sla_ms == 120.0]
    loose = [q for q in reqs if q.t_sla_ms == 400.0]
    assert mu(tight) < mu(loose)
    assert acc(tight) < acc(loose)
    # attainment was scored per-request, not against the run-level label
    met = sum(q.e2e_ms <= q.t_sla_ms for q in reqs)
    assert r.sla_attainment == met / r.n_arrived


def test_heterogeneous_sla_mix_through_simultaneous_batch():
    """The same mix arriving simultaneously: heterogeneous budgets form
    one batched route_batch call and still split by class."""
    n = 200
    sim = ServingSimulator(TABLE2, NetworkModel(50.0, 0.0),
                           per_model_replicas(TABLE2), seed=12)
    r = sim.run(ModiPick(t_threshold=20.0), 250.0, n,
                arrivals=TraceArrivals([0.0] * n),
                sla_for=lambda rid: 120.0 if rid % 2 == 0 else 400.0)
    assert sim.router.stats()["n_batches"] == 1
    assert r.n_completed == n
    reqs = sim.completed_requests
    tight = [q for q in reqs if q.t_sla_ms == 120.0]
    loose = [q for q in reqs if q.t_sla_ms == 400.0]
    mu = lambda qs: np.mean([TRUTH[q.model].mu_ms for q in qs])
    assert mu(tight) < mu(loose)


@dataclass
class _FakeVariant:
    name: str
    quality: float
    latency_fn: Callable[[], float]

    def run(self, tokens, n_decode=2) -> float:
        return float(self.latency_fn())


def test_heterogeneous_sla_mix_through_executor():
    """The live executor serves an alternating 45ms/300ms SLA stream:
    per-request budgets steer tight requests to the small variant and
    loose ones to the large, and met_sla is scored per request."""
    rng = np.random.default_rng(1)
    pool = [_FakeVariant("small", 0.5, lambda: rng.normal(10, 1)),
            _FakeVariant("medium", 0.7, lambda: rng.normal(30, 2)),
            _FakeVariant("large", 0.9, lambda: rng.normal(80, 4))]
    ex = PoolExecutor(pool, NetworkModel(15.0, 7.0),
                      ModiPick(t_threshold=10.0), seed=1)
    ex.warm_up(np.zeros((1, 4), np.int32))
    toks = np.zeros((1, 4), np.int32)
    for i in range(300):
        ex.execute(toks, t_sla=45.0 if i % 2 == 0 else 300.0)
    rs = ex.results
    tight = [r for r in rs if r.t_sla_ms == 45.0]
    loose = [r for r in rs if r.t_sla_ms == 300.0]
    small_share = sum(r.variant == "small" for r in tight) / len(tight)
    large_share = sum(r.variant == "large" for r in loose) / len(loose)
    assert small_share > 0.5
    assert large_share > 0.3
    # per-request scoring: loose requests overwhelmingly meet their SLA
    assert np.mean([r.met_sla for r in loose]) > 0.9


# ----------------------------------------------------------------------
# route_batch standalone (no engine): vectorized heterogeneous budgets
# ----------------------------------------------------------------------

def test_route_batch_vectorized_heterogeneous_budgets():
    store = make_store(TABLE2)
    router = Router(store, ModiPick(t_threshold=20.0))
    rng = np.random.default_rng(5)
    slas = np.where(np.arange(400) % 2 == 0, 120.0, 400.0)
    reqs = [InferenceRequest(t_sla_ms=float(s), t_input_ms=50.0, rid=i)
            for i, s in enumerate(slas)]
    decs = router.route_batch(reqs, rng)
    assert len(decs) == 400 and all(d.admitted for d in decs)
    tab = store.table()
    mu_of = lambda ds: np.mean([tab.mu[tab.index[d.variant]] for d in ds])
    assert mu_of(decs[0::2]) < mu_of(decs[1::2])
    # batched traces carry the stage decomposition
    assert all(d.trace is not None for d in decs)
    assert any(d.base is not None and len(d.probs) >= 1 for d in decs)


# ----------------------------------------------------------------------
# select_batch_traced: batched traces match the scalar stages
# ----------------------------------------------------------------------

@given(pool_strategy, st.lists(st.floats(-20.0, 500.0), min_size=1,
                               max_size=24),
       st.floats(0.0, 50.0), st.integers(0, 2**31 - 1))
# A one-budget batch takes the scalar rule in both functions.
@example(pool=[(1.0, 1.0, 0.0)] * 3, budgets=[2.0], threshold=0.0, seed=0)
@settings(max_examples=60, deadline=None)
def test_select_batch_traced_matches_scalar_stages(pool, budgets, threshold,
                                                   seed):
    store = store_from(pool)
    budgets = np.asarray(budgets)
    mp = ModiPick(t_threshold=threshold)
    traces = policy_vec.select_batch_traced(
        mp, store, budgets, np.random.default_rng(seed), backend="numpy")
    picks = mp.select_batch(store, budgets, np.random.default_rng(seed),
                            backend="numpy")
    assert [t.chosen for t in traces] == picks
    for b, tb in enumerate(budgets):
        scalar = mp.select_traced(store, float(tb),
                                  np.random.default_rng(0))
        assert traces[b].fallback == scalar.fallback
        if scalar.fallback:
            continue
        assert traces[b].base == scalar.base
        assert set(traces[b].eligible) == set(scalar.eligible)
        batched = dict(zip(traces[b].eligible, traces[b].probs))
        for name, p in zip(scalar.eligible, scalar.probs):
            assert abs(batched[name] - p) < 1e-9
    # deterministic policies: fallback flag matches the scalar trace
    dg_traces = policy_vec.select_batch_traced(
        DynamicGreedy(), store, budgets, np.random.default_rng(seed))
    for b, tb in enumerate(budgets):
        scalar = DynamicGreedy().select_traced(store, float(tb),
                                               np.random.default_rng(0))
        assert dg_traces[b].chosen == scalar.chosen
        assert dg_traces[b].fallback == scalar.fallback


# ----------------------------------------------------------------------
# satellite: TraceArrivals validation
# ----------------------------------------------------------------------

def test_trace_arrivals_validation():
    with pytest.raises(ValueError, match="non-negative"):
        TraceArrivals([-1.0, 2.0])
    with pytest.raises(ValueError, match="sorted"):
        TraceArrivals([3.0, 2.0])
    with pytest.raises(ValueError, match="finite"):
        TraceArrivals([0.0, float("nan")])
    with pytest.raises(ValueError, match="finite"):
        TraceArrivals([0.0, float("inf")])
    with pytest.raises(ValueError, match="at least one"):
        TraceArrivals([])
    # duplicates are legal: simultaneous arrivals
    assert len(TraceArrivals([0.0, 0.0, 5.0])) == 3


# ----------------------------------------------------------------------
# satellite: backend validation lists the valid values
# ----------------------------------------------------------------------

def test_unknown_env_backend_message_lists_valid_values():
    """An unknown ``backend`` raises with the value and the valid ones,
    from ``select_batch`` and from a Router's batch."""
    store = make_store(TABLE2)
    with pytest.raises(ValueError) as e:
        ModiPick(20.0).select_batch(store, np.full(4, 200.0),
                                    np.random.default_rng(0),
                                    backend="bogus")
    assert "'bogus'" in str(e.value)
    assert "auto, numpy, jax" in str(e.value)
    router = Router(store, ModiPick(20.0), backend="tpu")
    with pytest.raises(ValueError, match="auto, numpy, jax"):
        router.route_batch_arrays(np.full(4, 300.0), np.full(4, 20.0),
                                  np.random.default_rng(0))


# ----------------------------------------------------------------------
# closed-loop driver rides the same Router
# ----------------------------------------------------------------------

def test_closed_loop_simulator_exposes_router():
    sim = Simulator(entries=TABLE2, network=NET, seed=1)
    r = sim.run(ModiPick(t_threshold=20.0), 200.0, 50)
    assert isinstance(sim.router, Router)
    assert sim.router.stats()["n_routed"] == 50
    assert r.n == 50


# ----------------------------------------------------------------------
# satellite: benchmark --smoke harness (CI bit-rot guard)
# ----------------------------------------------------------------------

def test_benchmarks_smoke_mode(tmp_path):
    """`benchmarks/run.py --smoke` runs every registered benchmark at
    toy scale — including the admission-policy axis — so a benchmark
    that stopped importing or running fails tier-1, not sweep time."""
    env = dict(os.environ,
               PYTHONPATH=f"{REPO / 'src'}{os.pathsep}{REPO}")
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--smoke", "--json",
         "--fail-fast"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=570)
    assert out.returncode == 0, out.stderr
    for marker in ("table2/", "fig6/sla_100,", "threshold/thr_0,",
                   "load_sweep/modipick/rate_5,",
                   "load_sweep/admission_sla_aware/rate_40,",
                   "sla_frontier/modipick/sla_250,",
                   "policy_throughput/numpy/batch_1000,",
                   "scenario_suite/steady,",
                   "scenario_suite/class_mix/class_interactive,",
                   "scenario_suite/scale_up/epoch_4,",
                   "drift_resilience/drift_mu2_window,",
                   "drift_resilience/faulty_retry,",
                   "fleet_throughput/scale_4cell,",
                   "fleet_throughput/frontier_rate_540,",
                   "fleet_throughput/window_5ms,",
                   "live_pool/modipick,"):
        assert marker in out.stdout, marker
    # smoke writes suffixed records so toy-scale rows can never clobber
    # the tracked full-scale BENCH_<name>.json artifacts
    assert not (tmp_path / "BENCH_load_sweep.json").exists()
    data = json.loads((tmp_path / "BENCH_load_sweep_smoke.json").read_text())
    names = [r["name"] for r in data["rows"]]
    assert any(n.startswith("load_sweep/admission_") for n in names)
