"""Scenario API: spec validation + dict round trips, golden-pinned
build() equivalence with the historical kwargs paths, the from_scenario
adapters, diurnal/burst synthesizers, class-aware admission (unit and
end-to-end protection), the queue-target autoscaler loop, and the
Router.stats()/reset() + deprecation-shim satellites."""
import dataclasses
import json
from dataclasses import dataclass
from typing import Callable

import numpy as np
import pytest

from repro.core.netmodel import NetworkModel
from repro.core.policy import ModiPick, make_policy
from repro.core.profiles import ModelProfile, ProfileStore
from repro.core.simulate import Simulator
from repro.core.zoo import TABLE2
from repro.router import (ClassAwareAdmission, ClassPolicy, DepthCapAdmission,
                          InferenceRequest, Router, SlaAwareAdmission,
                          make_admission)
from repro.scenario import (AutoscalerSpec, DeploymentSpec, NetworkSpec,
                            PolicySpec, QueueTargetAutoscaler, Scenario,
                            SlaClass, WorkloadSpec, build, get_scenario,
                            list_scenarios, register)
from repro.serving.executor import PoolExecutor
from repro.sim import (PoissonArrivals, ServingSimulator, burst_trace,
                       diurnal_trace, per_model_replicas, shared_replicas)

NET = NetworkModel(50.0, 25.0)


# ----------------------------------------------------------------------
# spec: validation + serialization round trip
# ----------------------------------------------------------------------

def test_round_trip_every_registered_scenario():
    """Acceptance: Scenario.from_dict(s.to_dict()) == s for every
    registered scenario, through actual JSON text."""
    names = list_scenarios()
    assert {"steady", "diurnal", "burst", "class_mix", "scale_up",
            "fleet_steady", "fleet_diurnal", "premodel_mix", "tail_sla",
            "tail_sla_mean", "elastic_step", "elastic_proportional",
            "elastic_cost_weighted"} <= set(names)
    for name in names:
        s = get_scenario(name)
        d = s.to_dict()
        via_json = json.loads(json.dumps(d))    # plain data, JSON-clean
        assert Scenario.from_dict(via_json) == s
        assert Scenario.from_dict(d) == s


def test_from_file_round_trip_and_error_paths(tmp_path):
    s = get_scenario("premodel_mix")
    jpath = tmp_path / "scenario.json"
    jpath.write_text(json.dumps(s.to_dict()), encoding="utf-8")
    assert Scenario.from_file(jpath) == s

    tpath = tmp_path / "scenario.toml"
    tpath.write_text(
        'name = "tiny"\n'
        "[workload]\n"
        "n_requests = 10\n"
        "[policy]\n"
        "queue_aware = true\n", encoding="utf-8")
    t = Scenario.from_file(tpath)
    assert t.name == "tiny" and t.workload.n_requests == 10

    bad_json = tmp_path / "bad.json"
    bad_json.write_text("{not json", encoding="utf-8")
    with pytest.raises(json.JSONDecodeError):
        Scenario.from_file(bad_json)

    import tomllib
    bad_toml = tmp_path / "bad.toml"
    bad_toml.write_text("name = ", encoding="utf-8")
    with pytest.raises(tomllib.TOMLDecodeError):
        Scenario.from_file(bad_toml)

    typo = tmp_path / "typo.json"
    typo.write_text(json.dumps({**s.to_dict(), "wrokload": {}}),
                    encoding="utf-8")
    with pytest.raises(ValueError, match="unknown scenario keys"):
        Scenario.from_file(typo)

    with pytest.raises(FileNotFoundError):
        Scenario.from_file(tmp_path / "missing.json")


def test_spec_validation_rejects_malformed_configs():
    with pytest.raises(ValueError, match="arrival"):
        WorkloadSpec(arrival="bogus")
    with pytest.raises(ValueError, match="rate_rps"):
        WorkloadSpec(arrival="poisson", rate_rps=0.0)
    with pytest.raises(ValueError, match="times_ms"):
        WorkloadSpec(arrival="trace")
    with pytest.raises(ValueError, match="rate_schedule"):
        WorkloadSpec(arrival="poisson", rate_schedule=(5.0, 10.0), epochs=3)
    with pytest.raises(ValueError, match="burst_rate_rps"):
        WorkloadSpec(arrival="burst", rate_rps=10.0, burst_rate_rps=5.0)
    with pytest.raises(ValueError, match="amplitude"):
        WorkloadSpec(arrival="diurnal", rate_rps=5.0, amplitude=1.5)
    with pytest.raises(ValueError, match="burst_len_ms"):
        WorkloadSpec(arrival="burst", rate_rps=4.0, burst_rate_rps=8.0,
                     burst_len_ms=0.0)
    with pytest.raises(ValueError, match="backend"):
        PolicySpec(backend="garbage")
    with pytest.raises(ValueError, match="every epoch"):
        WorkloadSpec(arrival="poisson", rate_rps=5.0, n_requests=3,
                     epochs=4)
    with pytest.raises(ValueError, match="every epoch"):
        # trace n_requests derives from the trace: 3 points, 4 epochs
        WorkloadSpec(arrival="trace", times_ms=(0.0, 1.0, 2.0), epochs=4)
    with pytest.raises(ValueError, match="duplicate"):
        WorkloadSpec(classes=(SlaClass("a", 100.0), SlaClass("a", 200.0)))
    with pytest.raises(ValueError, match="topology"):
        DeploymentSpec(topology="mesh")
    with pytest.raises(ValueError, match="speeds"):
        DeploymentSpec(topology="shared", replicas=2, speeds=(1.0,))
    with pytest.raises(ValueError, match="admission"):
        DeploymentSpec(admission="bogus")
    with pytest.raises(ValueError, match="policy"):
        PolicySpec(policy="bogus")
    with pytest.raises(ValueError, match="min_replicas"):
        AutoscalerSpec(min_replicas=5, max_replicas=2)
    with pytest.raises(ValueError, match="epochs"):
        Scenario(name="x",
                 deployment=DeploymentSpec(autoscaler=AutoscalerSpec()))
    with pytest.raises(ValueError, match="subset"):
        build(Scenario(name="x", deployment=DeploymentSpec(
            subset=("NotAModel",)))).engine()


def test_registry_rejects_silent_shadowing():
    s = get_scenario("steady")
    with pytest.raises(ValueError, match="already registered"):
        register(dataclasses.replace(s))
    register(dataclasses.replace(s), replace=True)   # explicit is fine


# ----------------------------------------------------------------------
# acceptance: build() reproduces the seeded engine goldens bit-identically
# ----------------------------------------------------------------------

def test_steady_scenario_reproduces_engine_golden_bit_identical():
    """The registered steady scenario IS the seeded queue-aware golden
    config; the Scenario path must reproduce it bit for bit."""
    r = build(get_scenario("steady")).run().result
    assert r.sla_attainment == 0.9983333333333333
    assert r.mean_accuracy == 0.7975266666666666
    assert r.mean_latency == 191.67831081440173
    assert r.mean_queue_wait == 23.493148434870164
    # and it equals a fresh hand-wired engine run, field for field
    eng = ServingSimulator(TABLE2, NET, per_model_replicas(TABLE2), seed=3,
                           queue_aware=True)
    ref = eng.run(ModiPick(t_threshold=20.0), 250.0, 600,
                  arrivals=PoissonArrivals(30.0))
    assert r == ref


def test_closed_loop_scenario_reproduces_paper_golden():
    sc = Scenario(
        name="paper_loop",
        workload=WorkloadSpec(arrival="closed_loop", n_requests=800,
                              t_sla_ms=200.0),
        network=NetworkSpec(50.0, 25.0),
        deployment=DeploymentSpec(topology="shared", replicas=1),
        policy=PolicySpec(policy="modipick", kwargs={"t_threshold": 20.0}),
        seed=1)
    sim = Simulator.from_scenario(sc)
    r = sim.run(ModiPick(t_threshold=20.0), 200.0, 800)
    assert r.sla_attainment == 0.9775            # pinned golden
    assert r.mean_accuracy == 0.7813437499999999
    # the harness's engine path agrees on the same numbers
    h = build(sc).run().result
    assert h.sla_attainment == 0.9775
    assert h.mean_accuracy == 0.7813437499999999


def test_from_scenario_adapters_match_builder():
    sc = get_scenario("steady")
    eng = ServingSimulator.from_scenario(sc)
    assert isinstance(eng, ServingSimulator)
    assert eng.seed == 3 and eng.queue_aware
    assert len(eng.pool.replicas) == len(TABLE2)
    with pytest.raises(ValueError, match="closed loop"):
        Simulator.from_scenario(sc)              # steady is open-loop


@dataclass
class _FakeVariant:
    name: str
    quality: float
    latency_fn: Callable[[], float]

    def run(self, tokens, n_decode=2) -> float:
        return float(self.latency_fn())


def test_executor_from_scenario():
    sc = Scenario(
        name="exec", workload=WorkloadSpec(arrival="poisson", rate_rps=5.0,
                                           n_requests=10, t_sla_ms=200.0),
        network=NetworkSpec(15.0, 0.0),
        deployment=DeploymentSpec(admission="sla_aware"),
        policy=PolicySpec(policy="dynamic_greedy", queue_aware=True),
        seed=1)
    rng = np.random.default_rng(0)
    pool = [_FakeVariant("small", 0.5, lambda: rng.normal(10, 1)),
            _FakeVariant("large", 0.9, lambda: rng.normal(80, 4))]
    ex = PoolExecutor.from_scenario(sc, pool)
    assert isinstance(ex.router.admission, SlaAwareAdmission)
    assert ex.queue_aware and ex.seed == 1
    ex.warm_up(np.zeros((1, 4), np.int32))
    res = ex.execute(np.zeros((1, 4), np.int32), t_sla=200.0)
    assert res.admitted and res.variant in {"small", "large"}


# ----------------------------------------------------------------------
# diurnal / burst synthesizers
# ----------------------------------------------------------------------

def test_synthesized_trace_stream_decorrelated_from_engine_seed():
    """The thinning sampler must not share the engine's PCG64 stream:
    build_arrival_times salts the scenario seed."""
    from repro.scenario.build import build_arrival_times
    sc = get_scenario("diurnal")
    wl = sc.workload
    salted = build_arrival_times(sc)
    unsalted = np.asarray(diurnal_trace(
        wl.n_requests, wl.rate_rps, period_ms=wl.period_ms,
        amplitude=wl.amplitude, seed=sc.seed).times_ms)
    assert not np.array_equal(salted, unsalted)
    np.testing.assert_array_equal(salted, build_arrival_times(sc))


def test_diurnal_trace_shape_and_determinism():
    tr = diurnal_trace(2000, 20.0, period_ms=10_000.0, amplitude=0.9,
                       seed=4)
    t = np.asarray(tr.times_ms)
    assert len(t) == 2000 and (np.diff(t) > 0).all() and t[0] >= 0.0
    again = diurnal_trace(2000, 20.0, period_ms=10_000.0, amplitude=0.9,
                          seed=4)
    np.testing.assert_array_equal(t, np.asarray(again.times_ms))
    # peak half-cycles (sin > 0) must hold more arrivals than troughs
    phase = (t % 10_000.0) < 5_000.0
    assert phase.sum() > 1.5 * (~phase).sum()
    with pytest.raises(ValueError, match="amplitude"):
        diurnal_trace(10, 5.0, amplitude=1.0)


def test_burst_trace_concentrates_arrivals_in_bursts():
    tr = burst_trace(2000, 2.0, burst_rate_rps=100.0,
                     burst_every_ms=5_000.0, burst_len_ms=500.0, seed=4)
    t = np.asarray(tr.times_ms)
    assert len(t) == 2000 and (np.diff(t) > 0).all()
    in_burst = (t % 5_000.0) < 500.0
    # burst windows are 10% of the time but 100/2 = 50x the rate
    assert in_burst.mean() > 0.7
    with pytest.raises(ValueError, match="burst_len_ms"):
        burst_trace(10, 2.0, burst_rate_rps=10.0, burst_every_ms=100.0,
                    burst_len_ms=200.0)


# ----------------------------------------------------------------------
# class-aware admission: unit semantics
# ----------------------------------------------------------------------

def _table2_store():
    profiles = [ModelProfile(name=f"m{i}", accuracy=a)
                for i, a in enumerate((0.5, 0.9))]
    for p, (mu, s) in zip(profiles, ((10.0, 1.0), (80.0, 2.0))):
        p.mu, p.var, p.n_obs = mu, s ** 2, 100
    return ProfileStore(profiles)


def test_class_policy_validation():
    with pytest.raises(ValueError, match="protect"):
        ClassPolicy(protect=0.0)
    with pytest.raises(ValueError, match="max_share"):
        ClassPolicy(max_share=1.5)


def test_class_aware_protect_one_matches_sla_aware():
    """protect=1.0 is exactly SlaAwareAdmission viability."""
    tab = _table2_store().table()
    adm = ClassAwareAdmission(default=ClassPolicy(protect=1.0))
    ref = SlaAwareAdmission()
    req = InferenceRequest(t_sla_ms=200.0, t_input_ms=25.0)
    for waits in ({"m0": 149.0, "m1": 200.0}, {"m0": 150.0, "m1": 400.0},
                  {"m0": 0.0, "m1": 0.0}):
        for budget in (150.0, -5.0):
            assert adm.admit(req, budget, tab, waits.__getitem__)[0] == \
                ref.admit(req, budget, tab, waits.__getitem__)[0]
    assert adm.admit(req, -5.0, tab, None) == (True, "")   # no telemetry


def test_class_aware_weighted_shedding_orders_classes():
    """With queues eating 40% of the budget, a protect=0.35 class sheds
    while protect=1.0 still admits — batch drains before interactive."""
    tab = _table2_store().table()
    adm = ClassAwareAdmission(classes={
        "interactive": ClassPolicy(protect=1.0),
        "batch": {"protect": 0.35},      # dict form coerces
    })
    waits = {"m0": 80.0, "m1": 80.0}.__getitem__
    inter = InferenceRequest(t_sla_ms=200.0, t_input_ms=0.0,
                             sla_class="interactive")
    batch = InferenceRequest(t_sla_ms=200.0, t_input_ms=0.0,
                             sla_class="batch")
    assert adm.admit(inter, 200.0, tab, waits)[0]
    ok, reason = adm.admit(batch, 200.0, tab, waits)
    assert not ok and "batch" in reason and "0.35" in reason
    # unknown classes ride the default policy (protect=1.0 here)
    other = InferenceRequest(t_sla_ms=200.0, t_input_ms=0.0,
                             sla_class="mystery")
    assert adm.admit(other, 200.0, tab, waits)[0]


def test_class_aware_share_quota_under_pressure():
    tab = _table2_store().table()
    adm = ClassAwareAdmission(
        classes={"batch": ClassPolicy(protect=1.0, max_share=0.5)},
        pressure_ms=5.0)
    quiet = {"m0": 0.0, "m1": 0.0}.__getitem__
    busy = {"m0": 50.0, "m1": 60.0}.__getitem__
    batch = InferenceRequest(t_sla_ms=500.0, t_input_ms=0.0,
                             sla_class="batch")
    inter = InferenceRequest(t_sla_ms=500.0, t_input_ms=0.0,
                             sla_class="interactive")
    # no pressure: quota dormant, batch admits freely
    for _ in range(4):
        assert adm.admit(batch, 500.0, tab, quiet)[0]
    # under pressure batch is over its 50% share (4/4 admitted): shed
    ok, reason = adm.admit(batch, 500.0, tab, busy)
    assert not ok and "quota" in reason
    assert adm.admit(inter, 500.0, tab, busy)[0]   # unquotaed class fine
    # admitting interactive traffic dilutes batch's share below quota
    for _ in range(6):
        adm.admit(inter, 500.0, tab, busy)
    assert adm.admit(batch, 500.0, tab, busy)[0]
    # reset() clears the window: first-request guard admits again
    adm.reset()
    assert adm.n_admitted == 0 and adm.admitted_by_class == {}
    assert adm.admit(batch, 500.0, tab, busy)[0]
    assert isinstance(make_admission("class_aware"), ClassAwareAdmission)


def test_class_mix_scenario_protects_interactive_end_to_end():
    """Acceptance: under one saturated shared replica, class-aware
    admission sheds batch first and interactive keeps (much) more of its
    attainment than batch — and than it would under class-blind
    sla_aware admission."""
    sc = dataclasses.replace(
        get_scenario("class_mix"),
        workload=dataclasses.replace(get_scenario("class_mix").workload,
                                     n_requests=500))
    r = build(sc).run().result
    inter, batch = r.per_class["interactive"], r.per_class["batch"]
    assert batch["shed_rate"] > inter["shed_rate"] + 0.2
    assert inter["attainment"] > batch["attainment"] + 0.2
    # class-blind baseline: same load, sla_aware — interactive collapses
    blind = dataclasses.replace(
        sc, name="class_mix_blind",
        deployment=dataclasses.replace(sc.deployment, admission="sla_aware",
                                       admission_kwargs={}))
    rb = build(blind).run().result
    assert r.per_class["interactive"]["attainment"] > \
        rb.per_class["interactive"]["attainment"] + 0.2


def test_per_class_rows_do_not_perturb_the_run():
    """class_for labels must not touch the RNG: a labelled run is
    draw-for-draw identical to the unlabelled run, plus per_class rows
    whose totals reconcile with the run-level counters."""
    def run(class_for):
        eng = ServingSimulator(TABLE2, NET, per_model_replicas(TABLE2),
                               seed=6)
        return eng.run(ModiPick(t_threshold=20.0), 250.0, 300,
                       arrivals=PoissonArrivals(20.0), class_for=class_for)

    plain = run(None)
    labelled = run(lambda rid: "even" if rid % 2 == 0 else "odd")
    assert plain.per_class == {}
    assert set(labelled.per_class) == {"even", "odd"}
    for f in ("sla_attainment", "mean_accuracy", "mean_latency",
              "p99_latency", "mean_queue_wait"):
        assert getattr(plain, f) == getattr(labelled, f)
    total = sum(c["n_arrived"] for c in labelled.per_class.values())
    assert total == labelled.n_arrived


# ----------------------------------------------------------------------
# autoscaler
# ----------------------------------------------------------------------

def _stats(routed=100, shed=0, fallback=0):
    return {"n_routed": routed, "n_shed": shed, "n_fallback": fallback,
            "n_batches": 10, "mean_batch": routed / 10}


@dataclass
class _FakeResult:
    mean_queue_wait: float
    replica_utilization: dict


def test_queue_target_autoscaler_decisions():
    sc = QueueTargetAutoscaler(AutoscalerSpec(
        target_queue_ms=50.0, max_shed_rate=0.02, min_replicas=1,
        max_replicas=4, step=2, low_utilization=0.3))
    hot = _FakeResult(120.0, {"r0": 0.99})
    assert sc.decide(1, _stats(), hot) == 3
    assert sc.decide(3, _stats(), hot) == 4          # capped at max
    shedding = _FakeResult(10.0, {"r0": 0.8})
    assert sc.decide(2, _stats(shed=10), shedding) == 4
    steady = _FakeResult(20.0, {"r0": 0.6})
    assert sc.decide(2, _stats(), steady) == 2       # in band: hold
    idle = _FakeResult(1.0, {"r0": 0.05, "r1": 0.05})
    assert sc.decide(3, _stats(), idle) == 1
    assert sc.decide(1, _stats(), idle) == 1         # floored at min


def test_scale_up_scenario_recovers_attainment():
    """Acceptance: SLA attainment collapses at the 10x load step and
    recovers in later epochs purely through autoscaler replica adds."""
    full = get_scenario("scale_up")
    sc = dataclasses.replace(
        full, workload=dataclasses.replace(full.workload, n_requests=1000))
    out = build(sc).run()
    att, reps = out.attainment_history, out.replica_history
    assert reps[0] == reps[1] == 1                   # scaling acts *after*
    step_epoch, last = att[1], att[-1]
    assert step_epoch < 0.8                          # the step hurt
    assert reps[-1] > 1                              # it scaled up...
    assert last > step_epoch + 0.15                  # ...and recovered
    assert last > 0.9


# ----------------------------------------------------------------------
# satellites: Router.stats()/reset(), DepthCap edge case, shim warning
# ----------------------------------------------------------------------

def test_router_stats_after_mixed_admit_shed_batches_and_reset():
    """stats() semantics over batches that mix admits and sheds, then
    reset() for windowed (per-epoch) consumption."""
    profiles = [ModelProfile(name="m0", accuracy=0.9)]
    profiles[0].mu, profiles[0].var, profiles[0].n_obs = 50.0, 1.0, 100
    store = ProfileStore(profiles)
    router = Router(store, ModiPick(t_threshold=20.0),
                    admission=SlaAwareAdmission())
    rng = np.random.default_rng(0)
    # budget 300 admits; budget -100 (network ate the SLA) always sheds
    reqs = [InferenceRequest(t_sla_ms=300.0, t_input_ms=0.0, rid=0),
            InferenceRequest(t_sla_ms=100.0, t_input_ms=100.0, rid=1),
            InferenceRequest(t_sla_ms=300.0, t_input_ms=0.0, rid=2)]
    for _ in range(2):
        decs = router.route_batch(reqs, rng,
                                  w_queue_fn=lambda m: 0.0)
        assert [d.admitted for d in decs] == [True, False, True]
    s = router.stats()
    assert s["n_routed"] == 6 and s["n_admitted"] == 4 and s["n_shed"] == 2
    assert s["n_batches"] == 2 and s["mean_batch"] == 3.0
    router.reset()
    z = router.stats()
    assert all(z[k] == 0 for k in ("n_routed", "n_admitted", "n_shed",
                                   "n_fallback", "n_batches"))
    assert z["mean_batch"] == 0.0
    # windowed: post-reset stats cover only new traffic
    router.route_batch(reqs[:1], rng, w_queue_fn=lambda m: 0.0)
    assert router.stats()["n_routed"] == 1


def test_router_reset_clears_admission_window():
    store = _table2_store()
    adm = ClassAwareAdmission(default=ClassPolicy(max_share=0.5))
    router = Router(store, ModiPick(t_threshold=20.0), admission=adm)
    router.route(InferenceRequest(t_sla_ms=300.0, t_input_ms=0.0),
                 np.random.default_rng(0))
    assert adm.n_admitted == 1
    router.reset()
    assert adm.n_admitted == 0


def test_depth_cap_admission_without_w_queue_fn():
    """Regression pin: DepthCapAdmission never consumes W_queue — its
    verdict with w_queue_fn=None must equal the verdict with any
    estimator, and needs_w_queue stays False so the Router skips the
    telemetry snapshot entirely."""
    tab = _table2_store().table()
    adm = DepthCapAdmission(max_depth=2)
    assert adm.needs_w_queue is False
    req = InferenceRequest(t_sla_ms=200.0, t_input_ms=0.0)
    for depths in ({"m0": 0, "m1": 5}, {"m0": 2, "m1": 2}):
        with_fn = adm.admit(req, 200.0, tab, lambda m: 1e9,
                            depths.__getitem__)
        without = adm.admit(req, 200.0, tab, None, depths.__getitem__)
        assert with_fn == without
    # and with NEITHER telemetry source there is nothing to cap against
    assert adm.admit(req, 200.0, tab, None, None) == (True, "")
    # stateless: base-class reset() is a no-op that must exist (Router
    # calls it on every controller)
    adm.reset()


def test_make_policy_registry():
    assert isinstance(make_policy("modipick", t_threshold=5.0), ModiPick)
    with pytest.raises(ValueError, match="unknown policy"):
        make_policy("bogus")


# ----------------------------------------------------------------------
# harness slicing
# ----------------------------------------------------------------------

def test_epoch_slicing_and_rate_schedule():
    sc = Scenario(
        name="sliced",
        workload=WorkloadSpec(arrival="poisson", rate_rps=5.0,
                              rate_schedule=(5.0, 50.0, 50.0),
                              epochs=3, n_requests=100),
        deployment=DeploymentSpec(topology="shared", replicas=1))
    h = build(sc)
    assert h.epoch_sizes() == [34, 33, 33]
    assert [h.arrivals(e).rate_rps for e in range(3)] == [5.0, 50.0, 50.0]
    out = h.run()
    assert [e.result.n_arrived for e in out.epochs] == [34, 33, 33]


def test_trace_workload_derives_n_requests():
    """A trace IS the workload: n_requests always equals len(times_ms),
    so epoch slicing can never run off the end of the trace."""
    wl = WorkloadSpec(arrival="trace", times_ms=(0.0, 1.0, 2.0), epochs=2)
    assert wl.n_requests == 3
    sc = Scenario(name="tiny", workload=wl,
                  deployment=DeploymentSpec(topology="shared", replicas=1))
    out = build(sc).run()           # regression: used to IndexError
    assert sum(e.result.n_arrived for e in out.epochs) == 3


def test_policy_backend_reaches_the_router():
    sc = dataclasses.replace(
        get_scenario("steady"),
        policy=dataclasses.replace(get_scenario("steady").policy,
                                   backend="numpy"))
    eng = ServingSimulator.from_scenario(sc)
    assert eng.backend == "numpy"
    eng.run(ModiPick(t_threshold=20.0), 250.0, 5,
            arrivals=PoissonArrivals(5.0))
    assert eng.router.backend == "numpy"


def test_trace_scenario_epoch_slices_rebase_to_zero():
    times = tuple(float(10 * i) for i in range(40))
    sc = Scenario(
        name="tr",
        workload=WorkloadSpec(arrival="trace", times_ms=times, epochs=2,
                              n_requests=40),
        deployment=DeploymentSpec(topology="shared", replicas=1))
    h = build(sc)
    a0, a1 = h.arrivals(0), h.arrivals(1)
    assert len(a0) == 20 and len(a1) == 20
    assert a1.times_ms[0] == 0.0                  # rebased window
    np.testing.assert_allclose(np.diff(a1.times_ms), 10.0)
