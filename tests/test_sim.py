"""Discrete-event serving simulator: determinism, conservation,
closed-loop equivalence, queue-aware budgets, and load behaviour."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.netmodel import NetworkModel
from repro.core.policy import (DynamicGreedy, ModiPick, StaticGreedy,
                               budget)
from repro.core.profiles import ModelProfile, ProfileStore
from repro.core.simulate import Simulator
from repro.core.zoo import TABLE2
from repro.sim import (ClosedLoopArrivals, PoissonArrivals, QueueAwareSelector,
                       ServingSimulator, TraceArrivals, per_model_replicas,
                       queue_aware_budget, shared_replicas, shifted_store)

NET = NetworkModel(50.0, 25.0)


def engine(replicas=None, *, seed=0, queue_aware=False, **kw):
    return ServingSimulator(TABLE2, NET,
                            replicas or per_model_replicas(TABLE2),
                            seed=seed, queue_aware=queue_aware, **kw)


def result_key(r):
    return (r.n_arrived, r.n_completed, r.n_rejected, r.sla_attainment,
            r.mean_accuracy, r.mean_latency, r.p99_latency,
            r.mean_queue_wait, tuple(sorted(r.model_usage.items())))


# ----------------------------------------------------------------------
# determinism
def test_deterministic_under_fixed_seed():
    a = engine(seed=3, queue_aware=True).run(
        ModiPick(t_threshold=20.0), 250.0, 600,
        arrivals=PoissonArrivals(30.0))
    b = engine(seed=3, queue_aware=True).run(
        ModiPick(t_threshold=20.0), 250.0, 600,
        arrivals=PoissonArrivals(30.0))
    assert result_key(a) == result_key(b)


# ----------------------------------------------------------------------
# conservation
def test_conservation_all_requests_accounted():
    sim = engine(per_model_replicas(TABLE2, max_queue_depth=2), seed=5,
                 queue_aware=False)
    n = 800
    r = sim.run(ModiPick(t_threshold=20.0), 250.0, n,
                arrivals=PoissonArrivals(60.0))
    assert r.n_arrived == n
    assert r.n_completed + r.n_rejected == n
    assert r.n_rejected > 0  # depth-2 caps under 60 rps must shed load


def test_rejections_count_as_sla_misses():
    sim = engine(per_model_replicas(TABLE2, max_queue_depth=1), seed=5)
    r = sim.run(ModiPick(t_threshold=20.0), 250.0, 500,
                arrivals=PoissonArrivals(80.0))
    met_upper = (r.n_arrived - r.n_rejected) / r.n_arrived
    assert r.sla_attainment <= met_upper + 1e-12


# ----------------------------------------------------------------------
# closed-loop / zero-load equivalence
def test_closed_loop_has_zero_queue_wait():
    r = engine(shared_replicas(1), seed=1).run(
        ModiPick(t_threshold=20.0), 200.0, 400,
        arrivals=ClosedLoopArrivals())
    assert r.mean_queue_wait == 0.0
    assert r.n_rejected == 0


def test_queue_aware_closed_loop_identical_to_plain():
    """W_queue == 0 throughout a closed loop, so queue-aware selection
    must reduce exactly to Eq. 1 behaviour — bit-identical results."""
    plain = engine(shared_replicas(1), seed=2).run(
        ModiPick(t_threshold=20.0), 200.0, 400)
    qa = engine(shared_replicas(1), seed=2, queue_aware=True).run(
        ModiPick(t_threshold=20.0), 200.0, 400)
    assert result_key(plain) == result_key(qa)


def test_zero_load_open_loop_matches_paper_closed_loop():
    """At negligible arrival rate the open-loop engine reproduces the
    paper's closed-loop results within sampling tolerance."""
    n, sla = 800, 200.0
    closed = Simulator(entries=TABLE2, network=NET, seed=1).run(
        ModiPick(t_threshold=20.0), sla, n)
    open_ = engine(seed=1).run(
        ModiPick(t_threshold=20.0), sla, n,
        arrivals=PoissonArrivals(0.2))  # 5s gaps >> max service time
    assert open_.mean_queue_wait < 1.0
    assert abs(open_.sla_attainment - closed.sla_attainment) < 0.05
    assert abs(open_.mean_accuracy - closed.mean_accuracy) < 0.05
    assert abs(open_.mean_latency - closed.mean_latency) < 15.0


# ----------------------------------------------------------------------
# queue-aware budget algebra
def test_queue_aware_budget_reduces_to_eq1():
    assert queue_aware_budget(200.0, 30.0, 0.0) == budget(200.0, 30.0)
    assert queue_aware_budget(200.0, 30.0, 25.0) == 115.0


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


@given(pool_strategy, st.floats(10.0, 500.0), st.floats(0.0, 50.0),
       st.integers(0, 2**31 - 1))
@settings(max_examples=100, deadline=None)
def test_selector_with_zero_wait_equals_plain_policy(pool, t_budget,
                                                     threshold, seed):
    store = store_from(pool)
    policy = ModiPick(t_threshold=threshold)
    plain = policy.select_traced(store, t_budget,
                                 np.random.default_rng(seed))
    qa = QueueAwareSelector(policy).select_traced(
        store, t_budget, lambda m: 0.0, np.random.default_rng(seed))
    assert plain == qa


@given(pool_strategy, st.floats(10.0, 500.0), st.floats(1.0, 100.0),
       st.integers(0, 2**31 - 1))
@settings(max_examples=100, deadline=None)
def test_shifted_store_moves_means_only(pool, t_budget, wait, seed):
    store = store_from(pool)
    view = shifted_store(store, lambda m: wait)
    assert view is not store
    for name in store.names():
        assert view[name].mu == pytest.approx(store[name].mu + wait)
        assert view[name].sigma == pytest.approx(store[name].sigma)
        assert view[name].accuracy == store[name].accuracy


def test_queue_aware_respects_shifted_budget():
    """A model whose queue wait eats the whole budget must not be
    chosen by the greedy stage."""
    store = store_from([(0.9, 50.0, 1.0), (0.5, 10.0, 1.0)])
    sel = QueueAwareSelector(DynamicGreedy())
    rng = np.random.default_rng(0)
    # plain: the accurate m0 fits a 100ms budget
    assert DynamicGreedy().select(store, 100.0, rng) == "m0"
    # 80ms backlog in front of m0 pushes it over; m1 idle
    waits = {"m0": 80.0, "m1": 0.0}
    trace = sel.select_traced(store, 100.0, lambda m: waits[m], rng)
    assert trace.chosen == "m1"
    assert not trace.fallback


# ----------------------------------------------------------------------
# request lifecycle / ordering
def test_fifo_order_per_replica():
    sim = engine(shared_replicas(2), seed=9)
    r = sim.run(DynamicGreedy(), 400.0, 400,
                arrivals=PoissonArrivals(50.0))
    assert r.n_completed == 400
    assert r.mean_queue_wait >= 0.0
    # peak depth must have exceeded 1 for the FIFO to be exercised
    assert r.peak_queue_depth > 1


def test_trace_arrivals_replayed_exactly():
    times = [0.0, 10.0, 500.0, 1500.0, 1501.0]
    sim = engine(shared_replicas(1), seed=4)
    r = sim.run(DynamicGreedy(), 400.0, len(times),
                arrivals=TraceArrivals(times))
    assert r.n_arrived == len(times)
    assert r.n_completed == len(times)


def test_utilization_and_usage_consistency():
    r = engine(seed=6, queue_aware=True).run(
        ModiPick(t_threshold=20.0), 250.0, 500,
        arrivals=PoissonArrivals(20.0))
    assert abs(sum(r.model_usage.values()) - 1.0) < 1e-9
    assert all(0.0 <= u <= 1.0 + 1e-9
               for u in r.replica_utilization.values())


# ----------------------------------------------------------------------
# the headline: queue-awareness under load
def test_queue_aware_beats_plain_modipick_at_high_load():
    """Acceptance: at high arrival rates queue-aware ModiPick wins on
    SLA attainment (the queue-blind paper policy keeps feeding
    saturated endpoints)."""
    def run(qa):
        return engine(seed=7, queue_aware=qa).run(
            ModiPick(t_threshold=20.0), 250.0, 1000,
            arrivals=PoissonArrivals(40.0))
    plain, qa = run(False), run(True)
    assert qa.sla_attainment > plain.sla_attainment + 0.3
    assert qa.mean_queue_wait < plain.mean_queue_wait
    # the win is a *selection* effect, not a traffic drop
    assert qa.n_completed == plain.n_completed == 1000


def test_static_greedy_collapses_under_load_too():
    r = engine(seed=8).run(StaticGreedy(250.0), 250.0, 600,
                           arrivals=PoissonArrivals(40.0))
    assert r.sla_attainment < 0.5  # one endpoint takes all the traffic


@pytest.mark.slow
def test_paper_scale_closed_loop_10k():
    """Paper-scale 10k-request closed loop (opt-in: ``-m slow``)."""
    r = Simulator(entries=TABLE2, network=NET, seed=1).run(
        ModiPick(t_threshold=20.0), 250.0, 10_000)
    assert r.n == 10_000
    assert r.sla_attainment > 0.9
