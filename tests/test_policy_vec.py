"""Vectorized policy engine: scalar↔batched equivalence (Hypothesis
property sweeps), ProfileTable snapshot semantics, the seeded
end-to-end goldens pinning the ProfileTable rewire, StaticGreedy
re-freeze, the rejected-inclusive utilization horizon, and the
benchmark-harness smoke."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import policy_vec
from repro.core.netmodel import NetworkModel
from repro.core.policy import (DynamicGreedy, ModiPick, PureRandom,
                               RelatedAccurate, RelatedRandom, StaticGreedy)
from repro.core.profiles import ModelProfile, ProfileStore, ProfileTable
from repro.core.simulate import Simulator
from repro.core.zoo import TABLE2, make_store, true_profiles
from repro.sim import (PoissonArrivals, ServingSimulator, SimRequest,
                       per_model_replicas, shared_replicas)

REPO = Path(__file__).resolve().parent.parent
NET = NetworkModel(50.0, 25.0)


def store_from(specs, alpha=0.1):
    profiles = []
    for i, (acc, mu, sigma) in enumerate(specs):
        p = ModelProfile(name=f"m{i}", accuracy=acc)
        p.mu, p.var, p.n_obs = mu, sigma ** 2, 100
        profiles.append(p)
    return ProfileStore(profiles, alpha=alpha)


pool_strategy = st.lists(
    st.tuples(st.floats(0.05, 1.0),      # accuracy
              st.floats(1.0, 200.0),     # mu
              st.floats(0.0, 20.0)),     # sigma
    min_size=1, max_size=12)

budgets_strategy = st.lists(st.floats(-20.0, 500.0), min_size=1, max_size=32)


# ----------------------------------------------------------------------
# ProfileTable snapshot semantics
# ----------------------------------------------------------------------

def test_table_cached_and_patched_in_place_on_observation():
    store = store_from([(0.9, 50, 1), (0.5, 5, 1)])
    t1 = store.table()
    assert store.table() is t1          # cached, no per-call rebuild
    store.observe("m1", 7.0)            # telemetry patches in place
    t2 = store.table()
    assert t2 is t1                     # no snapshot churn per observe
    assert t2.mu[1] == store["m1"].mu
    assert t2.sigma[1] == store["m1"].sigma
    store.observe_queue("m0", 3.0)
    assert store.table() is t1
    assert t1.queue_mu[0] == store["m0"].queue_mu
    # the patched snapshot equals a from-scratch rebuild, field for field
    fresh = ProfileTable.from_store(store)
    np.testing.assert_array_equal(t1.mu, fresh.mu)
    np.testing.assert_array_equal(t1.sigma, fresh.sigma)
    np.testing.assert_array_equal(t1.queue_mu, fresh.queue_mu)
    assert t1.fastest == fresh.fastest
    np.testing.assert_array_equal(t1.acc_order, fresh.acc_order)
    # explicit invalidation (direct profile mutation) still rebuilds
    store.invalidate()
    assert store.table() is not t1


def test_table_order_matches_scalar_sort():
    store = store_from([(0.5, 9, 0), (0.9, 5, 0), (0.5, 3, 0), (0.7, 1, 0)])
    tab = store.table()
    expect = [p.name for p in sorted(store.profiles.values(),
                                     key=lambda p: -p.accuracy)]
    assert [tab.names[i] for i in tab.acc_order] == expect  # stable ties
    assert tab.names[tab.fastest] == "m3"


def test_shifted_table_reuses_order_and_moves_mu():
    store = store_from([(0.9, 50, 2), (0.5, 5, 1)])
    tab = store.table()
    sh = tab.shifted(np.array([100.0, 0.0]))
    assert sh.acc_order is tab.acc_order
    assert sh.mu[0] == 150.0 and sh.mu[1] == 5.0
    assert np.all(sh.sigma == tab.sigma)
    assert sh.fastest == 1


# ----------------------------------------------------------------------
# scalar ↔ batched equivalence
# ----------------------------------------------------------------------

@given(pool_strategy, budgets_strategy, st.floats(0.0, 50.0),
       st.integers(0, 2**31 - 1))
@settings(max_examples=150, deadline=None)
def test_deterministic_policies_bit_identical(pool, budgets, threshold, seed):
    store = store_from(pool)
    budgets = np.asarray(budgets)
    for policy in (DynamicGreedy(), RelatedAccurate(threshold),
                   StaticGreedy(t_sla=float(budgets[0]) + threshold)):
        batched = policy.select_batch(store, budgets,
                                      np.random.default_rng(seed),
                                      backend="numpy")
        scalar = [policy.select(store, float(b), np.random.default_rng(seed))
                  for b in budgets]
        assert batched == scalar, policy.name


@given(pool_strategy, budgets_strategy, st.floats(0.0, 50.0),
       st.integers(0, 2**31 - 1))
@settings(max_examples=150, deadline=None)
def test_modipick_probability_vectors_match_scalar(pool, budgets, threshold,
                                                   seed):
    store = store_from(pool)
    tab = store.table()
    budgets = np.asarray(budgets)
    policy = ModiPick(t_threshold=threshold)
    t_u, t_l = budgets, budgets - threshold
    base, has_base, elig, _ = policy_vec.modipick_masks(tab, t_u, t_l)
    probs = policy_vec.modipick_probs(tab, t_u, t_l, elig, policy.gamma)
    for b, tb in enumerate(budgets):
        trace = policy.select_traced(store, float(tb),
                                     np.random.default_rng(seed))
        if trace.fallback:
            assert not has_base[b]
            assert probs[b].sum() == 0.0
            continue
        assert has_base[b]
        assert tab.names[base[b]] == trace.base
        scalar = dict(zip(trace.eligible, trace.probs))
        for j, name in enumerate(tab.names):
            assert abs(probs[b, j] - scalar.get(name, 0.0)) < 1e-9


@given(pool_strategy, budgets_strategy, st.floats(0.0, 50.0),
       st.integers(0, 2**31 - 1))
@settings(max_examples=50, deadline=None)
def test_batched_picks_always_valid(pool, budgets, threshold, seed):
    """Every batched pick is a pool member, and infeasible rows fall back
    to the fastest model exactly like the scalar path."""
    store = store_from(pool)
    tab = store.table()
    rng = np.random.default_rng(seed)
    budgets = np.asarray(budgets)
    for policy in (ModiPick(threshold), RelatedRandom(threshold),
                   PureRandom()):
        names = policy.select_batch(store, budgets, rng, backend="numpy")
        assert len(names) == len(budgets)
        assert set(names) <= set(tab.names)
    mp = ModiPick(threshold)
    names = mp.select_batch(store, budgets, rng, backend="numpy")
    for b, tb in enumerate(budgets):
        if mp.select_traced(store, float(tb),
                            np.random.default_rng(0)).fallback:
            assert names[b] == tab.names[tab.fastest]


def test_modipick_batch_frequencies_match_probs():
    """Gumbel-top-1 sampling draws from the same law as the scalar
    rng.choice loop: empirical frequencies at a fixed budget converge to
    the scalar probability vector."""
    store = make_store(TABLE2)
    mp = ModiPick(t_threshold=20.0)
    trace = mp.select_traced(store, 180.0, np.random.default_rng(0))
    B = 100_000
    names = mp.select_batch(store, np.full(B, 180.0),
                            np.random.default_rng(3), backend="numpy")
    for name, p in zip(trace.eligible, trace.probs):
        assert abs(names.count(name) / B - p) < 0.01


def test_backend_env_override_and_validation(monkeypatch):
    store = make_store(TABLE2)
    budgets = np.full(8, 200.0)
    monkeypatch.setenv("REPRO_POLICY_BACKEND", "numpy")
    assert len(ModiPick(20.0).select_batch(
        store, budgets, np.random.default_rng(0))) == 8
    monkeypatch.setenv("REPRO_POLICY_BACKEND", "bogus")
    with pytest.raises(ValueError):
        ModiPick(20.0).select_batch(store, budgets, np.random.default_rng(0))


def test_jax_backend_matches_numpy_distribution():
    """The jitted/Pallas stage 3 produces the same probability rows as
    the numpy reference (float32 tolerance) and valid picks."""
    from repro.kernels import ops
    store = make_store(TABLE2)
    tab = store.table()
    rng = np.random.default_rng(5)
    budgets = rng.uniform(5.0, 350.0, size=257)  # odd size exercises padding
    t_u, t_l = budgets, budgets - 20.0
    _, has_base, elig, _ = policy_vec.modipick_masks(tab, t_u, t_l)
    expect = policy_vec.modipick_probs(tab, t_u, t_l, elig, 1.0)
    got = np.asarray(ops.modipick_probs(tab.mu, tab.sigma, tab.accuracy,
                                        t_u, t_l, elig, gamma=1.0))
    np.testing.assert_allclose(got, expect, rtol=1e-4, atol=1e-5)
    names = ModiPick(20.0).select_batch(store, budgets,
                                        np.random.default_rng(0),
                                        backend="jax")
    assert set(names) <= set(tab.names)
    for b in np.flatnonzero(~has_base):
        assert names[b] == tab.names[tab.fastest]


# ----------------------------------------------------------------------
# seeded end-to-end goldens: the ProfileTable rewire changed nothing
# ----------------------------------------------------------------------

def test_golden_closed_loop_unchanged():
    r = Simulator(entries=TABLE2, network=NET, seed=1).run(
        ModiPick(t_threshold=20.0), 200.0, 800)
    assert r.sla_attainment == 0.9775
    assert r.mean_accuracy == 0.7813437499999999
    assert r.mean_latency == 164.8560532103827
    assert r.p99_latency == 211.51074909935923


def test_golden_queue_aware_open_loop_unchanged():
    eng = ServingSimulator(TABLE2, NET, per_model_replicas(TABLE2), seed=3,
                           queue_aware=True)
    r = eng.run(ModiPick(t_threshold=20.0), 250.0, 600,
                arrivals=PoissonArrivals(30.0))
    assert (r.n_arrived, r.n_completed, r.n_rejected) == (600, 600, 0)
    assert r.sla_attainment == 0.9983333333333333
    assert r.mean_accuracy == 0.7975266666666666
    assert r.mean_latency == 191.67831081440173
    assert r.mean_queue_wait == 23.493148434870164


def test_golden_shedding_run_unchanged():
    eng = ServingSimulator(TABLE2, NET,
                           per_model_replicas(TABLE2, max_queue_depth=2),
                           seed=5)
    r = eng.run(DynamicGreedy(), 250.0, 500, arrivals=PoissonArrivals(60.0))
    assert (r.n_arrived, r.n_completed, r.n_rejected) == (500, 179, 321)
    assert r.sla_attainment == 0.178
    assert r.mean_accuracy == 0.8064134078212288
    assert r.mean_latency == 255.1617447042085
    assert r.p99_latency == 342.641615613392
    assert r.mean_queue_wait == 47.55524286454602


# ----------------------------------------------------------------------
# StaticGreedy freeze semantics
# ----------------------------------------------------------------------

def test_static_greedy_refreezes_per_store():
    """Regression: one StaticGreedy instance reused across sweep points
    must freeze against each point's store, not leak the first pick."""
    rng = np.random.default_rng(0)
    pol = StaticGreedy(t_sla=60.0)
    a = store_from([(0.9, 50, 1), (0.5, 5, 1)])
    assert pol.select(a, 10.0, rng) == "m0"
    # within one store the pick stays frozen through drift...
    a.profiles["m0"].mu = 500.0
    a.invalidate()
    assert pol.select(a, 10.0, rng) == "m0"
    # ...but a different store (a new sweep point) re-freezes.
    b = store_from([(0.9, 500, 1), (0.5, 5, 1)])  # m0 too slow here
    assert pol.select(b, 10.0, rng) == "m1"


def test_static_greedy_reset():
    rng = np.random.default_rng(0)
    store = store_from([(0.9, 50, 1), (0.5, 5, 1)])
    pol = StaticGreedy(t_sla=60.0)
    assert pol.select(store, 10.0, rng) == "m0"
    store.profiles["m0"].mu = 500.0
    store.invalidate()
    assert pol.select(store, 10.0, rng) == "m0"  # still frozen
    pol.reset()
    assert pol.select(store, 10.0, rng) == "m1"  # re-frozen post-drift


def test_static_greedy_stays_frozen_under_queue_aware_views():
    """Queue-aware wrapping builds a fresh shifted view per selection;
    the view's ``base`` points back at the real store, so the frozen
    pick must not thaw once W_queue telemetry arrives."""
    from repro.sim import QueueAwareSelector, shifted_store
    store = store_from([(0.9, 50, 1), (0.5, 5, 1)])
    rng = np.random.default_rng(0)
    pol = StaticGreedy(t_sla=60.0)
    sel = QueueAwareSelector(pol)
    assert sel.select(store, 100.0, lambda m: 0.0, rng) == "m0"
    # heavy backlog in front of m0: a shifted view per call, every call
    waits = {"m0": 500.0, "m1": 0.0}
    for _ in range(3):
        assert sel.select(store, 100.0, lambda m: waits[m], rng) == "m0"
    view = shifted_store(store, lambda m: waits[m])
    assert view.base is store


def test_static_greedy_batch_on_bare_table_honours_frozen_pick():
    store = store_from([(0.9, 50, 1), (0.5, 5, 1)])
    pol = StaticGreedy(t_sla=60.0)
    assert pol.select(store, 10.0, np.random.default_rng(0)) == "m0"
    store.profiles["m0"].mu = 500.0  # drift after freeze
    store.invalidate()
    batched = pol.select_batch(store.table(), np.full(4, 10.0),
                               np.random.default_rng(0), backend="numpy")
    assert batched == ["m0"] * 4  # matches what 4 scalar calls return


def test_select_batch_unknown_subclass_falls_back_to_scalar():
    class SharpModiPick(ModiPick):
        """Subclass overriding stage 3 — must not ride ModiPick's batch."""
        def _probs_indices(self, tab, idxs, t_u, t_l):
            p = np.zeros(len(idxs))
            p[int(np.argmax(tab.accuracy[idxs]))] = 1.0
            return p

    store = store_from([(0.9, 50, 1), (0.5, 5, 1), (0.7, 20, 1)])
    budgets = np.full(16, 100.0)
    pol = SharpModiPick(t_threshold=50.0)
    batched = pol.select_batch(store, budgets, np.random.default_rng(0),
                               backend="numpy")
    scalar = [pol.select(store, 100.0, np.random.default_rng(0))
              for _ in budgets]
    assert batched == scalar  # scalar fallback, not Gumbel sampling
    with pytest.raises(TypeError):
        pol.select_batch(store.table(), budgets, np.random.default_rng(0),
                         backend="numpy")


def test_static_greedy_reuse_across_rate_sweep_points():
    from repro.sim.engine import rate_sweep
    sim = ServingSimulator(TABLE2, NET, per_model_replicas(TABLE2), seed=2)
    shared = StaticGreedy(250.0)
    reused = rate_sweep(sim, lambda: shared, (5.0, 20.0), 250.0,
                        n_requests=150)
    sim2 = ServingSimulator(TABLE2, NET, per_model_replicas(TABLE2), seed=2)
    fresh = rate_sweep(sim2, lambda: StaticGreedy(250.0), (5.0, 20.0), 250.0,
                       n_requests=150)
    for a, b in zip(reused, fresh):
        assert a.model_usage == b.model_usage
        assert a.sla_attainment == b.sla_attainment


# ----------------------------------------------------------------------
# utilization horizon includes rejected requests
# ----------------------------------------------------------------------

def _req(rid, arrival, depart, model="SqueezeNet", service=0.0,
         rejected=False):
    r = SimRequest(rid=rid, arrival_ms=arrival, model=model,
                   service_ms=service, rejected=rejected)
    r.depart_ms = depart
    return r


def test_summarise_horizon_spans_rejected_requests():
    sim = ServingSimulator(TABLE2, NET, shared_replicas(1), seed=0)
    sim.pool.replicas[0].busy_ms = 50.0
    truth = true_profiles(TABLE2)
    completed = [_req(0, 0.0, 100.0, service=50.0)]
    late_reject = _req(1, 900.0, 1000.0, rejected=True)
    with_rej = sim._summarise("p", 250.0, truth, completed, [late_reject])
    assert with_rej.horizon_ms == pytest.approx(1000.0)
    assert with_rej.replica_utilization["r0"] == pytest.approx(50.0 / 1000.0)
    # without the rejected tail the horizon would have been 100ms and
    # utilization inflated 10x:
    without = sim._summarise("p", 250.0, truth, completed, [])
    assert without.horizon_ms == pytest.approx(100.0)
    assert without.replica_utilization["r0"] == pytest.approx(0.5)


# ----------------------------------------------------------------------
# bench harness smoke: the throughput benchmark cannot silently rot
# ----------------------------------------------------------------------

def test_policy_throughput_smoke(tmp_path):
    """Fast invocation of ``benchmarks/run.py policy_throughput`` — runs
    the harness end-to-end (CSV + --json record) at small batches."""
    env = dict(os.environ,
               PYTHONPATH=f"{REPO / 'src'}{os.pathsep}{REPO}")
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--only",
         "policy_throughput", "--fast", "--json", "--fail-fast"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=570)
    assert out.returncode == 0, out.stderr
    assert "policy_throughput/scalar/batch_1," in out.stdout
    assert "policy_throughput/numpy/batch_1000," in out.stdout
    data = json.loads((tmp_path / "BENCH_policy_throughput.json").read_text())
    assert data["benchmark"] == "policy_throughput"
    assert any(r["name"].startswith("policy_throughput/numpy/")
               for r in data["rows"])


@pytest.mark.slow
def test_policy_throughput_vectorized_speedup():
    """Acceptance: ≥10× selections/sec over the scalar loop at batch ≥10k
    on the Table-2 zoo (the 100k point is the recorded trajectory)."""
    from benchmarks.policy_throughput import bench_rows
    rows = {name: derived for name, _, derived in
            bench_rows(batches=(100_000,))}
    derived = rows["policy_throughput/numpy/batch_100000"]
    speedup = float(dict(kv.split("=") for kv in derived.split(";"))
                    ["speedup"].rstrip("x"))
    assert speedup >= 10.0, derived
