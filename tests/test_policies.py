"""Property tests for the ModiPick selection policies (Hypothesis)."""
import math

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.policy import (DynamicGreedy, ModiPick, PureRandom,
                               RelatedAccurate, RelatedRandom, StaticGreedy,
                               budget)
from repro.core.profiles import ModelProfile, ProfileStore


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


@given(pool_strategy, st.floats(10.0, 500.0), st.floats(0.0, 50.0),
       st.integers(0, 2**31 - 1))
@settings(max_examples=200, deadline=None)
def test_modipick_stage_invariants(pool, t_budget, threshold, seed):
    store = store_from(pool)
    rng = np.random.default_rng(seed)
    policy = ModiPick(t_threshold=threshold)
    trace = policy.select_traced(store, t_budget, rng)
    names = set(store.names())
    assert trace.chosen in names
    t_u, t_l = t_budget, t_budget - threshold
    if trace.fallback:
        # infeasible: fallback must be the fastest model (§3.3.1)
        fastest = min(store.profiles.values(), key=lambda p: p.mu).name
        assert trace.chosen == fastest
    else:
        # stage 1 base satisfies Eq. 2
        bp = store[trace.base]
        assert bp.mu + bp.sigma < t_u and bp.mu - bp.sigma < t_l
        # every eligible model obeys the hard limit (⇒ positive utility)
        for n in trace.eligible:
            p = store[n]
            assert p.mu + p.sigma < t_u
        assert trace.chosen in trace.eligible
        assert trace.base in trace.eligible
        # probabilities normalized
        assert math.isclose(sum(trace.probs), 1.0, rel_tol=1e-9)
        assert all(pr >= 0 for pr in trace.probs)


@given(pool_strategy, st.floats(10.0, 500.0), st.integers(0, 2**31 - 1))
@settings(max_examples=100, deadline=None)
def test_modipick_zero_threshold_zero_sigma_matches_greedy(pool, t_budget, seed):
    """Paper §3.3.1: with T_threshold=0 and tight σ, stage 1 equals the
    dynamic greedy pick — and with a single-member exploration set the
    final choice matches too when the base is strictly fastest-fitting."""
    pool = [(a, mu, 0.0) for a, mu, _ in pool]
    store = store_from(pool)
    rng = np.random.default_rng(seed)
    trace = ModiPick(t_threshold=0.0).select_traced(store, t_budget, rng)
    greedy = DynamicGreedy().select_traced(store, t_budget, rng)
    if trace.fallback:
        # Eq. 2 is strict (<) while Eq. 1 is ≤: at the exact boundary the
        # greedy pick may still fit.  Otherwise both must fall back.
        if not greedy.fallback:
            assert store[greedy.chosen].mu >= t_budget - 1e-9
        return
    # The stage-1 base model must equal the greedy choice (Eq. 2 → Eq. 1) —
    # up to accuracy ties and the strict-vs-≤ boundary.
    if trace.base != greedy.chosen:
        gp, bp = store[greedy.chosen], store[trace.base]
        assert gp.mu >= t_budget - 1e-9 or gp.accuracy == bp.accuracy


@given(pool_strategy, st.floats(10.0, 500.0), st.floats(0.0, 50.0),
       st.integers(0, 2**31 - 1))
@settings(max_examples=100, deadline=None)
def test_exploration_set_policies_share_stages(pool, t_budget, threshold, seed):
    store = store_from(pool)
    rng = np.random.default_rng(seed)
    mp = ModiPick(threshold).select_traced(store, t_budget, rng)
    rr = RelatedRandom(threshold).select_traced(store, t_budget, rng)
    ra = RelatedAccurate(threshold).select_traced(store, t_budget, rng)
    assert mp.fallback == rr.fallback == ra.fallback
    if not mp.fallback:
        assert set(mp.eligible) == set(rr.eligible) == set(ra.eligible)
        accs = [store[n].accuracy for n in ra.eligible]
        assert store[ra.chosen].accuracy == max(accs)


@given(pool_strategy, st.floats(10.0, 500.0), st.integers(0, 2**31 - 1))
@settings(max_examples=200, deadline=None)
def test_dynamic_greedy_never_over_budget(pool, t_budget, seed):
    """§3.2.2 invariant: DynamicGreedy only returns a model with
    μ > T_budget via the explicit fastest-model fallback — and falls
    back only when *no* model fits the budget."""
    store = store_from(pool)
    rng = np.random.default_rng(seed)
    trace = DynamicGreedy().select_traced(store, t_budget, rng)
    if trace.fallback:
        assert all(p.mu > t_budget for p in store.profiles.values())
        fastest = min(store.profiles.values(), key=lambda p: p.mu).name
        assert trace.chosen == fastest
    else:
        assert store[trace.chosen].mu <= t_budget
        # greedy: nothing more accurate also fits
        for p in store.profiles.values():
            if p.accuracy > store[trace.chosen].accuracy:
                assert p.mu > t_budget


def test_static_greedy_frozen():
    store = store_from([(0.9, 50, 1), (0.5, 5, 1)])
    pol = StaticGreedy(t_sla=60.0)
    rng = np.random.default_rng(0)
    first = pol.select(store, 10.0, rng)
    # profiles drift, static greedy must not react
    store.profiles["m0"].mu = 500.0
    assert pol.select(store, 10.0, rng) == first == "m0"


def test_budget_eq1():
    assert budget(200.0, 30.0) == 140.0


@given(st.lists(st.floats(1.0, 100.0), min_size=2, max_size=200),
       st.floats(0.01, 0.5))
@settings(max_examples=100, deadline=None)
def test_ewma_profile_tracks_within_range(samples, alpha):
    p = ModelProfile(name="m", accuracy=0.5)
    for s in samples:
        p.update(s, alpha)
    assert min(samples) - 1e-6 <= p.mu <= max(samples) + 1e-6
    assert p.sigma >= 0.0


def test_cold_model_flagging():
    store = store_from([(0.9, 50, 1), (0.5, 5, 1)], alpha=0.2)
    store.cold_age = 10
    for _ in range(20):
        store.mark_selected("m1")
        store.observe("m1", 5.0)
    assert "m0" in store.cold_models()
    assert "m1" not in store.cold_models()


def test_utility_prefers_accuracy_given_equal_profiles():
    # NasNet-Fictional scenario: identical latency profile, lower accuracy
    # ⇒ strictly lower selection probability, but non-zero (explorable).
    store = store_from([(0.826, 112.61, 0.36), (0.50, 112.61, 0.36),
                        (0.779, 31.11, 0.19)])
    rng = np.random.default_rng(0)
    trace = ModiPick(t_threshold=20.0).select_traced(store, 180.0, rng)
    assert not trace.fallback
    probs = dict(zip(trace.eligible, trace.probs))
    if "m0" in probs and "m1" in probs:
        assert probs["m0"] > probs["m1"] > 0.0
