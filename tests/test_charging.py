"""Intra-batch load charging (the batched-routing staleness fix).

Covers, in order:

- the oracle property: a charged ``route_batch`` over a batch of B is
  pick-for-pick equal to B sequential singleton ``route`` calls with
  the queue waits updated between calls — the singleton path is the
  trusted scalar oracle, so the charged batch inherits its semantics;
- honest admission under bursts: the regression the bench exposed
  (``shed=0`` while attainment sat at 0.16) — ``SlaAwareAdmission``
  judged against charged waits sheds what cannot be served, and the
  engine's attainment recovers;
- the array-native ``route_batch_arrays`` column contract;
- the ``lax.scan`` charged kernel (forced jax backend) against the
  numpy sequential loop on a deterministic single-model pool, plus
  multi-model sanity;
- the Pallas charged kernel (interpret mode) against the ``lax.scan``
  on the zoo's shape, and the router's kernel counter.
"""
import numpy as np
import pytest

from repro.core.netmodel import NetworkModel
from repro.core.policy import ModiPick
from repro.core.profiles import ModelProfile, ProfileStore
from repro.core.zoo import TABLE2
from repro.router import (ChargedWaits, InferenceRequest, Router,
                          SlaAwareAdmission)
from repro.router.api import BatchDecisions
from repro.sim import ServingSimulator, TraceArrivals, per_model_replicas


def _random_store(rng, n):
    ps = []
    for i in range(n):
        p = ModelProfile(name=f"m{i}", accuracy=float(rng.uniform(0.05, 1.0)))
        p.mu = float(rng.uniform(5, 120))
        p.var = float(rng.uniform(0, 10)) ** 2
        p.n_obs = 50
        ps.append(p)
    return ProfileStore(ps)


def _burst(n, width, every_ms):
    bursts = -(-n // width)
    return TraceArrivals(np.repeat(np.arange(bursts) * every_ms, width)[:n])


# ----------------------------------------------------------------------
# the oracle property: charged batch == sequential singletons
# ----------------------------------------------------------------------

def test_charged_batch_equals_sequential_singleton_oracle():
    """Charged ``route_batch`` over B requests must be pick-for-pick
    (and shed-for-shed, and reported-wait-for-reported-wait) what B
    singleton ``route`` calls produce when the caller charges the queue
    waits between calls — over randomized pools, budgets, initial
    waits, and with/without SLA-aware admission."""
    meta = np.random.default_rng(99)
    for trial in range(25):
        n = int(meta.integers(2, 9))
        B = int(meta.integers(2, 17))
        seed = int(meta.integers(1 << 30))
        store_a = _random_store(np.random.default_rng(seed), n)
        store_b = _random_store(np.random.default_rng(seed), n)
        adm = SlaAwareAdmission() if trial % 2 else None
        policy = ModiPick(t_threshold=float(meta.uniform(0, 40)))
        kw = dict(admission=adm, queue_aware=True)
        router_a = Router(store_a, policy, **kw)
        router_b = Router(store_b, policy, **kw)
        waits0 = {f"m{i}": float(meta.uniform(0, 60)) for i in range(n)}
        reqs = [InferenceRequest(t_sla_ms=float(meta.uniform(40, 400)),
                                 t_input_ms=float(meta.uniform(0, 30)),
                                 rid=i)
                for i in range(B)]

        rng_a = np.random.default_rng(seed + 1)
        decs = router_a.route_batch(reqs, rng_a, w_queue_map=dict(waits0),
                                    charge=True)

        # The trusted oracle: singleton routes with the wait map charged
        # by the caller after every admitted pick (model-granularity
        # queues, μ from the table — exactly what per-model charging
        # models).
        rng_b = np.random.default_rng(seed + 1)
        tab = store_b.table()
        mu_of = dict(zip(tab.names, (float(m) for m in tab.mu)))
        waits = {k: max(0.0, v) for k, v in waits0.items()}
        for req, dec in zip(reqs, decs):
            ora = router_b.route(req, rng_b, w_queue_fn=waits.__getitem__)
            assert ora.admitted == dec.admitted, (trial, req.rid)
            assert ora.budget.w_queue_ms == dec.budget.w_queue_ms
            if not ora.admitted:
                assert ora.reject_reason == dec.reject_reason
                continue
            assert ora.variant == dec.variant, (trial, req.rid)
            assert ora.fallback == dec.fallback
            waits[ora.variant] += mu_of[ora.variant]
        # identical residual RNG state: same number and kind of draws
        assert rng_a.random() == rng_b.random()


def test_charge_false_keeps_one_snapshot_semantics():
    """``charge=False`` (the object-path default) must keep the
    historical contract: every request judged against the same frozen
    snapshot, batched vectorized selection."""
    store = _random_store(np.random.default_rng(5), 6)
    router = Router(store, ModiPick(t_threshold=20.0), queue_aware=True)
    reqs = [InferenceRequest(t_sla_ms=300.0, t_input_ms=10.0, rid=i)
            for i in range(8)]
    waits = {f"m{i}": 5.0 * i for i in range(6)}
    decs = router.route_batch(reqs, np.random.default_rng(0),
                              w_queue_map=waits)
    # all decisions report the wait of their chosen model from the ONE
    # snapshot — no charges appear anywhere
    for d in decs:
        assert d.admitted
        assert d.budget.w_queue_ms == waits[d.variant]


# ----------------------------------------------------------------------
# honest admission under bursts (the bench regression)
# ----------------------------------------------------------------------

def test_admission_sheds_honestly_under_burst():
    """The regression the throughput bench exposed: under 400-wide
    bursts on the per-model topology, snapshot routing reports shed=0
    while attainment collapses (every request is judged against the
    same idle-looking pool); charged routing both sheds the requests no
    model can serve in budget AND recovers attainment for the rest."""
    def run(charge):
        sim = ServingSimulator(
            TABLE2, NetworkModel(50.0, 0.0), per_model_replicas(TABLE2),
            seed=3, queue_aware=True, admission=SlaAwareAdmission(),
            charge_batches=charge)
        r = sim.run(ModiPick(t_threshold=20.0), 250.0, 800,
                    arrivals=_burst(800, 400, 2000.0))
        return r

    snap = run(False)
    assert snap.n_rejected == 0          # blind to intra-batch load
    assert snap.sla_attainment < 0.1     # ... and it collapses
    charged = run(True)
    assert charged.n_rejected > 0        # shedding is honest now
    assert charged.sla_attainment > 0.4
    assert charged.sla_attainment > 10 * snap.sla_attainment


def test_burst_attainment_recovers_without_admission():
    """At sustainable burst load (4 replicas/model, 200-wide bursts —
    the bench's ``batched`` config at toy scale) charging alone
    recovers attainment to the singleton regime; the snapshot ablation
    stays degenerate."""
    def run(charge):
        sim = ServingSimulator(
            TABLE2, NetworkModel(50.0, 0.0),
            per_model_replicas(TABLE2, replicas_per_model=4),
            seed=3, queue_aware=True, charge_batches=charge)
        return sim.run(ModiPick(t_threshold=20.0), 250.0, 2000,
                       arrivals=_burst(2000, 200, 400.0))

    assert run(False).sla_attainment < 0.3
    assert run(True).sla_attainment > 0.9


# ----------------------------------------------------------------------
# the array-native entry point
# ----------------------------------------------------------------------

def test_route_batch_arrays_column_contract():
    """Columns out of ``route_batch_arrays`` mirror the object path's
    decisions field for field (same RNG seed → same picks)."""
    store = _random_store(np.random.default_rng(11), 5)
    mk = lambda: Router(store, ModiPick(t_threshold=20.0),
                        admission=SlaAwareAdmission(), queue_aware=True)
    reqs = [InferenceRequest(t_sla_ms=float(s), t_input_ms=5.0, rid=i)
            for i, s in enumerate((300.0, 90.0, 250.0, 30.0))]
    waits = {f"m{i}": 12.5 * i for i in range(5)}
    decs = mk().route_batch(reqs, np.random.default_rng(7),
                            w_queue_map=dict(waits), charge=True)
    res = mk().route_batch_arrays(
        [r.t_sla_ms for r in reqs], [r.t_input_ms for r in reqs],
        np.random.default_rng(7), w_queue_map=dict(waits), charge=True)
    assert isinstance(res, BatchDecisions)
    assert len(res) == len(reqs)
    for i, d in enumerate(decs):
        assert bool(res.admitted[i]) == d.admitted
        if d.admitted:
            assert res.names[int(res.model_idx[i])] == d.variant
            assert bool(res.fallback[i]) == d.fallback
        else:
            assert int(res.model_idx[i]) == -1
            assert res.reason_of(i) == d.reject_reason
        assert float(res.w_queue_ms[i]) == d.budget.w_queue_ms
        # per-model pseudo charging exposes no real replica indices
        assert int(res.replica_idx[i]) == -1


def test_batch_of_one_is_bit_identical_scalar_path():
    """Charging must not perturb a singleton batch: same picks and RNG
    consumption as ``route`` whatever the ``charge`` flag says (there
    is nothing within the batch to charge against)."""
    store_a = _random_store(np.random.default_rng(3), 6)
    store_b = _random_store(np.random.default_rng(3), 6)
    pol = ModiPick(t_threshold=20.0)
    req = InferenceRequest(t_sla_ms=240.0, t_input_ms=20.0)
    waits = {f"m{i}": 3.0 * i for i in range(6)}
    ra, rb = np.random.default_rng(2), np.random.default_rng(2)
    d1 = Router(store_a, pol, queue_aware=True).route_batch(
        [req], ra, w_queue_map=waits, charge=True)[0]
    d2 = Router(store_b, pol, queue_aware=True).route(
        req, rb, w_queue_fn=waits.__getitem__)
    assert (d1.variant, d1.fallback) == (d2.variant, d2.fallback)
    assert d1.budget.w_queue_ms == d2.budget.w_queue_ms
    assert ra.random() == rb.random()


def test_route_one_matches_batch_of_one():
    """The engine's scalar fast path (``route_one`` tuple out) is
    pick-for-pick, float-for-float, draw-for-draw and counter-for-
    counter the same as a batch of one through the array entry point."""
    store_a = _random_store(np.random.default_rng(8), 5)
    store_b = _random_store(np.random.default_rng(8), 5)
    pol = ModiPick(t_threshold=20.0)
    router_a = Router(store_a, pol, admission=SlaAwareAdmission(),
                      queue_aware=True)
    router_b = Router(store_b, pol, admission=SlaAwareAdmission(),
                      queue_aware=True)
    ra, rb = np.random.default_rng(4), np.random.default_rng(4)
    waits = {f"m{i}": 4.0 * i for i in range(5)}
    for k in range(12):
        sla = 360.0 - 31.0 * k          # last rows: budget ≤ 0 → shed
        mid, fb, w_q, reason = router_a.route_one(
            sla, 10.0, ra, w_queue_map=waits)
        res = router_b.route_batch_arrays(
            [sla], [10.0], rb, w_queue_map=dict(waits))
        assert mid == int(res.model_idx[0])
        assert bool(res.admitted[0]) == (mid >= 0)
        if mid >= 0:
            assert fb == bool(res.fallback[0])
        else:
            assert reason == res.reason_of(0)
        assert w_q == float(res.w_queue_ms[0])
    assert router_a.stats() == router_b.stats()
    assert router_a.stats()["n_shed"] > 0
    assert ra.random() == rb.random()


def test_charged_waits_ledger():
    """ChargedWaits unit semantics: min-over-candidates waits,
    pool-order tie-break, μ/speed charge amounts."""
    st = ChargedWaits(rep_wait=[10.0, 0.0, 5.0],
                      cand=[[0, 1], [1, 2]],
                      speed=[1.0, 2.0, 1.0],
                      mu=[30.0, 8.0],
                      names=("a", "b"))
    assert st.model_waits().tolist() == [0.0, 0.0]
    assert st.charge(0) == 1             # least-loaded of {0, 1}
    assert st.rep_wait[1] == 15.0        # 30 / speed 2
    assert st.wait_of(0) == 10.0
    assert st.as_map() == {"a": 10.0, "b": 5.0}
    assert st.charge(1) == 2             # replica 2 now least of {1, 2}
    assert st.rep_wait[2] == 13.0
    with pytest.raises(ValueError, match="no replica serves"):
        ChargedWaits([0.0], [[]], [1.0], [1.0], ("a",))


# ----------------------------------------------------------------------
# the jax lax.scan charged kernel
# ----------------------------------------------------------------------

def _one_model_store(mu=50.0):
    p = ModelProfile(name="m0", accuracy=0.9)
    p.mu, p.var, p.n_obs = mu, 0.0, 100
    return ProfileStore([p])


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_charged_scan_deterministic_single_model(backend):
    """One model, two replicas, fixed budgets: the charged pass (numpy
    sequential loop AND the forced-jax ``lax.scan`` kernel) must admit
    exactly while ``min-replica wait < budget`` and alternate replicas
    — a closed-form trajectory with no sampling freedom, so both
    backends are exactly comparable."""
    store = _one_model_store(50.0)
    router = Router(store, ModiPick(t_threshold=20.0),
                    admission=SlaAwareAdmission(), queue_aware=True,
                    trace_detail=False, backend=backend)
    state = ChargedWaits(rep_wait=[0.0, 0.0], cand=[[0, 1]],
                         speed=[1.0, 1.0], mu=[50.0], names=("m0",))
    B = 12
    res = router.route_batch_arrays(
        np.full(B, 200.0), np.zeros(B), np.random.default_rng(0),
        charged=state, charge=True)
    # admits while min(waits) < 200: pairs of picks raise the min by 50
    # → 8 admitted (min wait 0,0,50,50,100,100,150,150), then shed.
    assert res.admitted.tolist() == [True] * 8 + [False] * 4
    assert res.model_idx[:8].tolist() == [0] * 8
    assert res.replica_idx[:8].tolist() == [0, 1] * 4
    assert res.w_queue_ms[:8].tolist() == [0.0, 0.0, 50.0, 50.0,
                                           100.0, 100.0, 150.0, 150.0]
    assert res.w_queue_ms[8:].tolist() == [200.0] * 4
    assert all("budget" in res.reason_of(i) for i in range(8, 12))
    s = router.stats()
    assert s["n_admitted"] == 8 and s["n_shed"] == 4


def test_charged_scan_multimodel_spreads_and_places():
    """Forced-jax charged scan over a real zoo: picks are valid pool
    indices, every admitted request lands on a replica that serves its
    model, and the burst spreads over more than one model (the whole
    point of charging)."""
    from repro.core.zoo import make_store
    store = make_store(TABLE2)
    router = Router(store, ModiPick(t_threshold=20.0), queue_aware=True,
                    trace_detail=False, backend="jax")
    tab = store.table()
    n = len(tab.names)
    # per-model topology, 2 replicas each: replica 2*m and 2*m+1 serve m
    state = ChargedWaits(rep_wait=[0.0] * (2 * n),
                         cand=[[2 * m, 2 * m + 1] for m in range(n)],
                         speed=[1.0] * (2 * n),
                         mu=tab.mu, names=tab.names)
    B = 256
    res = router.route_batch_arrays(
        np.full(B, 250.0), np.full(B, 50.0), np.random.default_rng(1),
        charged=state, charge=True)
    assert res.admitted.all()
    picks = res.model_idx
    assert ((0 <= picks) & (picks < n)).all()
    assert len(np.unique(picks)) > 1
    reps = res.replica_idx
    assert ((reps == 2 * picks) | (reps == 2 * picks + 1)).all()
    # the ledger really was charged: total charged mass == Σ μ(pick)
    expect = sum(float(tab.mu[m]) for m in picks)
    assert np.sum(state.rep_wait) == 0.0  # jax path never mutates state
    # and the same call on numpy charges the caller's ledger in place
    router_np = Router(store, ModiPick(t_threshold=20.0), queue_aware=True,
                       trace_detail=False, backend="numpy")
    state2 = ChargedWaits(rep_wait=[0.0] * (2 * n),
                          cand=[[2 * m, 2 * m + 1] for m in range(n)],
                          speed=[1.0] * (2 * n),
                          mu=tab.mu, names=tab.names)
    res2 = router_np.route_batch_arrays(
        np.full(B, 250.0), np.full(B, 50.0), np.random.default_rng(1),
        charged=state2, charge=True)
    got = float(np.sum(state2.rep_wait))
    want = sum(float(tab.mu[m]) for m in res2.model_idx)
    assert got == pytest.approx(want, rel=1e-12)


# ----------------------------------------------------------------------
# the Pallas charged kernel (interpret mode here) against the lax.scan
# ----------------------------------------------------------------------

def _zoo_charged(B, rpm, tied, seed):
    """The paper's 11-model zoo behind ``rpm`` replicas a model, with
    Table 2 budgets: uplinks wide enough that some rows find no base
    model and some are shed.  ``tied`` draws replica waits from a few
    values so that equal waits (the first-index tie-break) are common."""
    from repro.core.zoo import make_store
    tab = make_store(TABLE2).table()
    n = len(tab.names)
    rng = np.random.default_rng(seed)
    R = n * rpm
    waits = (rng.integers(0, 4, R) * 25.0 if tied
             else rng.uniform(0, 100, R))
    state = ChargedWaits(waits, [range(m * rpm, (m + 1) * rpm)
                                 for m in range(n)],
                         rng.choice([1.0, 2.0], R), tab.mu, tab.names)
    budgets = (rng.uniform(150, 600, B)
               - 2 * np.maximum(rng.normal(57.87, 30.78, B), 0.1))
    return tab, state, budgets


@pytest.mark.parametrize("B, admission, include_mu, tied", [
    (4096, "sla", False, False),
    (1200, "sla", True, True),       # padded to 1280: blocks of 256 rows
    (300, "all", False, True),
])
def test_charged_kernel_matches_scan(monkeypatch, B, admission, include_mu,
                                     tied):
    """The Pallas kernel (interpreted) and the ``lax.scan`` give the same
    picks, verdicts, fallbacks and replicas, and the same waits, on the
    zoo's 11 models × 176 replicas: an admit/shed mix (or admit-all),
    rows with no base model, tied replica waits, padded batch rows."""
    from repro.kernels import policy_select
    tab, state, budgets = _zoo_charged(B, 16, tied, seed=B)
    kw = dict(gamma=1.0, seed=7, adm_include_mu=include_mu,
              adm_limit=budgets if admission == "sla" else None)
    args = (tab.device_pool(), budgets, budgets - 20.0, state)
    scan = policy_select.charged_select(*args, **kw)
    monkeypatch.setattr(policy_select, "charged_kernel_engaged",
                        lambda npad, n_replicas: True)
    kernel = policy_select.charged_select(*args, **kw)
    picks, admitted, has_base, rep, w = scan
    for name, want, got in zip(("picks", "admitted", "has_base", "replica"),
                               scan[:4], kernel[:4]):
        np.testing.assert_array_equal(got, want, err_msg=name)
    np.testing.assert_allclose(kernel[4], w, rtol=1e-6)
    # the cases reach what they name
    assert (~has_base).any() and has_base.any()
    assert admitted.all() if admission == "all" else (~admitted).any()
    if tied:
        ties = [np.sum(state.rep_wait[c] == state.rep_wait[c].min()) > 1
                for c in state.cand]
        assert any(ties)


def test_charged_kernel_counter(monkeypatch):
    """Off the TPU the charged tick takes the ``lax.scan``: the kernel
    counter stays 0 while ``n_scan_batches`` counts; a tick through the
    kernel counts in both; both reset."""
    from repro.core.zoo import make_store
    from repro.kernels import policy_select
    tab, state, budgets = _zoo_charged(64, 2, False, seed=3)
    router = Router(make_store(TABLE2), ModiPick(t_threshold=20.0),
                    admission=SlaAwareAdmission(), queue_aware=True,
                    trace_detail=False, backend="jax")

    def tick():
        router.route_batch_arrays(budgets + 100.0, np.full(64, 50.0),
                                  np.random.default_rng(0), charged=state)
    tick()
    tick()
    s = router.stats()
    assert s["n_scan_batches"] == 2 and s["n_charged_kernel_batches"] == 0
    assert router.window_stats()["n_charged_kernel_batches"] == 0
    monkeypatch.setattr(policy_select, "charged_kernel_engaged",
                        lambda npad, n_replicas: True)
    tick()
    win = router.window_stats()
    assert win["n_scan_batches"] == 1 and win["n_charged_kernel_batches"] == 1
    router.reset()
    assert router.stats()["n_scan_batches"] == 0
    assert router.stats()["n_charged_kernel_batches"] == 0


# ----------------------------------------------------------------------
# the charged tick's host side: column write-back, batched bookkeeping,
# the one-transfer pack
# ----------------------------------------------------------------------

_SHED_REASON = "W_queue exceeds the remaining budget for every model"


def _rowwise_write_back(router, res, out, pseudo):
    """The per-row write-back the column version replaced, kept as the
    reference: returns the fallback count it adds."""
    picks, admitted, has_base, replica, w_chosen = out
    names = router.store.table().names
    for i in range(len(picks)):
        if not admitted[i]:
            router._shed(res, i, _SHED_REASON, float(w_chosen[i]))
            continue
        mid = int(picks[i])
        router.store.mark_selected(names[mid])
        res.model_idx[i] = mid
        res.admitted[i] = True
        res.fallback[i] = not has_base[i]
        res.w_queue_ms[i] = float(w_chosen[i])
        if not pseudo:
            res.replica_idx[i] = int(replica[i])
    return int((res.admitted & res.fallback).sum())


def _fixed_columns(case, B, n, R):
    """``charged_select``'s five columns for one write-back case."""
    rng = np.random.default_rng(len(case))
    picks = rng.integers(0, n, B).astype(np.int32)
    picks[:6] = 3                       # one model picked again and again
    admitted = {"mixed": rng.random(B) > 0.2,
                "no_sheds": np.ones(B, bool),
                "none_admitted": np.zeros(B, bool)}[case]
    has_base = rng.random(B) > 0.3
    replica = rng.integers(0, R, B).astype(np.int32)
    w_chosen = rng.uniform(0.0, 80.0, B)
    return picks, admitted, has_base, replica, w_chosen


@pytest.mark.parametrize("pseudo", [False, True], ids=["replicas", "pseudo"])
@pytest.mark.parametrize("case", ["mixed", "no_sheds", "none_admitted"])
def test_charged_write_back_matches_rowwise(monkeypatch, case, pseudo):
    """The column write-back of a charged jax tick leaves the decision
    columns, the reasons, the router's counts and the store's selection
    bookkeeping exactly as the per-row loop did: shed and fallback rows,
    repeated picks of one model, a tick with no sheds (``reasons`` stays
    ``[""]``), a tick with none admitted; real replicas and pseudo
    replicas (where ``replica_idx`` stays −1)."""
    from repro.core.zoo import make_store
    from repro.kernels import policy_select
    B, rpm = 64, 2
    n = len(TABLE2)
    out = _fixed_columns(case, B, n, n * rpm)
    monkeypatch.setattr(policy_select, "charged_select",
                        lambda *a, **k: out)

    def build():
        store = make_store(TABLE2)
        store.mark_selected(store.names()[5])   # a prior selection
        return Router(store, ModiPick(t_threshold=20.0),
                      admission=SlaAwareAdmission(), queue_aware=True,
                      trace_detail=False, backend="jax")

    def state(tab):
        if pseudo:
            return ChargedWaits.per_model(tab.names, np.zeros(n), tab.mu)
        return ChargedWaits(np.zeros(n * rpm),
                            [range(m * rpm, (m + 1) * rpm) for m in range(n)],
                            np.ones(n * rpm), tab.mu, tab.names)

    router = build()
    res = router.route_batch_arrays(np.full(B, 300.0), np.full(B, 20.0),
                                    np.random.default_rng(0),
                                    charged=state(router.store.table()))
    assert router.stats()["n_scan_batches"] == 1

    ref = build()
    want = BatchDecisions.empty(B, ref.store.table().names)
    n_fallback = _rowwise_write_back(ref, want, out, pseudo)

    for col in ("model_idx", "admitted", "fallback", "replica_idx",
                "w_queue_ms", "reject_code"):
        got, exp = getattr(res, col), getattr(want, col)
        assert got.dtype == exp.dtype, col
        np.testing.assert_array_equal(got, exp, err_msg=col)
    assert res.reasons == want.reasons
    if case == "no_sheds":
        assert res.reasons == [""]
    if pseudo or case == "none_admitted":
        assert (res.replica_idx == -1).all()
    s = router.stats()
    n_admitted = int(want.admitted.sum())
    assert (s["n_admitted"], s["n_shed"], s["n_fallback"]) == (
        n_admitted, B - n_admitted, n_fallback)
    assert router.store.step == ref.store.step
    assert ({m: p.last_selected for m, p in router.store.profiles.items()}
            == {m: p.last_selected for m, p in ref.store.profiles.items()})


def _store_state(store):
    tab = store.table()
    return (store.step,
            {m: (p.last_selected, p.mu, p.var, p.n_obs)
             for m, p in store.profiles.items()},
            tab.mu.tolist(), tab.sigma.tolist(), tab.queue_mu.tolist(),
            tab.fastest)


@pytest.mark.parametrize("profile", ["ewma", "window"])
def test_mark_selected_many_matches_repeated_mark_selected(profile):
    """``mark_selected_many`` leaves a store as one ``mark_selected`` a
    pick would: on an empty sequence, and on a long mixed one split into
    chunks with observations between them.  In the windowed store the
    chunks push unpicked models past ``stale_after``, so the presented
    μ and the table move, and must move alike."""
    from repro.core.zoo import make_store
    kw = dict(profile=profile, window=8, stale_after=30)
    batched, looped = make_store(TABLE2, **kw), make_store(TABLE2, **kw)
    names = batched.names()
    for s in (batched, looped):
        s.table()                       # patched in place from here on
    batched.mark_selected_many(np.zeros(0, np.int64))
    assert _store_state(batched) == _store_state(looped)

    rng = np.random.default_rng(4)
    hot = [0, 2, 7]                     # the rest go stale in windowed mode
    for chunk in range(6):
        picks = rng.choice(hot, 25 + 10 * chunk)
        batched.mark_selected_many(picks)
        for m in picks:
            looped.mark_selected(names[m])
        assert _store_state(batched) == _store_state(looped)
        for m in hot:
            lat = float(rng.uniform(5, 100))
            batched.observe(names[m], lat)
            looped.observe(names[m], lat)
    if profile == "window":
        stale = [m for m in names if batched.staleness(m) > 30]
        assert stale
        assert any(batched.profiles[m].mu != batched._raw[m][0]
                   for m in stale)


def _rowwise_cand_mask(npad, R, cand):
    mask = np.zeros((npad, R), dtype=bool)
    for m, c in enumerate(cand):
        mask[m, np.asarray(c)] = True
    return mask


@pytest.mark.parametrize("topology", ["overlapping", "zoo"])
def test_charged_pack_operands(monkeypatch, topology):
    """The vectorised pack builds the per-model loop's ``cand_mask`` (a
    replica serving two models; the zoo's 11 models × 16 replicas), and
    the jitted ``run`` receives the pool columns and one packed buffer
    that unpacks to the operands the pass received as separate arrays:
    same order, shapes, dtypes and values, then ``PRNGKey(seed)``."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import policy_select
    B = 300
    if topology == "zoo":
        tab, state, budgets = _zoo_charged(B, 16, False, seed=5)
    else:
        store = _random_store(np.random.default_rng(6), 4)
        tab = store.table()
        state = ChargedWaits(np.arange(5) * 10.0,
                             [[0, 1], [1, 2], [2, 3, 4], [4, 0]],
                             np.ones(5), tab.mu, tab.names)
        budgets = np.random.default_rng(7).uniform(50, 400, B)
    pool = tab.device_pool()
    R = len(state.rep_wait)
    bpad = policy_select._bucket(B, 256)
    t_l = budgets - 20.0

    host = policy_select._charged_operands(pool.npad, pool.n, bpad,
                                           budgets, t_l, state, budgets)
    np.testing.assert_array_equal(
        host[1], _rowwise_cand_mask(pool.npad, R, state.cand))

    mu_charge = np.zeros(pool.npad, np.float32)
    mu_charge[:pool.n] = np.asarray(state.mu, np.float64)[:pool.n]
    lim = np.full(bpad, -np.inf, np.float32)
    lim[:B] = np.asarray(budgets, np.float32)
    f32 = jnp.float32
    want = (jnp.asarray(mu_charge),
            jnp.asarray(_rowwise_cand_mask(pool.npad, R, state.cand)),
            jnp.asarray(state.speed, f32), jnp.asarray(state.rep_wait, f32),
            jnp.asarray(policy_select._pad_batch(budgets, bpad)),
            jnp.asarray(policy_select._pad_batch(t_l, bpad)),
            jnp.asarray(lim))
    seen = []
    real_jit = policy_select._charged_jit

    def spy(*key):
        fn = real_jit(*key)

        def run(*args, **kw):
            seen.append((args, kw))
            return fn(*args, **kw)
        return run
    monkeypatch.setattr(policy_select, "_charged_jit", spy)
    policy_select.charged_select(pool, budgets, t_l, state, seed=3,
                                 adm_limit=budgets)
    ((args, kw),) = seen
    assert kw == {"n_replicas": R, "bpad": bpad}
    assert len(args) == 5
    for got, col in zip(args, (pool.mu, pool.sigma, pool.acc, pool.rank)):
        assert got is col
    packed = args[4]
    assert isinstance(packed, jax.Array)
    size = pool.npad * (R + 1) + 2 * R + 3 * bpad + 2
    assert (packed.shape, packed.dtype) == ((size,), jnp.uint32)
    *got, key = policy_select._charged_unpack(packed, pool.npad, R, bpad)
    for i, (g, exp) in enumerate(zip(got, want)):
        assert (g.shape, g.dtype, g.weak_type) == (
            exp.shape, exp.dtype, exp.weak_type), i
        np.testing.assert_array_equal(np.asarray(g), np.asarray(exp),
                                      err_msg=str(i))
    np.testing.assert_array_equal(
        np.asarray(key), np.asarray(jax.random.key_data(
            jax.random.PRNGKey(3))))


def test_charged_select_one_transfer(monkeypatch):
    """A charged tick makes no implicit host→device transfer and exactly
    one explicit one, of one array: the packed operands and key words."""
    import jax
    from repro.kernels import policy_select
    tab, state, budgets = _zoo_charged(300, 16, False, seed=5)
    pool = tab.device_pool()
    args = (pool, budgets, budgets - 20.0, state)
    kw = dict(seed=2 ** 63 - 1, adm_limit=budgets)
    policy_select.charged_select(*args, **kw)       # compile outside
    puts = []
    real_put = jax.device_put

    def spy(x, *a, **k):
        puts.append(x)
        return real_put(x, *a, **k)
    monkeypatch.setattr(jax, "device_put", spy)
    with jax.transfer_guard_host_to_device("disallow"):
        out = policy_select.charged_select(*args, **kw)
    (put,) = puts
    assert isinstance(put, np.ndarray) and put.dtype == np.uint32
    assert out[1].any()


@pytest.mark.parametrize("seed", [0, 7, 2 ** 32 + 5, 8765432109876543210,
                                  2 ** 63 - 1, -5])
@pytest.mark.parametrize("x64", [False, True], ids=["x32", "x64"])
def test_charged_key_words(seed, x64):
    """The host-made key words are ``PRNGKey(seed)``'s in either mode."""
    import jax
    from repro.kernels import policy_select
    with jax.enable_x64(x64):
        want = np.asarray(jax.random.key_data(jax.random.PRNGKey(seed)))
        np.testing.assert_array_equal(policy_select._key_words(seed), want)


@pytest.mark.parametrize("kernel", [False, True], ids=["scan", "kernel"])
@pytest.mark.parametrize("B", [4096, 1200])
@pytest.mark.parametrize("seed", [0, 7, 2 ** 32 + 5, 2 ** 63 - 1])
def test_charged_draw_in_run_matches_eager_draw(monkeypatch, seed, B,
                                                kernel):
    """The uniforms drawn inside ``run`` from the packed key words are the
    eager ``uniform(PRNGKey(seed))`` stream: the five columns equal those
    of the same pass fed that eager draw, on the scan and on the
    (interpreted) kernel, at a full tick and at one whose rows are not a
    multiple of the kernel's row block."""
    import functools
    import jax
    import jax.numpy as jnp
    from repro.kernels import policy_select
    tab, state, budgets = _zoo_charged(B, 16, False, seed=B)
    pool = tab.device_pool()
    t_l = budgets - 20.0
    monkeypatch.setattr(policy_select, "charged_kernel_engaged",
                        lambda npad, n_replicas: kernel)
    got = policy_select.charged_select(pool, budgets, t_l, state,
                                       seed=seed, adm_limit=budgets)

    bpad = policy_select._bucket(B, 256)
    host = policy_select._charged_operands(pool.npad, pool.n, bpad,
                                           budgets, t_l, state, budgets)
    r01 = jax.random.uniform(jax.random.PRNGKey(seed), (bpad,),
                             dtype=jnp.float32)
    ref = jax.jit(functools.partial(
        policy_select._charged_pass, gamma=1.0, slack=0.0,
        include_mu=False, fastest=pool.fastest, use_kernel=kernel,
        interpret=kernel))(pool.mu, pool.sigma, pool.acc, pool.rank,
                           *host[:6], r01, host[6])
    for name, g, want in zip(("picks", "admitted", "has_base", "replica",
                              "w_chosen"), got, jax.device_get(ref)):
        np.testing.assert_array_equal(g, np.asarray(want)[:B],
                                      err_msg=name)
