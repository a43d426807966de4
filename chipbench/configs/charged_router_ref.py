"""Plain reference of the router's charged batch: ModiPick (arXiv:1909.02053,
section 3.3) under SLA-aware admission, routed sequentially against a
per-replica wait ledger that every admitted pick charges.

Per request ``i`` of a tick, against the ledger as requests ``0..i-1``
left it:

1. ``W(m)``: each model's least wait over its candidate replicas.
2. Admission: admitted where some model has ``W(m) + slack (+ mu(m))``
   below the budget ``T_sla - 2 T_input``.
3. Selection on the shifted profiles ``mu'(m) = mu(m) + W(m)``, with
   ``t_u`` the budget and ``t_l = t_u - T_threshold``: the base is the
   most accurate model with ``mu' + sigma < t_u`` and
   ``mu' - sigma < t_l``; the eligible set is the base and every model
   with ``mu' + sigma < t_u`` and ``mu'`` within
   ``|t_l - mu'(base)| + sigma(base)`` of ``t_l``; utilities
   ``acc^gamma (t_u - mu' - sigma) / max(|t_l - mu'|, 1e-9)`` (uniform
   over the eligible set where they sum to nothing positive); the pick
   is the first model whose cumulative utility exceeds ``r * total``,
   ``r`` the request's uniform draw.  With no base, the pick is the
   model of least ``mu``.
4. Charge: an admitted pick adds ``mu(pick) / speed`` to its candidate
   replica of least wait (the first such, in pool order).

The uniform draws are those of the router's stated draw rule:
``jax.random.uniform(PRNGKey(s), (bpad,))`` with ``s`` the router's one
``integers(2**63 - 1)`` draw per tick and ``bpad`` the tick padded to a
multiple of 256 (of 4096 above 4096).  This file imports nothing of the
program.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

EPS = 1e-9
# A comparison closer than this to its threshold (ms), or a draw closer
# than this share of the total to a cumulative boundary, can go either
# way in the program's float32: such rows are not compared.
TIME_TOL_MS = 1e-3
DRAW_TOL = 1e-5


class Pool(NamedTuple):
    mu: np.ndarray
    sigma: np.ndarray
    acc: np.ndarray
    rank: np.ndarray          # position in accuracy-descending order
    fastest: int
    threshold_ms: float
    gamma: float
    slack_ms: float
    include_mu: bool
    cand: list                # per model: its replicas' ledger indices
    speed: np.ndarray


def pool(config: dict) -> Pool:
    models = config["models"]
    mu = np.array([m["mu_ms"] for m in models], np.float64)
    acc = np.array([m["top1"] / 100.0 for m in models], np.float64)
    rank = np.empty(len(models))
    rank[np.argsort(-acc, kind="stable")] = np.arange(len(models))
    rpm = config["replicas_per_model"]
    adm = config["admission"]
    return Pool(mu=mu, sigma=np.array([m["sigma_ms"] for m in models]),
                acc=acc, rank=rank, fastest=int(np.argmin(mu)),
                threshold_ms=config["policy"]["t_threshold_ms"],
                gamma=config["policy"]["gamma"], slack_ms=adm["slack_ms"],
                include_mu=adm["include_service_time"],
                cand=[np.arange(i * rpm, (i + 1) * rpm)
                      for i in range(len(models))],
                speed=np.full(len(models) * rpm, config["replica_speed"]))


def draws(seed: int, n: int) -> np.ndarray:
    import jax
    import jax.numpy as jnp
    bpad = max(256, -(-n // (256 if n <= 4096 else 4096))
               * (256 if n <= 4096 else 4096))
    r = jax.random.uniform(jax.random.PRNGKey(seed), (bpad,), jnp.float32)
    return np.asarray(r, np.float64)[:n]


def _step(p: Pool, rep_wait, budget, r01, rd):
    """One request's decision against the ledger ``rep_wait``, with every
    arithmetic result passed through ``rd`` (identity in float64; a
    rounding for the control).  Returns the decision and whether any
    comparison lay too close to call."""
    wq = rd(np.array([rep_wait[c].min() for c in p.cand]))
    cost = rd(wq + p.slack_ms)
    if p.include_mu:
        cost = rd(cost + p.mu)
    lim = rd(budget)
    admitted = bool(np.any(cost < lim))
    close = bool(np.any(np.abs(cost - lim) < TIME_TOL_MS))
    tu, tl = lim, rd(budget - p.threshold_ms)
    mui = rd(p.mu + wq)
    mus = rd(mui + p.sigma)
    lo_s = rd(mui - p.sigma)
    elig1 = (mus < tu) & (lo_s < tl)
    close |= bool(np.any(np.abs(mus - tu) < TIME_TOL_MS)
                  | np.any(np.abs(lo_s - tl) < TIME_TOL_MS))
    has_base = bool(elig1.any())
    if has_base:
        base = int(np.argmin(np.where(elig1, p.rank, np.inf)))
        half = rd(rd(np.abs(rd(tl - mui[base]))) + p.sigma[base])
        lo, hi = rd(tl - half), rd(tl + half)
        natural = (lo <= mui) & (mui <= hi) & (mus < tu)
        close |= bool(np.any(np.abs(mui - lo) < TIME_TOL_MS)
                      | np.any(np.abs(mui - hi) < TIME_TOL_MS))
        elig = natural.copy()
        elig[base] = True
        num = rd(tu - mus)
        den = np.maximum(rd(np.abs(rd(tl - mui))), EPS)
        u = rd(rd(rd(np.maximum(p.acc, EPS) ** p.gamma) * num) / den)
        u = np.where(elig, u, 0.0)
        total = rd(np.sum(u))
        if not (np.isfinite(total) and total > 0):
            u = elig.astype(np.float64)
        cdf = rd(np.cumsum(u))
        total = cdf[-1]
        th = rd(r01 * total)
        above = np.flatnonzero(cdf > th)
        pick = int(above[0]) if (total > th and len(above)) else base
        close |= bool(np.any(np.abs(cdf - th) <= DRAW_TOL * abs(total)))
    else:
        pick = p.fastest
    c = p.cand[pick]
    rep = int(c[np.argmin(rep_wait[c])])
    ordered = np.sort(rep_wait[c])
    close |= len(c) > 1 and bool(ordered[1] - ordered[0] < TIME_TOL_MS)
    w_chosen = float(wq[pick] if admitted else wq.min())
    return (admitted, pick, has_base, rep, w_chosen), close


def route_tick(p: Pool, rep_wait, budgets, r01, rd=lambda x: x):
    """A whole tick routed by the reference rule (the control uses this
    with a rounding ``rd``).  Returns the program's column layout:
    ``(admitted, model_idx, fallback, replica_idx, w_queue_ms)``."""
    ledger = rd(np.array(rep_wait, np.float64))
    B = len(budgets)
    out = (np.zeros(B, bool), np.full(B, -1), np.zeros(B, bool),
           np.full(B, -1), np.zeros(B))
    for i in range(B):
        (adm, pick, has, rep, w), _ = _step(p, ledger, budgets[i], r01[i], rd)
        out[0][i], out[4][i] = adm, w
        if adm:
            out[1][i], out[2][i], out[3][i] = pick, not has, rep
            ledger[rep] = rd(ledger[rep] + p.mu[pick] / p.speed[rep])
    return out


def mismatches(p: Pool, rep_wait, budgets, r01, got) -> tuple:
    """Compare a routed tick ``got`` (the program's columns, as
    :func:`route_tick` returns them) with the reference rule, request by
    request, each judged against the ledger that the program's own
    earlier decisions leave.  Returns ``(rows that differ, rows too
    close to call)``."""
    adm_g, pick_g, fb_g, rep_g, w_g = got
    ledger = np.array(rep_wait, np.float64)
    bad = close_rows = 0
    for i in range(len(budgets)):
        (adm, pick, has, rep, w), close = _step(p, ledger, budgets[i],
                                                r01[i], lambda x: x)
        same = bool(adm_g[i]) == adm and abs(w_g[i] - w) <= TIME_TOL_MS \
            + 1e-6 * abs(w)
        if adm:
            same &= (int(pick_g[i]) == pick and bool(fb_g[i]) == (not has)
                     and int(rep_g[i]) == rep)
        if close:
            close_rows += 1
        elif not same:
            bad += 1
        if adm_g[i]:
            m = int(pick_g[i]) if 0 <= pick_g[i] < len(p.cand) else pick
            r = int(rep_g[i]) if int(rep_g[i]) in p.cand[m] else rep
            ledger[r] += p.mu[m] / p.speed[r]
    return bad, close_rows
