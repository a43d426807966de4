"""Device-resident ModiPick selection.

One stage 1–3 body serves every device path: :func:`_stages12` (the
Eq. 2 eligibility matrix, the accuracy-order masked argmin and the
window-membership mask), :func:`_utilities` (the Eq. 3–4 utility rows)
and :func:`_draw` (the inverse-CDF categorical pick).  They take the
pool as ``(rows, npad)`` μ/σ rows: a shared pool passes its columns as
one ``(1, npad)`` row that broadcasts over the batch, a classed batch
passes one row per request.  Two layers run that body:

- the **fused selection** under one jit, from ``(mu, sigma, acc, t_u,
  t_l)`` straight to sampled pool indices with nothing crossing to the
  host between stages: :func:`select_fused` over one pool,
  :func:`select_fleet_stacked` over a leading cell axis,
  :func:`select_classed` over per-request class rows;
- the **charged sequential-greedy pass** (:func:`charged_select`): the
  same stages per request against a per-replica wait ledger that every
  admitted pick charges — one Pallas kernel on the TPU
  (``_charged_kernel``), a ``lax.scan`` over :func:`_charged_step`
  elsewhere.

Compiled callables are cached per ``(pool_size, gamma, ...)``
(``functools.lru_cache`` over the jit closure; XLA's own cache handles
the bucketed batch shapes), and the pool-side operands are padded to the
128-lane axis ONCE per :class:`DevicePool` — built at ``ProfileTable``
freeze via ``ProfileTable.device_pool()`` — instead of per call.

Sampling uses the inverse-CDF trick (one uniform per request against
the cumulative utility row) instead of per-lane Gumbel noise: exactly
categorical, and it draws B random numbers instead of B × 128.
``kernels.ref`` holds the pure-jnp stage 1–2 reference.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.obs import span

EPS = 1e-9
LANES = 128
# Padded-lane sentinels: a fake model this slow can never be eligible,
# and a rank this large never wins the stage-1 argmin.
PAD_MU = 1e30
PAD_RANK = 1e9


# ======================================================================
# Device-resident stages 1–3: one jit from (mu, sigma, acc, t_u, t_l)
# straight to sampled pool indices.
# ======================================================================

class DevicePool:
    """Pool-side operands of the fused selection, padded to the 128-lane
    axis once and parked on device.  Frozen against one ProfileTable
    snapshot — rebuild (cheap) when the profiles move.

    ``rank[i]`` is model ``i``'s position in the accuracy-descending
    order (the stable argsort the scalar path caches), so the stage-1
    "first eligible in accuracy order" is ``argmin`` of the masked rank
    row.  Padded lanes carry ``PAD_MU``/``PAD_RANK`` sentinels, which
    keeps every stage's math finite without a separate validity mask.

    The 128-lane padding is a TPU tiling constraint (the pool rides the
    vreg lane axis, and the charged kernel's rows with it); the XLA-CPU
    path has no such
    constraint, so off-TPU the pool keeps its natural width instead of
    paying 16× elementwise waste on a typical 8-model zoo.
    """

    __slots__ = ("n", "npad", "mu", "sigma", "acc", "rank", "fastest")

    def __init__(self, mu, sigma, acc, acc_order, fastest: int):
        n = len(mu)
        if jax.default_backend() == "tpu":
            npad = max(LANES, -(-n // LANES) * LANES)
        else:
            npad = n
        self.n = n
        self.npad = npad

        def pad(x, value):
            return jnp.asarray(np.pad(np.asarray(x, np.float32),
                                      (0, npad - n),
                                      constant_values=value))

        rank = np.empty(n, np.float32)
        rank[np.asarray(acc_order)] = np.arange(n, dtype=np.float32)
        self.mu = pad(mu, PAD_MU)
        self.sigma = pad(sigma, 0.0)
        self.acc = pad(acc, 1.0)
        self.rank = pad(rank, PAD_RANK)
        self.fastest = int(fastest)


def _stages12(mu, sig, rank, t_u, t_l):
    """Stages 1–2 on device.  mu/sig: (1, npad) shared or (B, npad)
    per-request pool rows; rank: (npad,); t_u/t_l: (B,).  Returns
    ``(base, has_base, eligible)`` — the Eq. 2 eligibility matrix
    reduced by accuracy-order masked argmin (stage 1) and the window
    membership mask with the base forced in (stage 2)."""
    tu, tl = t_u[:, None], t_l[:, None]
    mus = mu + sig
    elig1 = (mus < tu) & ((mu - sig) < tl)                   # Eq. 2, (B, npad)
    has_base = elig1.any(axis=1)
    base = jnp.argmin(jnp.where(elig1, rank[None, :], PAD_RANK + 1.0),
                      axis=1).astype(jnp.int32)              # first in acc order
    at_base = lambda x: jnp.take_along_axis(
        jnp.broadcast_to(x, elig1.shape), base[:, None], axis=1)[:, 0]
    half = jnp.abs(t_l - at_base(mu)) + at_base(sig)         # (B,)
    lo, hi = (t_l - half)[:, None], (t_l + half)[:, None]
    natural = (lo <= mu) & (mu <= hi) & (mus < tu)
    eligible = natural | (jnp.arange(mu.shape[1])[None, :] == base[:, None])
    eligible &= has_base[:, None]
    return base, has_base, eligible


def _utilities(mu, sig, acc, t_u, t_l, eligible, gamma):
    """Eq. 3–4 utility rows over the same pool rows as :func:`_stages12`
    (acc: (npad,)); degenerate rows (non-finite or non-positive mass)
    fall back to uniform-over-eligible, exactly like the scalar path."""
    tu, tl = t_u[:, None], t_l[:, None]
    num = tu - (mu + sig)
    den = jnp.maximum(jnp.abs(tl - mu), EPS)
    u = jnp.power(jnp.maximum(acc, EPS), gamma)[None, :] * num / den
    u = jnp.where(eligible, u, 0.0)
    total = jnp.sum(u, axis=1, keepdims=True)
    good = jnp.isfinite(total) & (total > 0)
    return jnp.where(good, u, eligible.astype(u.dtype))


def _draw(w, base, has_base, r01, fallback):
    """Inverse-CDF pick from (B, npad) weight rows with one uniform
    ``r01`` per row: the first index whose cumulative mass exceeds
    ``r01 · total`` — exact categorical sampling without per-lane noise.
    Zero-weight lanes have flat cdf segments and are never picked; the
    float edge ``thresh == total`` goes to the (always eligible) base,
    and rows without a base go to ``fallback``."""
    cdf = jnp.cumsum(w, axis=1)
    total = cdf[:, -1]
    thresh = r01 * total
    choice = jnp.argmax(cdf > thresh[:, None], axis=1).astype(jnp.int32)
    choice = jnp.where(total > thresh, choice, base)
    return jnp.where(has_base, choice, fallback)


def _fused_select(mu, sig, acc, rank, t_u, t_l, seed, *, gamma: float):
    """The whole pipeline under one trace (:func:`fleet_select_body`,
    keyed by ``seed``): (B,) int32 sampled pool indices, -1 where no
    base model exists (the caller's fallback lane)."""
    return fleet_select_body(mu, sig, acc, rank, t_u, t_l,
                             jax.random.PRNGKey(seed), gamma=gamma)


@functools.lru_cache(maxsize=64)
def _fused_jit(npad: int, gamma: float):
    """The jit cache: one compiled callable per (pool_size, gamma) —
    XLA's shape cache handles the bucketed batch axis."""
    return jax.jit(functools.partial(_fused_select, gamma=gamma))


@functools.lru_cache(maxsize=8)
def _masks_jit(npad: int):
    return jax.jit(_stages12)


def _bucket(B: int, block_b: int) -> int:
    """Pad the batch axis to a bounded family of shapes so jit retraces
    stay rare: multiples of ``block_b`` up to 4096, multiples of 4096
    beyond (≤4% padding waste at large B)."""
    step = block_b if B <= 4096 else 4096
    return max(block_b, -(-B // step) * step)


def _pad_batch(x, bpad: int) -> np.ndarray:
    out = np.zeros(bpad, np.float32)
    out[:len(x)] = x
    return out


def select_fused(pool: DevicePool, t_u, t_l, *, gamma: float = 1.0,
                 seed: int = 0, block_b: int = 256):
    """Device-resident batched ModiPick selection.

    ``t_u``/``t_l``: (B,) per-request budget bounds.  Returns
    ``(idx, has_base)`` numpy arrays — ``idx[b]`` is the sampled pool
    index (already routed to ``pool.fastest`` where ``~has_base``).
    One host→device transfer (the budget rows), one device→host
    transfer (the packed picks)."""
    B = len(t_u)
    bpad = _bucket(B, block_b)
    fn = _fused_jit(pool.npad, float(gamma))
    out = np.asarray(fn(pool.mu, pool.sigma, pool.acc, pool.rank,
                        jnp.asarray(_pad_batch(t_u, bpad)),
                        jnp.asarray(_pad_batch(t_l, bpad)),
                        np.uint32(seed & 0xFFFFFFFF)))[:B]
    has_base = out >= 0
    return np.where(has_base, out, pool.fastest), has_base


# ======================================================================
# Fleet selection: the fused pipeline over a leading cell axis.  Every
# cell's pending batch is judged in ONE device call — (cell × batch ×
# pool) operands in, (cell × batch) picks out.  Cells ride `jax.vmap`,
# and `distributed.shardmap_ops.sharded_fleet_select` wraps the same
# body under `shard_map` when a mesh carries a "cell" axis, so the call
# is bit-identical between CPU tests and sharded meshes.
# ======================================================================

def fleet_select_body(mu, sig, acc, rank, t_u, t_l, key, *,
                      gamma: float = 1.0):
    """One cell's fused selection, written to be vmapped/shard_mapped
    over a leading cell axis.  mu/sig/acc/rank: (npad,) pool operands
    (PAD_MU/PAD_RANK sentinels on lanes beyond the cell's own pool);
    t_u/t_l: (B,) budget bounds; key: a PRNG key.  Returns (B,) int32
    picks, −1 where no base model exists (the caller's shed/fallback
    lane)."""
    mu, sig = mu[None, :], sig[None, :]
    base, has_base, eligible = _stages12(mu, sig, rank, t_u, t_l)
    w = _utilities(mu, sig, acc, t_u, t_l, eligible, gamma)
    r01 = jax.random.uniform(key, t_u.shape, dtype=w.dtype)
    return _draw(w, base, has_base, r01, -1)


@functools.lru_cache(maxsize=16)
def _fleet_jit(npad: int, gamma: float):
    """One compiled callable per (common pool width, gamma): cells ride
    a vmap over the leading axis, batches bucket like `select_fused`."""
    return jax.jit(jax.vmap(
        functools.partial(fleet_select_body, gamma=gamma)))


def select_fleet_stacked(mu, sig, acc, rank, t_u, t_l, *,
                         gamma: float = 1.0, seed: int = 0) -> np.ndarray:
    """All cells' pending batches as one device call.

    ``mu/sig/acc/rank``: (C, npad) stacked pool operands (see
    ``fleet.device.stack_cell_tables``); ``t_u``/``t_l``: (C, B) budget
    bounds — row c is cell c's judgment of every pending request.
    Returns (C, B) int32 numpy picks, −1 where cell c has no eligible
    model for request b.  Each cell draws from its own fold of the
    seed, so per-cell streams are decorrelated but deterministic."""
    C, B = np.shape(t_u)
    bpad = _bucket(B, 256)
    pad2 = lambda x: np.pad(np.asarray(x, np.float32),
                            ((0, 0), (0, bpad - B)))
    keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        jax.random.PRNGKey(seed), jnp.arange(C, dtype=jnp.uint32))
    fn = _fleet_jit(int(np.shape(mu)[1]), float(gamma))
    out = fn(jnp.asarray(mu), jnp.asarray(sig), jnp.asarray(acc),
             jnp.asarray(rank), jnp.asarray(pad2(t_u)),
             jnp.asarray(pad2(t_l)), keys)
    return np.asarray(out)[:, :B]


# ======================================================================
# Class-conditional selection: the fused pipeline with PER-REQUEST pool
# rows.  The premodel layer keeps K per-class profile tables over the
# same zoo (premodel.conditional.ConditionalProfileStore); a batch
# carrying per-request input-class ids gathers each request's class row
# out of the stacked (K, npad) mu/sigma operands and runs the same
# stage 1–3 body — ONE device call for the whole classed batch.
# Accuracy (and the stage-1 rank derived from it) never varies by
# class, so acc/rank stay (npad,) and broadcast.
# ======================================================================

def _classed_select(mu_k, sig_k, acc, rank, cls, shifts, t_u, t_l, seed, *,
                    gamma: float):
    """The classed pipeline under one trace: gather each request's class
    row, add the (class-independent) queue-wait shifts, then stages 1–3
    and the draw.  Returns (B,) int32 picks with the no-base fallback
    resolved to the row's own fastest model (padded lanes carry PAD_MU
    and never win the argmin), plus the has_base mask."""
    mu = mu_k[cls] + shifts[None, :]       # (B, npad); shifts are per-model
    sig = sig_k[cls]
    base, has_base, eligible = _stages12(mu, sig, rank, t_u, t_l)
    w = _utilities(mu, sig, acc, t_u, t_l, eligible, gamma)
    r01 = jax.random.uniform(jax.random.PRNGKey(seed), t_u.shape,
                             dtype=w.dtype)
    fastest = jnp.argmin(mu, axis=1).astype(jnp.int32)
    return _draw(w, base, has_base, r01, fastest), has_base


@functools.lru_cache(maxsize=32)
def _classed_jit(K: int, npad: int, gamma: float):
    return jax.jit(functools.partial(_classed_select, gamma=gamma))


def select_classed(stacked, cls, t_u, t_l, *, shifts=None,
                   gamma: float = 1.0, seed: int = 0,
                   block_b: int = 256):
    """Batched class-conditional ModiPick selection in one device call.

    ``stacked``: a ``premodel.conditional.StackedClassPools`` — (K, npad)
    per-class mu/sigma plus shared (npad,) acc/rank.  ``cls``: (B,)
    int input-class ids; ``t_u``/``t_l``: (B,) budget bounds;
    ``shifts``: optional (n,) per-model queue-wait shifts (identical
    across classes — waits live at replicas, not input classes).
    Returns ``(idx, has_base)`` numpy arrays with the fallback already
    resolved to the per-class fastest model.
    """
    B = len(t_u)
    bpad = _bucket(B, block_b)
    cls_pad = np.zeros(bpad, np.int32)
    cls_pad[:B] = np.asarray(cls, np.int32)
    sh = np.zeros(stacked.npad, np.float32)
    if shifts is not None:
        sh[:len(shifts)] = np.asarray(shifts, np.float32)
    fn = _classed_jit(stacked.k, stacked.npad, float(gamma))
    idx, has_base = fn(stacked.mu, stacked.sigma, stacked.acc, stacked.rank,
                       jnp.asarray(cls_pad), jnp.asarray(sh),
                       jnp.asarray(_pad_batch(t_u, bpad)),
                       jnp.asarray(_pad_batch(t_l, bpad)),
                       np.uint32(seed & 0xFFFFFFFF))
    return np.asarray(idx)[:B], np.asarray(has_base)[:B]


# ======================================================================
# Charged sequential-greedy selection: a sequential pass over the batch,
# with the per-replica wait ledger as the carry — a lax.scan, or one
# Pallas kernel on the TPU.
# ======================================================================

def _charged_step(rep_wait, xs, *, mu, sig, acc, rank, mu_charge,
                  cand_mask, speed, gamma: float, slack: float,
                  include_mu: bool, fastest: int):
    """One scan step = one request judged against the *charged* waits.

    Carry: ``rep_wait`` (R,) — every replica's wait including all
    charges so far.  Per step: derive the live ``W_queue(m)`` row (min
    over each model's candidate replicas), run admission viability +
    shifted-μ stages 1–3 + the inverse-CDF draw against it, then charge
    the admitted pick's μ/speed to its least-loaded capable replica
    before the next step sees the carry.
    """
    tu, tl, r01, lim = xs
    # (npad,) per-model wait: min over candidate replicas.  Padded lanes
    # have no candidates → +inf; they also carry PAD_MU, so clamping
    # their shift to 0 keeps every downstream comparison finite.
    wq_raw = jnp.min(jnp.where(cand_mask, rep_wait[None, :], jnp.inf),
                     axis=1)
    wq = jnp.where(jnp.isfinite(wq_raw), wq_raw, 0.0)

    # SLA-aware admission viability against the charged waits: some
    # model must satisfy W_queue + slack (+ μ) < limit.  AdmitAll passes
    # lim=+inf; padded *batch* rows pass lim=−inf so they neither admit
    # nor charge.
    cost = wq_raw + slack
    if include_mu:
        cost = cost + mu_charge
    admitted = jnp.any(cost < lim)

    # The shifted-μ store view, as one shared pool row.
    mu_i, sig, tu, tl = (mu + wq)[None, :], sig[None, :], tu[None], tl[None]
    base, has_base, eligible = _stages12(mu_i, sig, rank, tu, tl)
    w = _utilities(mu_i, sig, acc, tu, tl, eligible, gamma)
    pick = _draw(w, base, has_base, r01[None], fastest)[0]

    # Charge: least-loaded capable replica, first-index tie-break (the
    # pool-order rule ``ReplicaPool.best_for`` uses).
    masked = jnp.where(cand_mask[pick], rep_wait, jnp.inf)
    rep = jnp.argmin(masked).astype(jnp.int32)
    delta = jnp.where(admitted, mu_charge[pick] / speed[rep], 0.0)
    rep_wait = rep_wait.at[rep].add(delta)

    w_chosen = jnp.where(admitted, wq[pick], jnp.min(wq_raw))
    return rep_wait, (pick, admitted, has_base[0], rep, w_chosen)


# ----------------------------------------------------------------------
# The same sequential-greedy loop as ONE Pallas TPU kernel.  The scan
# above launches a dozen small XLA fusions per request, each paying a
# launch and an HBM round trip for its carry; here every step of a
# tick runs inside one kernel with the ledger, the pool rows and the
# candidate topology resident in VMEM.
#
# Layout: the candidate matrix is transposed to (replicas × models), so
# a model's least candidate wait is a sublane reduction that lands on
# the pool's lane layout; the ledger rides beside it, broadcast over
# the lanes.  Each request's four scalars come from SMEM; its five
# results are gathered into one vreg tile per column and stored once
# per block of rows.  The ledger lives in VMEM scratch across the
# sequential ("arbitrary") grid over row blocks, so the kernel's VMEM
# does not grow with the batch.
# ----------------------------------------------------------------------

# Rows per grid step: one (8, 128) result tile per column.
CHARGED_BLOCK = 1024
# VMEM for the kernel's (replicas × lanes) float32 planes: the candidate,
# speed and initial-wait matrices (each double-buffered by the pipeline)
# and the live ledger.  Beyond it the scan runs instead.
CHARGED_VMEM_BYTES = 8 << 20


def charged_kernel_engaged(npad: int, n_replicas: int) -> bool:
    """Whether ``charged_select`` runs the Pallas kernel rather than the
    ``lax.scan``: on the TPU backend, when the (replicas × models)
    operands fit the kernel's VMEM budget."""
    lanes = -(-npad // LANES) * LANES
    rows = -(-n_replicas // 8) * 8
    return (jax.default_backend() == "tpu"
            and 7 * rows * lanes * 4 <= CHARGED_VMEM_BYTES)


def _lane_cumsum(x, lane):
    """Inclusive prefix sum along lanes: log-step roll-and-add."""
    k = 1
    while k < x.shape[1]:
        x = x + jnp.where(lane >= k, pltpu.roll(x, k, 1), 0.0)
        k *= 2
    return x


def _charged_kernel(pool_ref, cand_ref, spd_ref, wait_ref, rows_ref, out_ref,
                    ledger_ref, *, slack: float, include_mu: bool,
                    fastest: int):
    """One block of rows of the charged pass; step for step what
    :func:`_charged_step` does, in float32."""
    rp, npad = cand_ref.shape
    sub, lanes = out_ref.shape[1:]
    f32 = jnp.float32

    @pl.when(pl.program_id(0) == 0)
    def _():
        ledger_ref[...] = wait_ref[...]

    mu, sig = pool_ref[0:1, :], pool_ref[1:2, :]
    accg, rank = pool_ref[2:3, :], pool_ref[3:4, :]
    mu_charge = pool_ref[4:5, :]
    cand = cand_ref[...] > 0.0
    spd = spd_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, npad), 1).astype(f32)
    rep_row = jax.lax.broadcasted_iota(jnp.int32, (rp, npad), 0).astype(f32)
    slot = (jax.lax.broadcasted_iota(jnp.int32, (sub, lanes), 0) * lanes
            + jax.lax.broadcasted_iota(jnp.int32, (sub, lanes), 1)
            ).astype(f32)
    inf = jnp.inf
    no_rank = jnp.float32(PAD_RANK + 1.0)

    def lane_min(x):
        return jnp.min(x, axis=1, keepdims=True)

    def at(onehot, x):
        return jnp.max(jnp.where(onehot, x, -inf), axis=1, keepdims=True)

    def step(j, tiles):
        tu, tl, r01, lim = (rows_ref[0, k, j] for k in range(4))
        ledger = ledger_ref[...]
        # Per model: least candidate wait (+inf without candidates), its
        # first least-waiting replica (argmin's first index, 0 where
        # every entry is +inf) and that replica's speed.
        masked = jnp.where(cand, ledger, inf)
        wq_raw = jnp.min(masked, axis=0, keepdims=True)
        first = jnp.min(jnp.where(masked == wq_raw, rep_row, float(rp)),
                        axis=0, keepdims=True)
        speed = jnp.max(jnp.where(rep_row == first, spd, -inf), axis=0,
                        keepdims=True)
        wq = jnp.where(jnp.abs(wq_raw) < inf, wq_raw, 0.0)

        cost = wq_raw + slack
        if include_mu:
            cost = cost + mu_charge
        admitted = jnp.max(jnp.where(cost < lim, 1.0, 0.0), axis=1,
                           keepdims=True) > 0.0

        # Stages 1-2 on the shifted profiles.  Real lanes carry distinct
        # ranks, so wherever a base exists the least rank marks one lane.
        mu_i = mu + wq
        mus = mu_i + sig
        elig1 = (mus < tu) & ((mu_i - sig) < tl)
        keyed = jnp.where(elig1, rank, no_rank)
        rmin = lane_min(keyed)
        has_base = jnp.max(jnp.where(elig1, 1.0, 0.0), axis=1,
                           keepdims=True) > 0.0
        is_base = keyed == rmin
        half = at(is_base, jnp.abs(tl - mu_i) + sig)
        lo, hi = tl - half, tl + half
        natural = (lo <= mu_i) & (mu_i <= hi) & (mus < tu)
        eligible = (natural | is_base) & has_base

        # Eq. 3-4 utilities and the inverse-CDF draw.  Both candidate
        # weight rows are scanned at once; the degenerate-row test picks.
        u = jnp.where(eligible, accg * (tu - mus)
                      / jnp.maximum(jnp.abs(tl - mu_i), EPS), 0.0)
        total_u = jnp.sum(u, axis=1, keepdims=True)
        good = (jnp.abs(total_u) < inf) & (total_u > 0.0)
        cdf = jnp.where(good, _lane_cumsum(u, lane),
                        _lane_cumsum(jnp.where(eligible, 1.0, 0.0), lane))
        total = at(lane == float(npad - 1), cdf)
        thresh = r01 * total
        choice = lane_min(jnp.where(cdf > thresh, lane, float(npad)))
        hit_cdf = total > thresh
        drawn = (hit_cdf & (lane == choice)) | (~hit_cdf & is_base)
        picked = (has_base & drawn) | (~has_base & (lane == float(fastest)))

        # Charge the pick to its least-waiting replica.
        rep = at(picked, first)
        delta = jnp.where(admitted, at(picked, mu_charge / speed), 0.0)
        ledger_ref[...] = jnp.where(rep_row == rep, ledger + delta, ledger)

        w_chosen = jnp.where(admitted, at(picked, wq), lane_min(wq_raw))
        row = (at(picked, lane), jnp.where(admitted, 1.0, 0.0),
               jnp.where(has_base, 1.0, 0.0), rep, w_chosen)
        hit = slot == j.astype(f32)
        return tuple(jnp.where(hit, v, t) for v, t in zip(row, tiles))

    zero = jnp.zeros((sub, lanes), f32)
    tiles = jax.lax.fori_loop(0, sub * lanes, step, (zero,) * 5)
    for k, t in enumerate(tiles):
        out_ref[k] = t


def _charged_pallas(mu, sig, acc, rank, mu_charge, cand_mask, speed,
                    rep_wait, t_u, t_l, r01, lim, *, gamma: float,
                    slack: float, include_mu: bool, fastest: int,
                    interpret: bool = False):
    """The charged pass as one ``pallas_call``; same operands and the
    same five result columns as the scan."""
    f32 = jnp.float32
    n = mu.shape[0]
    R = rep_wait.shape[0]
    npad = -(-n // LANES) * LANES
    rp = -(-R // 8) * 8
    B = t_u.shape[0]
    b8 = -(-B // 8) * 8
    block = math.gcd(b8, CHARGED_BLOCK)
    nblk = b8 // block

    # Pool rows (one sublane tile): mu, sigma, acc**gamma, rank, charge-mu.
    pool = jnp.stack([
        jnp.pad(x.astype(f32), (0, npad - n), constant_values=v)
        for x, v in ((mu, PAD_MU), (sig, 0.0),
                     (jnp.power(jnp.maximum(acc, EPS), gamma), 1.0),
                     (rank, PAD_RANK), (mu_charge, 0.0))])
    pool = jnp.pad(pool, ((0, 3), (0, 0)))
    cand = jnp.pad(cand_mask.T.astype(f32), ((0, rp - R), (0, npad - n)))
    col = lambda x, v: jnp.broadcast_to(
        jnp.pad(x.astype(f32), (0, rp - R), constant_values=v)[:, None],
        (rp, npad))
    rows = jnp.stack([jnp.pad(x.astype(f32), (0, b8 - B), constant_values=v)
                      for x, v in ((t_u, 0.0), (t_l, 0.0), (r01, 0.0),
                                   (lim, -jnp.inf))])
    rows = rows.reshape(4, nblk, block).transpose(1, 0, 2)

    whole = lambda shape: pl.BlockSpec(shape, lambda i: (0, 0))
    out = pl.pallas_call(
        functools.partial(_charged_kernel, slack=slack,
                          include_mu=include_mu, fastest=fastest),
        grid=(nblk,),
        in_specs=[whole(pool.shape), whole((rp, npad)),
                  whole((rp, npad)), whole((rp, npad)),
                  pl.BlockSpec((1, 4, block), lambda i: (i, 0, 0),
                               memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((5, 8, block // 8), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((5, 8 * nblk, block // 8), f32),
        scratch_shapes=[pltpu.VMEM((rp, npad), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(pool, cand, col(speed, 1.0), col(rep_wait, 0.0), rows)
    out = out.reshape(5, nblk, 8, block // 8).reshape(5, b8)[:, :B]
    return (out[0].astype(jnp.int32), out[1] > 0.0, out[2] > 0.0,
            out[3].astype(jnp.int32), out[4])


def _charged_operands(npad: int, n: int, bpad: int, t_u, t_l, state,
                      adm_limit):
    """The charged pass's host operands, as numpy in the order of
    :func:`_charged_pass` after the pool columns: ``(mu_charge,
    cand_mask, speed, rep_wait, t_u, t_l)`` and, last, the admission
    limit row (``-inf`` on padded rows, ``+inf`` everywhere real when
    ``adm_limit`` is ``None``)."""
    cand_mask = np.zeros((npad, len(state.rep_wait)), dtype=bool)
    lens = [len(c) for c in state.cand]
    cand_mask[np.repeat(np.arange(len(lens)), lens),
              np.concatenate(state.cand)] = True
    mu_charge = np.zeros(npad, np.float32)
    mu_charge[:n] = np.asarray(state.mu, np.float64)[:n]
    lim = np.full(bpad, -np.inf, np.float32)
    lim[:len(t_u)] = (np.inf if adm_limit is None
                      else np.asarray(adm_limit, np.float32))
    return (mu_charge, cand_mask,
            np.asarray(state.speed, np.float32),
            np.asarray(state.rep_wait, np.float32),
            _pad_batch(t_u, bpad), _pad_batch(t_l, bpad), lim)


def _key_words(seed: int) -> np.ndarray:
    """The two ``uint32`` words of ``jax.random.PRNGKey(seed)`` for a
    Python int seed, made on the host: the low 32 bits, and above them
    the next 32 where x64 is on (``0`` where it is off)."""
    hi = (seed >> 32) & 0xFFFFFFFF if jax.config.jax_enable_x64 else 0
    return np.array([hi, seed & 0xFFFFFFFF], np.uint32)


def _charged_layout(npad: int, n_replicas: int, bpad: int):
    """``(start, stop)`` of each field of the packed tick buffer:
    :func:`_charged_operands`' seven columns, then the two key words."""
    sizes = (npad, npad * n_replicas, n_replicas, n_replicas,
             bpad, bpad, bpad, 2)
    stops = np.cumsum(sizes).tolist()
    return tuple(zip([0] + stops[:-1], stops))


def _charged_pack(host, seed: int) -> np.ndarray:
    """One contiguous ``uint32`` buffer of the tick's host operands (in
    :func:`_charged_layout`'s order; floats by their bits, the mask as
    0/1) and the draw's key words."""
    bits = lambda x: np.asarray(x, np.float32).view(np.uint32)
    mu_charge, cand_mask, *rows = host
    return np.concatenate([bits(mu_charge),
                           cand_mask.astype(np.uint32).ravel(),
                           *map(bits, rows), _key_words(seed)])


def _charged_unpack(packed, npad: int, n_replicas: int, bpad: int):
    """The inverse of :func:`_charged_pack`, traced: the seven operands
    (the mask as ``bool (npad, R)``), then the key words."""
    fields = [packed[a:b] for a, b in _charged_layout(npad, n_replicas,
                                                      bpad)]
    f32 = lambda x: jax.lax.bitcast_convert_type(x, jnp.float32)
    mu_charge, cand, speed, rep_wait, t_u, t_l, lim, key = fields
    return (f32(mu_charge), (cand != 0).reshape(npad, n_replicas),
            f32(speed), f32(rep_wait), f32(t_u), f32(t_l), f32(lim), key)


def _charged_pass(mu, sig, acc, rank, mu_charge, cand_mask, speed,
                  rep_wait, t_u, t_l, r01, lim, *, gamma: float,
                  slack: float, include_mu: bool, fastest: int,
                  use_kernel: bool, interpret: bool):
    """The charged pass over unpacked operands: the ``lax.scan`` over
    :func:`_charged_step`, or with ``use_kernel`` the Pallas kernel
    (``interpret`` runs it through the Pallas interpreter)."""
    if use_kernel:
        return _charged_pallas(
            mu, sig, acc, rank, mu_charge, cand_mask, speed, rep_wait,
            t_u, t_l, r01, lim, gamma=gamma, slack=slack,
            include_mu=include_mu, fastest=fastest, interpret=interpret)
    step = functools.partial(
        _charged_step, mu=mu, sig=sig, acc=acc, rank=rank,
        mu_charge=mu_charge, cand_mask=cand_mask, speed=speed,
        gamma=gamma, slack=slack, include_mu=include_mu, fastest=fastest)
    _, ys = jax.lax.scan(step, rep_wait, (t_u, t_l, r01, lim))
    return ys


@functools.lru_cache(maxsize=32)
def _charged_jit(npad: int, gamma: float, slack: float, include_mu: bool,
                 fastest: int, use_kernel: bool = False,
                 interpret: bool = False):
    """The charged pass's jit cache: one program ``run`` that unpacks
    the tick's buffer, draws its uniforms and runs
    :func:`_charged_pass`.  The buffer's layout is static per
    ``(n_replicas, bpad)``."""
    def run(mu, sig, acc, rank, packed, *, n_replicas: int, bpad: int):
        *cols, lim, key = _charged_unpack(packed, npad, n_replicas, bpad)
        r01 = jax.random.uniform(key, (bpad,), dtype=jnp.float32)
        return _charged_pass(
            mu, sig, acc, rank, *cols, r01, lim, gamma=gamma, slack=slack,
            include_mu=include_mu, fastest=fastest, use_kernel=use_kernel,
            interpret=interpret)
    return jax.jit(run, static_argnames=("n_replicas", "bpad"))


def charged_select(pool: DevicePool, t_u, t_l, state, *,
                   gamma: float = 1.0, adm_limit=None,
                   adm_slack: float = 0.0, adm_include_mu: bool = False,
                   seed: int = 0, block_b: int = 256):
    """Device-resident charged batch selection: a sequential pass over
    the batch whose carry is the per-replica wait ledger, so request
    ``i`` is admitted and selected against waits that include the
    charges of requests ``0..i-1`` — the sequential-greedy staleness
    fix, riding the same fused stage-1–3 math as :func:`select_fused`.
    The pass is one Pallas kernel where :func:`charged_kernel_engaged`
    says so (the TPU, replicas within its VMEM budget), else a
    ``lax.scan`` over :func:`_charged_step`.

    ``state`` is a :class:`repro.router.charging.ChargedWaits` (replica
    waits, model → candidate topology, speeds, live charge-μ).
    ``adm_limit`` (B,) enables the in-scan SLA-aware viability test
    (``W_queue + slack (+ μ) < limit``); ``None`` admits everything.
    Returns numpy ``(picks, admitted, has_base, replica, w_chosen)``:
    the picked pool index, the admission verdict, the fallback
    indicator, the replica the charge landed on, and the chosen model's
    pre-charge wait (for shed rows: the pool's minimum wait).

    A tick is one host→device transfer (the operands and the key words
    of ``PRNGKey(seed)``, packed into one buffer) and one program, which
    draws the batch's uniforms itself.  Like the uncharged fused path,
    the draw is categorical from the exact per-request distribution but
    rides jax's RNG — same law as the numpy sequential loop, not the
    same stream.
    """
    B = len(t_u)
    n, npad = pool.n, pool.npad
    R = len(state.rep_wait)
    bpad = _bucket(B, block_b)

    with span("router.select.pack"):
        host = _charged_operands(npad, n, bpad, t_u, t_l, state, adm_limit)
        packed = jax.device_put(_charged_pack(host, seed))

    kernel = charged_kernel_engaged(npad, R)
    fn = _charged_jit(npad, float(gamma), float(adm_slack),
                      bool(adm_include_mu), pool.fastest, kernel,
                      kernel and jax.default_backend() != "tpu")
    with span("router.select.readback"):
        # One device_get starts all five copies before waiting on any.
        picks, admitted, has_base, rep, w_chosen = jax.device_get(
            fn(pool.mu, pool.sigma, pool.acc, pool.rank, packed,
               n_replicas=R, bpad=bpad))
        return (picks[:B], admitted[:B], has_base[:B], rep[:B],
                np.asarray(w_chosen, np.float64)[:B])


def masks_device(pool: DevicePool, t_u, t_l):
    """Stages 1–2 alone, through the same traced code as
    :func:`select_fused` — the test surface for pinning the device
    masks against the ``policy_vec.modipick_masks`` numpy reference.
    Returns numpy ``(base, has_base, eligible)`` with ``eligible``
    trimmed to the unpadded pool."""
    B = len(t_u)
    bpad = _bucket(B, 8)
    base, has, elig = _masks_jit(pool.npad)(
        pool.mu[None, :], pool.sigma[None, :], pool.rank,
        jnp.asarray(_pad_batch(t_u, bpad)),
        jnp.asarray(_pad_batch(t_l, bpad)))
    return (np.asarray(base)[:B], np.asarray(has)[:B],
            np.asarray(elig)[:B, :pool.n])
