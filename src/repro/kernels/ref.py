"""Pure-jnp oracles for the kernels and the device selection stages
(small-shape ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def flash_attention_ref(q, k, v, *, causal=True, window=0):
    """q: (B,H,Sq,hd); k,v: (B,KV,Sk,hd) → (B,H,Sq,hd). Naive full softmax."""
    B, H, Sq, hd = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    group = H // KV
    kx = jnp.repeat(k, group, axis=1)
    vx = jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kx.astype(jnp.float32)) * hd ** -0.5
    qi = jnp.arange(Sq)[:, None]
    kj = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kj <= qi
    if window > 0:
        mask &= kj > qi - window
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vx.astype(jnp.float32)).astype(q.dtype)


def decode_attention_ref(q, k, v, pos, *, window=0):
    """q: (B,KV,G,hd); k,v: (B,KV,S,hd); pos: (B,) → (B,KV,G,hd)."""
    hd = q.shape[-1]
    S = k.shape[2]
    s = jnp.einsum("bngd,bnkd->bngk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * hd ** -0.5
    kj = jnp.arange(S)[None, None, None, :]
    mask = kj <= pos[:, None, None, None]
    if window > 0:
        mask &= kj > pos[:, None, None, None] - window
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bngk,bnkd->bngd", p, v.astype(jnp.float32)).astype(q.dtype)


def ssd_scan_ref(x, dt, A, B_, C_):
    """Sequential SSD recurrence. x: (B,H,S,hd); dt: (B,H,S); A: (H,);
    B_,C_: (B,G,S,N). h_t = exp(dt·A)·h + dt·B⊗x ; y = C·h."""
    Bb, H, S, hd = x.shape
    G, N = B_.shape[1], B_.shape[3]
    group = H // G
    Bx = jnp.repeat(B_, group, axis=1).astype(jnp.float32)  # (B,H,S,N)
    Cx = jnp.repeat(C_, group, axis=1).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    decay = jnp.exp(dtf * A[None, :, None])  # (B,H,S)

    def step(h, t):
        d, u, c = t
        h = h * d[..., None, None] + u
        y = jnp.einsum("bhpn,bhn->bhp", h, c)
        return h, y

    upd = jnp.einsum("bhs,bhsp,bhsn->sbhpn", dtf, xf, Bx)
    h0 = jnp.zeros((Bb, H, hd, N), jnp.float32)
    _, ys = jax.lax.scan(step, h0, (jnp.moveaxis(decay, 2, 0), upd,
                                    jnp.moveaxis(Cx, 2, 0)))
    return jnp.moveaxis(ys, 0, 2).astype(x.dtype)  # (B,H,S,hd)


def modipick_masks_ref(mu, sigma, rank, t_u, t_l, *, pad_rank=1e9):
    """Batched ModiPick stages 1–2 oracle (pure jnp, unpadded shapes).

    mu/sigma: (n,); rank: (n,) position of each model in the
    accuracy-descending order; t_u/t_l: (B,).  Returns
    ``(base, has_base, eligible)``: the Eq. 2 eligibility reduced by
    accuracy-order masked argmin (stage 1) and the window-membership
    matrix with the base forced in (stage 2) — the ground truth for the
    fused device pipeline in ``kernels.policy_select``."""
    tu, tl = t_u[:, None], t_l[:, None]
    mus = (mu + sigma)[None, :]
    elig1 = (mus < tu) & ((mu - sigma)[None, :] < tl)
    has_base = elig1.any(axis=1)
    base = jnp.argmin(jnp.where(elig1, rank[None, :], pad_rank + 1.0),
                      axis=1).astype(jnp.int32)
    half = jnp.abs(t_l - mu[base]) + sigma[base]
    lo, hi = (t_l - half)[:, None], (t_l + half)[:, None]
    natural = (lo <= mu[None, :]) & (mu[None, :] <= hi) & (mus < tu)
    eligible = natural | (jnp.arange(mu.shape[0])[None, :] == base[:, None])
    eligible &= has_base[:, None]
    return base, has_base, eligible


def rglru_scan_ref(a, b):
    """Sequential linear recurrence h_t = a_t h_{t-1} + b_t. a,b: (B,S,W)."""
    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)

    def step(h, t):
        at, bt = t
        h = at * h + bt
        return h, h

    h0 = jnp.zeros((a.shape[0], a.shape[2]), jnp.float32)
    _, hs = jax.lax.scan(step, h0, (jnp.moveaxis(af, 1, 0), jnp.moveaxis(bf, 1, 0)))
    return jnp.moveaxis(hs, 0, 1).astype(a.dtype)
