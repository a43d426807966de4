"""Mixture-of-Experts FFN: routing, the served held-expert layer, and the
GShard-style dispatch of the training path.

Routing (``route``) runs over all ``n_experts``: softmax scores, or
sigmoid scores whose top-k is chosen on the scores plus a correction
bias while the gates stay the unbiased scores (DeepSeek-V3's
``noaux_tc`` with one group).  The chosen gates are renormalised to sum
1 and multiplied by ``routed_scale``.

The served layer (``held_ffn_grouped`` for prefill, ``held_ffn_decode``
for a decode step) holds experts ``[0, n_held)`` — one chip's share of
an expert-parallel deployment, or all of them — and computes the part of
the result that they give, dropless: every (token, k) assignment to a
held expert is computed, none is dropped.  Assignments to experts held
elsewhere are left out; on one chip the layer runs without the
exchange that would bring their part.  Shared experts run for every
token.  Prefill sorts the assignments by expert and runs grouped
matmuls (``lax.ragged_dot``); a decode step visits each held expert
under a ``lax.cond`` so that an expert no token of the step chose is not
read.  Both return counters — held experts that ran (received at least
one token), assignments to held experts, and all assignments — and the
experts each token chose, which the model keeps in its cache per
position and layer (``route``) so that a run's routing can be checked.

The training path (``moe_ffn``) keeps GShard's one-hot dispatch with a
static per-group capacity, the formulation GSPMD partitions well
(expert-sharded weights turn the dispatch/combine einsums into
all-to-alls); capacity overflow drops tokens (Switch behaviour).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import shard
from repro.models.layers import ParamSpec, mlp_apply, mlp_template


def moe_template(cfg: ModelConfig) -> dict:
    d, e = cfg.d_model, cfg.moe
    t = {
        # scaled by its fan-in (d_model), so routing logits are O(1)
        "router": ParamSpec((d, e.n_experts), ("embed_fsdp", None), "normal", -2),
        "experts": {
            "wi": ParamSpec((e.held, d, e.d_ff_expert), ("experts", "embed_fsdp", "expert_ff")),
            "wg": ParamSpec((e.held, d, e.d_ff_expert), ("experts", "embed_fsdp", "expert_ff")),
            "wo": ParamSpec((e.held, e.d_ff_expert, d), ("experts", "expert_ff", "embed_fsdp"),
                            "normal_out", 1),
        },
    }
    if e.scoring == "sigmoid":
        t["bias"] = ParamSpec((e.n_experts,), (None,), "zeros")
    if e.n_shared:
        t["shared"] = mlp_template(d, e.n_shared * e.d_ff_expert, "swiglu")
    return t


def route(params, x, cfg: ModelConfig):
    """x: (T, D) → (idx (T, K) int32, gates (T, K) f32, probs (T, E) f32).
    ``probs`` are the scores normalised over all experts (for the aux
    loss)."""
    e = cfg.moe
    logits = jnp.einsum("td,de->te", x, params["router"],
                        preferred_element_type=jnp.float32)
    if e.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(scores + params["bias"].astype(jnp.float32), e.top_k)
        gates = jnp.take_along_axis(scores, idx, axis=-1)
        probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(scores, e.top_k)
        probs = scores
    gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    return idx, gates * e.routed_scale, probs


def _counts(idx, n_held: int):
    """Assignments per held expert, (n_held,) int32."""
    return jnp.zeros((n_held,), jnp.int32).at[idx.reshape(-1)].add(1, mode="drop")


def _stats(counts, idx):
    """[held experts that ran, assignments to held experts, assignments]."""
    return jnp.stack([jnp.sum(counts > 0, dtype=jnp.int32), jnp.sum(counts),
                      jnp.int32(idx.size)])


def _shared(params, x, cfg):
    if not cfg.moe.n_shared:
        return 0
    with jax.named_scope("moe.shared"):
        return mlp_apply(params["shared"], x, "swiglu")


def held_ffn_grouped(params, x, cfg: ModelConfig):
    """Prefill: x (B, S, D) → (out (B, S, D), counters int32[3], chosen
    experts (B, S, K) int32).  The assignments to held experts are sorted
    by expert and run as grouped matmuls; the rest of the sorted rows
    belong to no group."""
    e = cfg.moe
    B, S, D = x.shape
    xf = x.reshape(B * S, D)
    with jax.named_scope("moe.route"):
        idx, gates, _ = route(params, xf, cfg)
        counts = _counts(idx, e.held)
    with jax.named_scope("moe.held"):
        flat = idx.reshape(-1)
        held = flat < e.held
        order = jnp.argsort(jnp.where(held, flat, e.held), stable=True)
        tok = order // e.top_k
        xs = jnp.take(xf, tok, axis=0)
        w = params["experts"]
        h = jax.lax.ragged_dot(xs, w["wi"], counts)
        g = jax.lax.ragged_dot(xs, w["wg"], counts)
        o = jax.lax.ragged_dot(jax.nn.silu(h) * g, w["wo"], counts)
        gate = jnp.where(held[order], gates.reshape(-1)[order], 0.0)
        o = jnp.where(held[order][:, None], o.astype(jnp.float32), 0.0)
        out = jnp.zeros((B * S, D), jnp.float32).at[tok].add(o * gate[:, None])
        out = out.astype(x.dtype).reshape(B, S, D)
    return out + _shared(params, x, cfg), _stats(counts, idx), idx.reshape(B, S, -1)


def _expert(w, layer, e):
    """Expert ``e`` of a held-expert leaf, of layer ``layer`` where the
    leaf is stacked over layers (``layer`` not None)."""
    if layer is None:
        return jax.lax.dynamic_index_in_dim(w, e, 0, keepdims=False)
    return jax.lax.dynamic_slice(w, (layer, e) + (0,) * (w.ndim - 2),
                                 (1, 1) + w.shape[2:])[0, 0]


def held_ffn_decode(params, x, cfg: ModelConfig, experts, layer=None):
    """Decode: x (B, 1, D) → (out, counters int32[3], chosen experts
    (B, 1, K) int32).  ``experts`` is the held-expert leaves (``{"wi",
    "wg", "wo"}``), stacked over layers where ``layer`` indexes them;
    each expert is sliced inside its own ``lax.cond`` branch, which runs
    only where some token chose it."""
    e = cfg.moe
    B, S, D = x.shape
    xf = x.reshape(B * S, D)
    with jax.named_scope("moe.route"):
        idx, gates, _ = route(params, xf, cfg)
        counts = _counts(idx, e.held)
        # (T, held): each token's gate on each held expert (0: not chosen)
        wtok = jnp.sum(jnp.where(idx[..., None] == jnp.arange(e.held),
                                 gates[..., None], 0.0), axis=1)
    with jax.named_scope("moe.held"):
        def one(i, out):
            def run(out):
                wi, wg, wo = (_expert(experts[k], layer, i) for k in ("wi", "wg", "wo"))
                h = jax.nn.silu(xf @ wi) * (xf @ wg)
                return out + (h @ wo).astype(jnp.float32) * wtok[:, i, None]
            return jax.lax.cond(counts[i] > 0, run, lambda o: o, out)

        out = jax.lax.fori_loop(0, e.held, one, jnp.zeros((B * S, D), jnp.float32))
        out = out.astype(x.dtype).reshape(B, S, D)
    return out + _shared(params, x, cfg), _stats(counts, idx), idx.reshape(B, S, -1)


def _capacity(group: int, top_k: int, n_experts: int, factor: float) -> int:
    c = int(group * top_k * factor / n_experts)
    return max(4, ((c + 3) // 4) * 4)


def moe_ffn(params, x, cfg: ModelConfig):
    """Training path, all experts held: x (B, S, D) → (out (B, S, D),
    aux_loss scalar fp32)."""
    e = cfg.moe
    if e.held != e.n_experts:
        raise ValueError("the GShard dispatch runs whole expert layers only")
    B, S, D = x.shape
    T = B * S
    g = min(e.group_size, T)
    if T % g:  # pad the flattened token dim to a group multiple
        pad = g - T % g
        xf = jnp.pad(x.reshape(T, D), [(0, pad), (0, 0)])
        out, aux = moe_ffn(params, xf[None], cfg)
        return out[0, :T].reshape(B, S, D), aux
    G = T // g
    E, K = e.n_experts, e.top_k
    C = _capacity(g, K, E, e.capacity_factor)

    xg = x.reshape(G, g, D)
    xg = shard(xg, "batch", None, None)
    top_idx, top_gates, probs = route(params, xg.reshape(T, D), cfg)
    top_idx, top_gates = top_idx.reshape(G, g, K), top_gates.reshape(G, g, K)

    # Load-balancing aux loss (Switch): E * Σ_e fraction_e · mean_prob_e
    me = jnp.mean(probs, axis=0)
    one_hot_all = jax.nn.one_hot(top_idx, E, dtype=jnp.float32)  # (G,g,K,E)
    ce = jnp.mean(jnp.sum(one_hot_all, axis=2), axis=(0, 1)) / K
    aux_loss = E * jnp.sum(me * ce)

    # Position of each (token, k) entry within its expert, token-major,
    # k-minor priority (GShard).
    ohf = one_hot_all.reshape(G, g * K, E)
    pos = jnp.cumsum(ohf, axis=1) - ohf  # entries ahead of this one
    pos = jnp.sum(pos * ohf, axis=-1).reshape(G, g, K)  # (G, g, K)
    keep = pos < C

    gate_kept = top_gates * keep  # dropped entries contribute nothing
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), C,
                            dtype=jnp.float32) * keep[..., None]
    # combine[G,g,E,C] = Σ_k gate · 1[expert] · 1[slot]
    combine = jnp.einsum("GgKE,GgKC->GgEC", one_hot_all * gate_kept[..., None], pos_oh)
    dispatch = (combine > 0).astype(x.dtype)

    w = params["experts"]
    expert_in = jnp.einsum("GgEC,Ggd->EGCd", dispatch, xg)
    expert_in = shard(expert_in, "experts", "batch", None, None)
    h = jnp.einsum("EGCd,Edf->EGCf", expert_in, w["wi"])
    hg = jnp.einsum("EGCd,Edf->EGCf", expert_in, w["wg"])
    h = jax.nn.silu(h) * hg
    h = shard(h, "experts", "batch", None, "expert_ff")
    expert_out = jnp.einsum("EGCf,Efd->EGCd", h, w["wo"])
    out = jnp.einsum("GgEC,EGCd->Ggd", combine.astype(x.dtype), expert_out)
    return out.reshape(B, S, D) + _shared(params, x, cfg), aux_loss


def moe_ffn_dense_eval(params, x, cfg: ModelConfig):
    """Dropless oracle: every token computed by every held expert,
    weighted by its gate on it (0 where not chosen), plus the shared
    experts.  O(held) FLOPs — for tests only."""
    e = cfg.moe
    B, S, D = x.shape
    idx, gates, _ = route(params, x.reshape(B * S, D), cfg)
    w = jnp.sum(jax.nn.one_hot(idx, e.n_experts, dtype=jnp.float32)
                * gates[..., None], axis=-2)[:, :e.held].reshape(B, S, e.held)
    p = params["experts"]
    h = jnp.einsum("bsd,Edf->bsEf", x, p["wi"])
    hg = jnp.einsum("bsd,Edf->bsEf", x, p["wg"])
    h = jax.nn.silu(h) * hg
    o = jnp.einsum("bsEf,Efd->bsEd", h, p["wo"])
    return jnp.einsum("bsE,bsEd->bsd", w.astype(x.dtype), o) + _shared(params, x, cfg)
