"""Mixture-of-Experts FFN with GShard-style one-hot dispatch.

Tokens are reshaped into fixed-size groups and dispatched to experts via
one-hot einsums with a static per-group capacity.  This is the formulation
GSPMD partitions well: expert-sharded weights (E over the `model` axis)
turn the dispatch/combine einsums into all-to-alls.  Capacity overflow
drops tokens (residual passes them through) — standard Switch behaviour.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import shard
from repro.models.layers import ParamSpec


def moe_template(cfg: ModelConfig) -> dict:
    d, e = cfg.d_model, cfg.moe
    return {
        "router": ParamSpec((d, e.n_experts), ("embed_fsdp", None)),
        "wi": ParamSpec((e.n_experts, d, e.d_ff_expert), ("experts", "embed_fsdp", "expert_ff")),
        "wg": ParamSpec((e.n_experts, d, e.d_ff_expert), ("experts", "embed_fsdp", "expert_ff")),
        "wo": ParamSpec((e.n_experts, e.d_ff_expert, d), ("experts", "expert_ff", "embed_fsdp"), "normal_out", 1),
    }


def _capacity(group: int, top_k: int, n_experts: int, factor: float) -> int:
    c = int(group * top_k * factor / n_experts)
    return max(4, ((c + 3) // 4) * 4)


def moe_ffn(params, x, cfg: ModelConfig):
    """x: (B, S, D) → (out (B, S, D), aux_loss scalar fp32)."""
    e = cfg.moe
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
    logits = jnp.einsum("Ggd,de->Gge", xg, params["router"],
                        preferred_element_type=jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)  # (G, g, E) fp32

    top_gates, top_idx = jax.lax.top_k(gates, K)  # (G, g, K)
    top_gates = top_gates / jnp.sum(top_gates, axis=-1, keepdims=True)

    # Load-balancing aux loss (Switch): E * Σ_e fraction_e · mean_gate_e
    me = jnp.mean(gates, axis=(0, 1))
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

    expert_in = jnp.einsum("GgEC,Ggd->EGCd", dispatch, xg)
    expert_in = shard(expert_in, "experts", "batch", None, None)
    h = jnp.einsum("EGCd,Edf->EGCf", expert_in, params["wi"])
    hg = jnp.einsum("EGCd,Edf->EGCf", expert_in, params["wg"])
    h = jax.nn.silu(h) * hg
    h = shard(h, "experts", "batch", None, "expert_ff")
    expert_out = jnp.einsum("EGCf,Efd->EGCd", h, params["wo"])
    out = jnp.einsum("GgEC,EGCd->Ggd", combine.astype(x.dtype), expert_out)
    return out.reshape(B, S, D), aux_loss


def moe_ffn_dense_eval(params, x, cfg: ModelConfig):
    """Dropless oracle: every token computed by all experts, weighted by its
    (renormalized) top-k gates.  O(E) FLOPs — for tests only."""
    e = cfg.moe
    logits = jnp.einsum("bsd,de->bse", x, params["router"],
                        preferred_element_type=jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)
    top_gates, top_idx = jax.lax.top_k(gates, e.top_k)
    top_gates = top_gates / jnp.sum(top_gates, axis=-1, keepdims=True)
    w = jnp.sum(jax.nn.one_hot(top_idx, e.n_experts, dtype=jnp.float32)
                * top_gates[..., None], axis=-2)  # (B,S,E)
    h = jnp.einsum("bsd,Edf->bsEf", x, params["wi"])
    hg = jnp.einsum("bsd,Edf->bsEf", x, params["wg"])
    h = jax.nn.silu(h) * hg
    o = jnp.einsum("bsEf,Efd->bsEd", h, params["wo"])
    return jnp.einsum("bsE,bsEd->bsd", w.astype(x.dtype), o)
