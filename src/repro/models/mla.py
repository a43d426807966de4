"""Multi-head latent attention (DeepSeek-V2/V3, as Moonlight runs it).

Per token, ``kv_a = x · W_kva`` has ``kv_lora_rank + qk_rope_head_dim``
values.  The first ``kv_lora_rank`` are RMS-normed into the latent
``c_kv``; the last ``qk_rope_head_dim`` are rotated into ``k_pe``, one
rotary key that every head shares.  ``W_kvb`` rebuilds each head's
``k_nope`` and ``v`` from ``c_kv``.  Queries come from one full
projection (``q_lora_rank`` is None) and split into a ``qk_nope`` part and
a rotated ``qk_rope`` part.  A head's key is ``[k_nope, k_pe]``; scores
take the scale ``1/sqrt(qk_nope + qk_rope)``.

The cache holds ``c_kv`` and ``k_pe`` per position, not keys and values
per head.  Prefill attends in the expanded form (keys and values built
per head, the flash loop of ``attention.attention_full``).  Decode
attends in the absorbed form: ``q_nope · W_kb`` is scored against
``c_kv`` directly and ``W_vb`` is applied after the weighted sum, so a
step reads the latent cache and never rebuilds keys.

Rotary convention: ``layers.rope``'s rotate-half pairing over the
``qk_rope_head_dim`` values (value ``i`` with ``i + 32``), frequencies
``theta^(-2i/64)``, positions the token indices.  The published
checkpoint pairs interleaved values; on weights drawn at random the two
differ only by a fixed permutation of ``W_q``'s and ``W_kva``'s rope
columns.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.attention import NEG_INF, attention_full
from repro.models.layers import ParamSpec, norm_template, rmsnorm, rope


def mla_template(cfg: ModelConfig) -> dict:
    a, d, H = cfg.mla, cfg.d_model, cfg.n_heads
    return {
        "wq": ParamSpec((d, H * a.qk_head_dim), ("embed_fsdp", "heads_merged")),
        "wkv_a": ParamSpec((d, a.kv_lora_rank + a.qk_rope_head_dim),
                           ("embed_fsdp", "kv_lora")),
        "kv_norm": norm_template(a.kv_lora_rank),
        "wkv_b": ParamSpec((a.kv_lora_rank, H * (a.qk_nope_head_dim + a.v_head_dim)),
                           ("kv_lora", "heads_merged")),
        "wo": ParamSpec((H * a.v_head_dim, d), ("heads_merged", "embed_fsdp"),
                        "normal_out", 0),
    }


def cache_template(cfg: ModelConfig, batch: int, cache_len: int) -> dict:
    a = cfg.mla
    ax = ("batch", "cache_seq", None)
    return {"c_kv": ParamSpec((batch, cache_len, a.kv_lora_rank), ax, "zeros"),
            "k_pe": ParamSpec((batch, cache_len, a.qk_rope_head_dim), ax, "zeros")}


def _project(params, x, positions, cfg: ModelConfig):
    """x (B, S, D) → q_nope (B,S,H,n), q_pe (B,S,H,r), c_kv (B,S,L),
    k_pe (B,S,1,r)."""
    a = cfg.mla
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dh->bsh", x, params["wq"]).reshape(
        B, S, cfg.n_heads, a.qk_head_dim)
    q_nope, q_pe = q[..., :a.qk_nope_head_dim], q[..., a.qk_nope_head_dim:]
    q_pe = rope(q_pe, positions, cfg.rope_theta)
    kv_a = jnp.einsum("bsd,dl->bsl", x, params["wkv_a"])
    c_kv = rmsnorm(kv_a[..., :a.kv_lora_rank], params["kv_norm"]["scale"],
                   cfg.norm_eps)
    k_pe = rope(kv_a[..., None, a.kv_lora_rank:], positions, cfg.rope_theta)
    return q_nope, q_pe, c_kv, k_pe


def prefill_mla(params, x, positions, cfg: ModelConfig, cache_len=None):
    """Expanded-form causal attention over the whole sequence.
    Returns (out (B,S,D), cache or None)."""
    a, H = cfg.mla, cfg.n_heads
    B, S, _ = x.shape
    with jax.named_scope("mla.attend"):
        q_nope, q_pe, c_kv, k_pe = _project(params, x, positions, cfg)
        kv = jnp.einsum("bsl,lh->bsh", c_kv, params["wkv_b"]).reshape(
            B, S, H, a.qk_nope_head_dim + a.v_head_dim)
        k_nope, v = kv[..., :a.qk_nope_head_dim], kv[..., a.qk_nope_head_dim:]
        q = jnp.concatenate([q_nope, q_pe], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe, (B, S, H, a.qk_rope_head_dim))], -1)
        out = attention_full(q, k, v, positions, positions, causal=True,
                             dynamic_skip=cache_len is not None)
        out = jnp.einsum("bsh,hd->bsd", out.reshape(B, S, H * a.v_head_dim),
                         params["wo"])
    cache = None
    if cache_len is not None:
        pad = [(0, 0), (0, cache_len - S), (0, 0)]
        cache = {"c_kv": jnp.pad(c_kv, pad), "k_pe": jnp.pad(k_pe[:, :, 0], pad)}
    return out, cache


def decode_mla(params, cache, x, pos, cfg: ModelConfig):
    """One absorbed-form step.  x: (B, 1, D); pos: (B,) position of the
    new token.  Returns (out (B,1,D), new cache)."""
    a, H = cfg.mla, cfg.n_heads
    B = x.shape[0]
    with jax.named_scope("mla.attend"):
        q_nope, q_pe, c_new, kpe_new = _project(params, x, pos[:, None], cfg)

        def write(c, t, s):
            return jax.lax.dynamic_update_slice(c, t, (s, 0))

        c_kv = jax.vmap(write)(cache["c_kv"], c_new[:, 0:1], pos)
        k_pe = jax.vmap(write)(cache["k_pe"], kpe_new[:, 0:1, 0], pos)
        wkv_b = params["wkv_b"].reshape(a.kv_lora_rank, H,
                                        a.qk_nope_head_dim + a.v_head_dim)
        w_kb = wkv_b[..., :a.qk_nope_head_dim]
        w_vb = wkv_b[..., a.qk_nope_head_dim:]
        q_lat = jnp.einsum("bhn,lhn->bhl", q_nope[:, 0], w_kb,
                           preferred_element_type=jnp.float32).astype(x.dtype)
        s = (jnp.einsum("bhl,btl->bht", q_lat, c_kv,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bhr,btr->bht", q_pe[:, 0], k_pe,
                          preferred_element_type=jnp.float32))
        s = s * a.qk_head_dim ** -0.5
        valid = jnp.arange(c_kv.shape[1])[None, :] <= pos[:, None]
        p = jax.nn.softmax(jnp.where(valid[:, None, :], s, NEG_INF), axis=-1)
        o_lat = jnp.einsum("bht,btl->bhl", p.astype(c_kv.dtype), c_kv)
        o = jnp.einsum("bhl,lhv->bhv", o_lat, w_vb)
        out = jnp.einsum("bsh,hd->bsd", o.reshape(B, 1, H * a.v_head_dim),
                         params["wo"])
    return out, {"c_kv": c_kv, "k_pe": k_pe}
