"""Attention blocks: GQA, causal/sliding-window, prefill KV caches, decode.

The XLA path is q-chunked (``lax.map`` over query blocks) so 32k-token
prefills never materialize (S, S) score matrices; sliding-window layers use
banded KV slices so their FLOPs scale with S·window, not S².  On TPU the
Pallas kernels in ``repro.kernels`` replace the inner computation via
``shard_map`` (see repro/distributed); this module is the portable,
GSPMD-shardable fallback the dry-run lowers.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import shard
from repro.models import runtime_flags
from repro.models.layers import ParamSpec, rope

NEG_INF = -1e30


def _chunk_loop(fn, n_chunks):
    """lax.map over chunk indices, or an unrolled Python loop when the
    dry-run's scan-calibration flag is set (static ints then enable causal
    block skipping with exact static bounds)."""
    if runtime_flags.UNROLL_SCANS:
        outs = [fn(i) for i in range(n_chunks)]
        return jnp.stack(outs, axis=0)
    return jax.lax.map(fn, jnp.arange(n_chunks))


def attn_template(cfg: ModelConfig) -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, kv = cfg.n_heads, cfg.n_kv_heads
    t = {
        "wq": ParamSpec((d, h * hd), ("embed_fsdp", "heads_merged")),
        "wk": ParamSpec((d, kv * hd), ("embed_fsdp", "kv_merged")),
        "wv": ParamSpec((d, kv * hd), ("embed_fsdp", "kv_merged")),
        "wo": ParamSpec((h * hd, d), ("heads_merged", "embed_fsdp"), "normal_out", 0),
    }
    if cfg.qkv_bias:
        t["bq"] = ParamSpec((h * hd,), (None,), "zeros")
        t["bk"] = ParamSpec((kv * hd,), (None,), "zeros")
        t["bv"] = ParamSpec((kv * hd,), (None,), "zeros")
    return t


def _project_qkv(params, x, cfg: ModelConfig):
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dh->bsh", x, params["wq"])
    k = jnp.einsum("bsd,dh->bsh", x, params["wk"])
    v = jnp.einsum("bsd,dh->bsh", x, params["wv"])
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    q = q.reshape(B, S, cfg.n_heads, hd)
    k = k.reshape(B, S, cfg.n_kv_heads, hd)
    v = v.reshape(B, S, cfg.n_kv_heads, hd)
    return q, k, v


def _sdpa_block(q, k, v, mask, head_dim):
    """One (q-block × kv-block) grouped-query attention tile, fp32 softmax.

    q: (B, cq, KV, G, hd); k/v: (B, ck, KV, hd); mask: (B|1, cq, ck) bool.
    """
    scale = head_dim ** -0.5
    s = jnp.einsum("bqngh,bknh->bngqk", q, k, preferred_element_type=jnp.float32)
    s = s * scale
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bngqk,bknh->bqngh", p.astype(v.dtype), v)
    return out


def _grouped(q, n_kv):
    B, S, H, hd = q.shape
    return q.reshape(B, S, n_kv, H // n_kv, hd)


def attention_full(q, k, v, q_positions, kv_positions, *, causal=True,
                   q_chunk=2048, kv_chunk=2048, dynamic_skip=False):
    """Causal full attention: q-chunked outer loop × online-softmax kv
    scan (flash attention in portable XLA).  q: (B,Sq,H,hd); k:
    (B,Skv,KV,hd); v: (B,Skv,KV,hv), whose width may differ from hd.  Never materializes (Sq, Skv) scores: per (q-block,
    kv-block) tiles are fp32 but transient, the carried state is
    (m, l, acc).

    positions: (B, S) absolute token positions (rows beyond a sequence's
    length should carry position < 0 to be masked)."""
    B, Sq, H, hd = q.shape
    KV, Skv = k.shape[2], k.shape[1]
    if runtime_flags.Q_CHUNK_OVERRIDE:
        q_chunk = runtime_flags.Q_CHUNK_OVERRIDE
    if runtime_flags.KV_CHUNK_OVERRIDE:
        kv_chunk = runtime_flags.KV_CHUNK_OVERRIDE
    cq = min(q_chunk, Sq)
    if Sq % cq:  # pad queries (position −1 ⇒ fully masked), trim after
        pad = cq - Sq % cq
        qp = jnp.pad(q, [(0, 0), (0, pad), (0, 0), (0, 0)])
        pp = jnp.pad(q_positions, [(0, 0), (0, pad)], constant_values=-1)
        out = attention_full(qp, k, v, pp, kv_positions, causal=causal,
                             q_chunk=q_chunk, kv_chunk=kv_chunk)
        return out[:, :Sq]
    ck = min(kv_chunk, Skv)
    if Skv % ck:  # pad kv (position −1 ⇒ masked everywhere)
        pad = ck - Skv % ck
        kp = jnp.pad(k, [(0, 0), (0, pad), (0, 0), (0, 0)])
        vp = jnp.pad(v, [(0, 0), (0, pad), (0, 0), (0, 0)])
        pp = jnp.pad(kv_positions, [(0, 0), (0, pad)], constant_values=-1)
        return attention_full(q, kp, vp, q_positions, pp, causal=causal,
                              q_chunk=q_chunk, kv_chunk=kv_chunk)
    qg = _grouped(q, KV)
    n_q = Sq // cq
    n_k = Skv // ck
    G = H // KV
    hv = v.shape[-1]
    scale = hd ** -0.5

    # kv blocks as scan xs: (n_k, B, ck, KV, hd|hv)
    kb = jnp.moveaxis(k.reshape(B, n_k, ck, KV, hd), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, n_k, ck, KV, hv), 1, 0)
    pb = jnp.moveaxis(kv_positions.reshape(B, n_k, ck), 1, 0)

    def one_q_chunk(i):
        qs = jax.lax.dynamic_slice_in_dim(qg, i * cq, cq, axis=1).astype(jnp.float32)
        qpos = jax.lax.dynamic_slice_in_dim(q_positions, i * cq, cq, axis=1)

        def kv_step(carry, xs):
            m, l, acc = carry
            kblk, vblk, pblk = xs
            s = jnp.einsum("bqngh,bknh->bngqk", qs, kblk.astype(jnp.float32),
                           preferred_element_type=jnp.float32) * scale
            if causal:
                mask = pblk[:, None, :] <= qpos[:, :, None]
            else:
                mask = jnp.broadcast_to(pblk[:, None, :] >= 0,
                                        (B, cq, ck))
            mask = jnp.logical_and(mask, pblk[:, None, :] >= 0)
            s = jnp.where(mask[:, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bngqk,bknh->bngqh", p, vblk.astype(jnp.float32))
            return (m_new, l, acc), 0

        m0 = jnp.full((B, KV, G, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, cq), jnp.float32)
        a0 = jnp.zeros((B, KV, G, cq, hv), jnp.float32)
        # Causal block skipping: q-chunk i only needs kv blocks covering
        # positions ≤ (i+1)·cq − 1 (standard contiguous positions; the
        # elementwise mask still guards exactness).  Halves attention
        # FLOPs/bytes vs masked-full.  The dynamic-bound loop is not
        # reverse-differentiable, so the train path keeps the full scan
        # (dynamic_skip=False) while prefill opts in.
        skip = causal and Sq == Skv and (
            dynamic_skip or runtime_flags.UNROLL_SCANS)
        if n_k == 1:
            (m, l, acc), _ = kv_step((m0, l0, a0), (kb[0], vb[0], pb[0]))
        elif runtime_flags.UNROLL_SCANS:
            carry = (m0, l0, a0)
            hi = min(n_k, (i * cq) // ck + (cq + ck - 1) // ck) \
                if (skip and isinstance(i, int)) else n_k
            for j in range(hi):
                carry, _ = kv_step(carry, (kb[j], vb[j], pb[j]))
            m, l, acc = carry
        elif skip:
            hi = jnp.minimum((i * cq) // ck + (cq + ck - 1) // ck, n_k)

            def fori_body(j, carry):
                xs = jax.tree.map(lambda a: a[j], (kb, vb, pb))
                return kv_step(carry, xs)[0]

            m, l, acc = jax.lax.fori_loop(0, hi, fori_body, (m0, l0, a0))
        else:
            (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (kb, vb, pb))
        out = acc / jnp.maximum(l, 1e-30)[..., None]  # (B,KV,G,cq,hv)
        return jnp.moveaxis(out, 3, 1).astype(q.dtype)  # (B,cq,KV,G,hv)

    if n_q == 1:
        out = one_q_chunk(0)
    else:
        out = _chunk_loop(one_q_chunk, n_q)
        out = jnp.moveaxis(out, 0, 1).reshape(B, Sq, KV, G, hv)
    return out.reshape(B, Sq, H, hv)


def attention_windowed(q, k, v, q_positions, kv_positions, *, window, q_chunk=512):
    """Sliding-window causal attention with banded KV slices: each q-chunk
    only reads KV in [chunk_start - window, chunk_end) so FLOPs are
    O(S · (window + chunk)) rather than O(S²)."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    if runtime_flags.Q_CHUNK_OVERRIDE:
        q_chunk = runtime_flags.Q_CHUNK_OVERRIDE
    cq = min(q_chunk, Sq)
    if Sq % cq:
        pad = cq - Sq % cq
        qp = jnp.pad(q, [(0, 0), (0, pad), (0, 0), (0, 0)])
        pp = jnp.pad(q_positions, [(0, 0), (0, pad)], constant_values=-1)
        out = attention_windowed(qp, k, v, pp, kv_positions, window=window,
                                 q_chunk=q_chunk)
        return out[:, :Sq]
    qg = _grouped(q, KV)
    n_chunks = Sq // cq
    band = window + cq

    # Front-pad KV by `window` so every band slice is in range.
    pad = [(0, 0), (window, 0), (0, 0), (0, 0)]
    kp = jnp.pad(k, pad)
    vp = jnp.pad(v, pad)
    posp = jnp.pad(kv_positions, [(0, 0), (window, 0)], constant_values=-1)

    def one_chunk(i):
        start = i * cq  # band starts at (chunk_start - window) + window pad
        qs = jax.lax.dynamic_slice_in_dim(qg, i * cq, cq, axis=1)
        qp = jax.lax.dynamic_slice_in_dim(q_positions, i * cq, cq, axis=1)
        ks = jax.lax.dynamic_slice_in_dim(kp, start, band, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(vp, start, band, axis=1)
        bp = jax.lax.dynamic_slice_in_dim(posp, start, band, axis=1)
        mask = (bp[:, None, :] <= qp[:, :, None]) & (
            bp[:, None, :] > qp[:, :, None] - window) & (bp[:, None, :] >= 0)
        return _sdpa_block(qs, ks, vs, mask, hd)

    if n_chunks == 1:
        out = one_chunk(0)
    else:
        out = _chunk_loop(one_chunk, n_chunks)
        out = jnp.moveaxis(out, 0, 1).reshape(B, Sq, KV, H // KV, hd)
    return out.reshape(B, Sq, H, hd)


# ----------------------------------------------------------------------
# KV caches
# ----------------------------------------------------------------------
class AttnCache(NamedTuple):
    k: jax.Array  # (B, C, KV, hd) — C = full length (global) or window (local)
    v: jax.Array


def cache_template(cfg: ModelConfig, kind: str, batch: int, cache_len: int) -> dict:
    hd, kv = cfg.resolved_head_dim, cfg.n_kv_heads
    C = min(cache_len, cfg.window) if kind == "local" else cache_len
    ax = ("batch", "cache_seq", "kv_heads", None)
    if cfg.kv_cache_dtype == "int8":
        # per-(batch, slot, kv-head) scaled int8 storage: halves the cache
        # footprint (the decode-capacity lever); scales are tiny fp32.
        return {
            "k": ParamSpec((batch, C, kv, hd), ax, "zeros", dtype="int8"),
            "v": ParamSpec((batch, C, kv, hd), ax, "zeros", dtype="int8"),
            "k_scale": ParamSpec((batch, C, kv), ax[:3], "zeros", dtype="float32"),
            "v_scale": ParamSpec((batch, C, kv), ax[:3], "zeros", dtype="float32"),
        }
    return {
        "k": ParamSpec((batch, C, kv, hd), ax, "zeros"),
        "v": ParamSpec((batch, C, kv, hd), ax, "zeros"),
    }


def _quantize_kv(x):
    """x: (..., hd) → (int8, f32 scale over the trailing dim)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize_kv(q, scale, dtype):
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def prefill_cache(cfg: ModelConfig, kind: str, k, v, cache_len: int):
    """Build the cache after a full prefill of S tokens (RoPE already applied
    to k).  Local layers keep a ring of the last `window` positions, stored
    at slot = position % window."""
    B, S = k.shape[:2]
    if kind == "local" and cfg.window < cache_len:
        W = cfg.window
        slots = jnp.arange(W)
        # latest position p < S with p % W == slot
        pos = (S - 1) - ((S - 1 - slots) % W)
        cache = {"k": jnp.take(k, pos, axis=1), "v": jnp.take(v, pos, axis=1)}
    elif kind == "local":
        W = min(cfg.window, cache_len)
        if S < W:
            pad = [(0, 0), (0, W - S), (0, 0), (0, 0)]
            cache = {"k": jnp.pad(k, pad), "v": jnp.pad(v, pad)}
        else:
            cache = {"k": k[:, :W], "v": v[:, :W]}
    else:
        if S < cache_len:
            pad = [(0, 0), (0, cache_len - S), (0, 0), (0, 0)]
            k, v = jnp.pad(k, pad), jnp.pad(v, pad)
        cache = {"k": k, "v": v}
    if cfg.kv_cache_dtype == "int8":
        qk, sk = _quantize_kv(cache["k"])
        qv, sv = _quantize_kv(cache["v"])
        cache = {"k": qk, "v": qv, "k_scale": sk, "v_scale": sv}
    return cache


def decode_attention(params, cache, x, pos, cfg: ModelConfig, kind: str):
    """One decode step. x: (B, 1, D); pos: (B,) absolute position of the new
    token. Returns (attn_out (B,1,D), new_cache)."""
    B = x.shape[0]
    hd = cfg.resolved_head_dim
    q, k_new, v_new = _project_qkv(params, x, cfg)
    if cfg.use_rope:
        q = rope(q, pos[:, None], cfg.rope_theta)
        k_new = rope(k_new, pos[:, None], cfg.rope_theta)

    C = cache["k"].shape[1]
    if kind == "local":
        slot = pos % C
    else:
        slot = pos

    def write(c, t, s):
        return jax.lax.dynamic_update_slice(c, t, (s,) + (0,) * (c.ndim - 1))

    int8_kv = cfg.kv_cache_dtype == "int8"
    new_cache = {}
    if int8_kv:
        qk, sk = _quantize_kv(k_new)
        qv, sv = _quantize_kv(v_new)
        new_cache["k"] = jax.vmap(write)(cache["k"], qk, slot)
        new_cache["v"] = jax.vmap(write)(cache["v"], qv, slot)
        new_cache["k_scale"] = jax.vmap(write)(cache["k_scale"], sk, slot)
        new_cache["v_scale"] = jax.vmap(write)(cache["v_scale"], sv, slot)
        new_k = _dequantize_kv(new_cache["k"], new_cache["k_scale"], x.dtype)
        new_v = _dequantize_kv(new_cache["v"], new_cache["v_scale"], x.dtype)
    else:
        new_k = jax.vmap(write)(cache["k"], k_new, slot)
        new_v = jax.vmap(write)(cache["v"], v_new, slot)
        new_cache = {"k": new_k, "v": new_v}

    # Slot-absolute positions for masking / validity.
    slots = jnp.arange(C)[None, :]
    if kind == "local":
        slot_pos = pos[:, None] - ((pos[:, None] - slots) % C)
    else:
        slot_pos = jnp.broadcast_to(slots, (B, C))
    valid = (slot_pos >= 0) & (slot_pos <= pos[:, None]) & (
        slot_pos > pos[:, None] - (cfg.window if kind == "local" else C + 1))

    KV = cfg.n_kv_heads
    qg = q.reshape(B, KV, cfg.n_heads // KV, hd)
    s = jnp.einsum("bngh,bknh->bngk", qg, new_k, preferred_element_type=jnp.float32)
    s = s * (hd ** -0.5)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bngk,bknh->bngh", p.astype(new_v.dtype), new_v)
    out = out.reshape(B, 1, cfg.n_heads * hd)
    out = jnp.einsum("bsh,hd->bsd", out, params["wo"])
    return out, new_cache


def prefill_attention(params, x, positions, cfg: ModelConfig, kind: str,
                      cache_len: Optional[int] = None, cross_kv=None):
    """Full-sequence attention (train or prefill).

    Returns (out (B,S,D), cache_or_None)."""
    q, k, v = _project_qkv(params, x, cfg)
    if cross_kv is None:
        if cfg.use_rope:
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
        k = shard(k, "batch", "seq", "kv_heads", None)
        v = shard(v, "batch", "seq", "kv_heads", None)
        if kind == "local":
            out = attention_windowed(q, k, v, positions, positions, window=cfg.window)
        else:
            # prefill (cache_len set) has no backward pass ⇒ enable the
            # dynamic causal block skip; train keeps the scan path.
            out = attention_full(q, k, v, positions, positions, causal=True,
                                 dynamic_skip=cache_len is not None)
    else:
        ck, cv, cpos = cross_kv
        out = attention_full(q, ck, cv, positions, cpos, causal=False)
        k, v = ck, cv
    out = shard(out, "batch", "seq", "heads", None)
    B, S = x.shape[:2]
    out = out.reshape(B, S, -1)
    out = jnp.einsum("bsh,hd->bsd", out, params["wo"])
    cache = None
    if cache_len is not None and cross_kv is None:
        cache = prefill_cache(cfg, kind, k, v, cache_len)
    return out, cache
