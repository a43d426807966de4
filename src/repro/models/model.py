"""Model assembly: superblock-scanned decoder LMs, whisper enc-dec, VLM.

Layer stacks lower to ``lax.scan`` over *superblocks* (one repetition of the
config's block pattern) so HLO size — and XLA compile time — is independent
of depth.  The remainder layers (e.g. gemma3's 34 = 5×6 + 4) run unrolled
as the tail.  The same block code serves train, prefill and decode.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import shard
from repro.models import attention as attn
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import runtime_flags
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    ParamSpec, abstract, apply_norm, axes_tree, materialize, mlp_apply,
    mlp_template, norm_template, sinusoidal_pos, spec_map, stack_specs,
)

Params = Dict[str, Any]


# ======================================================================
# Templates
# ======================================================================
def block_template(cfg: ModelConfig, kind: str, dense: bool = False) -> dict:
    """One layer's parameters; ``dense`` gives a leading layer a dense
    MLP of width ``d_ff`` where the others route to experts."""
    d = cfg.d_model
    t = {"norm1": norm_template(d)}
    if kind in ("attn", "local", "enc"):
        t["attn"] = attn.attn_template(cfg)
    elif kind == "mla":
        t["attn"] = mla_mod.mla_template(cfg)
    elif kind == "xdec":
        t["attn"] = attn.attn_template(cfg)
        t["norm_x"] = norm_template(d)
        t["xattn"] = attn.attn_template(cfg)
    elif kind == "ssd":
        t["ssd"] = ssm_mod.ssd_template(cfg)
        return t  # mamba2 blocks carry no separate MLP
    elif kind == "rglru":
        t["rglru"] = rglru_mod.rglru_template(cfg)
    else:
        raise ValueError(kind)
    t["norm2"] = norm_template(d)
    t["mlp"] = (moe_mod.moe_template(cfg) if cfg.moe and not dense
                else mlp_template(d, cfg.d_ff, cfg.mlp))
    return t


def param_template(cfg: ModelConfig) -> dict:
    d, v = cfg.d_model, cfg.padded_vocab
    t: dict = {"embed": {"table": ParamSpec((v, d), ("vocab", "embed_fsdp"))}}
    if cfg.first_k_dense:
        t["lead"] = {f"l{i}": block_template(cfg, k, dense=True)
                     for i, k in enumerate(cfg.lead_kinds)}
    if cfg.n_superblocks > 0:
        t["blocks"] = {
            f"p{i}": stack_specs(block_template(cfg, decoder_kind(cfg, k)), cfg.n_superblocks)
            for i, k in enumerate(cfg.pattern)
        }
    t["tail"] = {
        f"t{i}": block_template(cfg, decoder_kind(cfg, k))
        for i, k in enumerate(cfg.tail_kinds)
    }
    t["final_norm"] = norm_template(d)
    if not cfg.tie_embeddings:
        t["lm_head"] = ParamSpec((d, v), ("embed_fsdp", "vocab"))
    if cfg.encdec is not None:
        t["encoder"] = {
            "blocks": stack_specs(block_template(cfg, "enc"), cfg.encdec.n_encoder_layers),
            "final_norm": norm_template(d),
        }
    return t


def decoder_kind(cfg: ModelConfig, kind: str) -> str:
    if cfg.encdec is not None and kind == "attn":
        return "xdec"
    return kind


def block_cache_template(cfg: ModelConfig, kind: str, batch: int, cache_len: int,
                         dense: bool = False) -> dict:
    """One layer's cache; an expert layer (not ``dense``) also keeps the
    experts each position chose (``route``, int8 expert ids)."""
    if cfg.moe is not None and not dense and kind != "ssd":
        c = block_cache_template(cfg, kind, batch, cache_len, dense=True)
        c["route"] = ParamSpec((batch, cache_len, cfg.moe.top_k),
                               ("batch", "cache_seq", None), "zeros", dtype="int8")
        return c
    if kind in ("attn", "local"):
        return attn.cache_template(cfg, kind, batch, cache_len)
    if kind == "mla":
        return mla_mod.cache_template(cfg, batch, cache_len)
    if kind == "xdec":
        c = attn.cache_template(cfg, "attn", batch, cache_len)
        hd, kv, F = cfg.resolved_head_dim, cfg.n_kv_heads, cfg.encdec.n_frames
        c["xk"] = ParamSpec((batch, F, kv, hd), ("batch", None, "kv_heads", None), "zeros")
        c["xv"] = ParamSpec((batch, F, kv, hd), ("batch", None, "kv_heads", None), "zeros")
        return c
    if kind == "ssd":
        return ssm_mod.ssd_cache_template(cfg, batch)
    if kind == "rglru":
        return rglru_mod.rglru_cache_template(cfg, batch)
    raise ValueError(kind)


# Rows of the on-device MoE counters carried in the cache: prefill, decode.
# Columns: held experts that ran (per layer), assignments to held experts,
# all top-k assignments — each summed over expert layers and steps.
MOE_COUNTS = ("prefill", "decode")


def cache_template(cfg: ModelConfig, batch: int, cache_len: int) -> dict:
    t: dict = {}
    if cfg.first_k_dense:
        t["lead"] = {f"l{i}": block_cache_template(cfg, k, batch, cache_len, dense=True)
                     for i, k in enumerate(cfg.lead_kinds)}
    if cfg.moe is not None:
        t["moe_counts"] = ParamSpec((len(MOE_COUNTS), 3), (None, None), "zeros",
                                    dtype="int32")
    if cfg.n_superblocks > 0:
        t["blocks"] = {
            f"p{i}": stack_specs(
                block_cache_template(cfg, decoder_kind(cfg, k), batch, cache_len),
                cfg.n_superblocks)
            for i, k in enumerate(cfg.pattern)
        }
    t["tail"] = {
        f"t{i}": block_cache_template(cfg, decoder_kind(cfg, k), batch, cache_len)
        for i, k in enumerate(cfg.tail_kinds)
    }
    return t


def init_params(cfg: ModelConfig, key, dtype=jnp.bfloat16) -> Params:
    return materialize(param_template(cfg), key, dtype)


def init_cache(cfg: ModelConfig, batch: int, cache_len: int, dtype=jnp.bfloat16):
    return spec_map(lambda s: jnp.zeros(s.shape, s.dtype or dtype),
                    cache_template(cfg, batch, cache_len))


# ======================================================================
# Block forward (train / prefill)
# ======================================================================
def _no_counts():
    return jnp.zeros((3,), jnp.int32)


def block_forward_full(cfg: ModelConfig, kind: str, p, x, positions, cache_len,
                       enc_out=None, enc_pos=None, dense: bool = False):
    """Returns (x, aux_loss, cache|None, MoE counters int32[3]).  With
    ``cache_len`` None (training) an expert layer takes the GShard
    dispatch, else (prefill) the dropless held-expert layer."""
    aux = jnp.zeros((), jnp.float32)
    counts = _no_counts()
    h = apply_norm(cfg.norm, x, p["norm1"]["scale"], cfg.norm_eps)
    cache = None
    if kind in ("attn", "local"):
        out, cache = attn.prefill_attention(p["attn"], h, positions, cfg, kind,
                                            cache_len=cache_len)
        x = x + out
    elif kind == "mla":
        out, cache = mla_mod.prefill_mla(p["attn"], h, positions, cfg, cache_len)
        x = x + out
    elif kind == "enc":
        q, k, v = attn._project_qkv(p["attn"], h, cfg)
        o = attn.attention_full(q, k, v, positions, positions, causal=False)
        B, S = h.shape[:2]
        x = x + jnp.einsum("bsh,hd->bsd", o.reshape(B, S, -1), p["attn"]["wo"])
    elif kind == "xdec":
        out, cache = attn.prefill_attention(p["attn"], h, positions, cfg, "attn",
                                            cache_len=cache_len)
        x = x + out
        hx = apply_norm(cfg.norm, x, p["norm_x"]["scale"], cfg.norm_eps)
        _, ek, ev = attn._project_qkv(p["xattn"], enc_out, cfg)
        xout, _ = attn.prefill_attention(p["xattn"], hx, positions, cfg, "attn",
                                         cross_kv=(ek, ev, enc_pos))
        x = x + xout
        if cache is not None:
            cache["xk"], cache["xv"] = ek, ev
    elif kind == "ssd":
        if cache_len is not None:
            out, cache = ssm_mod.ssd_block_apply(p["ssd"], h, cfg, return_cache=True)
        else:
            out = ssm_mod.ssd_block_apply(p["ssd"], h, cfg)
        return x + out, aux, cache, counts  # no MLP
    elif kind == "rglru":
        if cache_len is not None:
            out, cache = rglru_mod.rglru_prefill_cache(p["rglru"], h, cfg)
        else:
            out = rglru_mod.rglru_block_apply(p["rglru"], h, cfg)
        x = x + out
    else:
        raise ValueError(kind)

    h2 = apply_norm(cfg.norm, x, p["norm2"]["scale"], cfg.norm_eps)
    if cfg.moe is not None and kind != "enc" and not dense:
        if cache_len is None:
            mo, aux = moe_mod.moe_ffn(p["mlp"], h2, cfg)
        else:
            mo, counts, chosen = moe_mod.held_ffn_grouped(p["mlp"], h2, cfg)
            B, S = chosen.shape[:2]
            cache = dict(cache, route=jnp.pad(chosen.astype(jnp.int8),
                                              [(0, 0), (0, cache_len - S), (0, 0)]))
        x = x + mo
    else:
        x = x + mlp_apply(p["mlp"], h2, cfg.mlp)
    x = shard(x, "batch", "seq", None)
    return x, aux, cache, counts


def block_forward_decode(cfg: ModelConfig, kind: str, p, x, cache, pos,
                         dense: bool = False, experts=None, layer=None):
    """x: (B,1,D). Returns (x, new_cache, MoE counters int32[3]).  An
    expert layer takes its held experts from ``experts`` (stacked over
    layers where ``layer`` indexes them), not from ``p``."""
    counts = _no_counts()
    h = apply_norm(cfg.norm, x, p["norm1"]["scale"], cfg.norm_eps)
    if kind in ("attn", "local"):
        out, new_cache = attn.decode_attention(p["attn"], cache, h, pos, cfg, kind)
        x = x + out
    elif kind == "mla":
        out, new_cache = mla_mod.decode_mla(p["attn"], cache, h, pos, cfg)
        x = x + out
    elif kind == "xdec":
        self_cache = {"k": cache["k"], "v": cache["v"]}
        out, new_self = attn.decode_attention(p["attn"], self_cache, h, pos, cfg, "attn")
        x = x + out
        hx = apply_norm(cfg.norm, x, p["norm_x"]["scale"], cfg.norm_eps)
        x = x + _cross_decode(cfg, p["xattn"], hx, cache["xk"], cache["xv"])
        new_cache = dict(new_self, xk=cache["xk"], xv=cache["xv"])
    elif kind == "ssd":
        out, new_cache = ssm_mod.ssd_decode_step(p["ssd"], cache, h, cfg)
        return x + out, new_cache, counts
    elif kind == "rglru":
        out, new_cache = rglru_mod.rglru_decode_step(p["rglru"], cache, h, cfg)
        x = x + out
    else:
        raise ValueError(kind)

    h2 = apply_norm(cfg.norm, x, p["norm2"]["scale"], cfg.norm_eps)
    if cfg.moe is not None and not dense:
        mo, counts, chosen = moe_mod.held_ffn_decode(p["mlp"], h2, cfg, experts, layer)
        write = lambda c, t, s: jax.lax.dynamic_update_slice(c, t, (s, 0))  # noqa: E731
        new_cache = dict(new_cache, route=jax.vmap(write)(
            cache["route"], chosen.astype(jnp.int8), pos))
        x = x + mo
    else:
        x = x + mlp_apply(p["mlp"], h2, cfg.mlp)
    return x, new_cache, counts


def _cross_decode(cfg, p, x, xk, xv):
    B = x.shape[0]
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"])
    if cfg.qkv_bias:
        q = q + p["bq"]
    KV = cfg.n_kv_heads
    qg = q.reshape(B, KV, cfg.n_heads // KV, hd)
    s = jnp.einsum("bngh,bknh->bngk", qg, xk, preferred_element_type=jnp.float32)
    pr = jax.nn.softmax(s * hd ** -0.5, axis=-1)
    o = jnp.einsum("bngk,bknh->bngh", pr.astype(xv.dtype), xv)
    return jnp.einsum("bsh,hd->bsd", o.reshape(B, 1, -1), p["wo"])


# ======================================================================
# Trunk application
# ======================================================================
def _apply_trunk_full(cfg, params, x, positions, cache_len, enc_out, enc_pos,
                      remat: bool):
    """Returns (x, aux, caches, MoE counters int32[3])."""
    pattern = tuple(decoder_kind(cfg, k) for k in cfg.pattern)
    aux_total = jnp.zeros((), jnp.float32)
    counts = _no_counts()
    caches: dict = {}

    lead_caches = {}
    for i, k in enumerate(cfg.lead_kinds):
        x, aux, c, _ = block_forward_full(cfg, decoder_kind(cfg, k),
                                          params["lead"][f"l{i}"], x, positions,
                                          cache_len, enc_out, enc_pos, dense=True)
        aux_total = aux_total + aux
        lead_caches[f"l{i}"] = c
    if cfg.first_k_dense and cache_len is not None:
        caches["lead"] = lead_caches

    def superblock(x, layer_params):
        aux_sb = jnp.zeros((), jnp.float32)
        n_sb = _no_counts()
        sb_caches = {}
        for i, kind in enumerate(pattern):
            x, aux, c, n = block_forward_full(cfg, kind, layer_params[f"p{i}"], x,
                                              positions, cache_len, enc_out, enc_pos)
            aux_sb = aux_sb + aux
            n_sb = n_sb + n
            if cache_len is not None:
                sb_caches[f"p{i}"] = c
        return x, aux_sb, n_sb, sb_caches

    if remat:
        superblock = jax.checkpoint(superblock,
                                    policy=jax.checkpoint_policies.nothing_saveable)

    if cfg.n_superblocks > 0:
        def body(carry, layer_params):
            x, aux, n = carry
            x, aux_sb, n_sb, sb_caches = superblock(x, layer_params)
            return (x, aux + aux_sb, n + n_sb), (sb_caches if cache_len is not None else 0)

        if runtime_flags.UNROLL_SCANS:
            ys_list = []
            for i in range(cfg.n_superblocks):
                lp = jax.tree.map(lambda a: a[i], params["blocks"])
                (x, aux_total, counts), y = body((x, aux_total, counts), lp)
                ys_list.append(y)
            ys = jax.tree.map(lambda *zs: jnp.stack(zs), *ys_list) \
                if cache_len is not None else None
        else:
            (x, aux_total, counts), ys = jax.lax.scan(
                body, (x, aux_total, counts), params["blocks"])
        if cache_len is not None:
            caches["blocks"] = ys

    tail_caches = {}
    for i, k in enumerate(cfg.tail_kinds):
        kind = decoder_kind(cfg, k)
        x, aux, c, n = block_forward_full(cfg, kind, params["tail"][f"t{i}"], x,
                                          positions, cache_len, enc_out, enc_pos)
        aux_total = aux_total + aux
        counts = counts + n
        if cache_len is not None:
            tail_caches[f"t{i}"] = c
    if cache_len is not None:
        caches["tail"] = tail_caches
    return x, aux_total, caches, counts


def _split_experts(tree: dict):
    """``(tree without its held-expert leaves, those leaves)``, per
    pattern position ("p0", ...), for an expert layer's decode."""
    rest, experts = {}, {}
    for name, p in tree.items():
        if "mlp" in p and "experts" in p["mlp"]:
            experts[name] = p["mlp"]["experts"]
            p = dict(p, mlp={k: v for k, v in p["mlp"].items() if k != "experts"})
        rest[name] = p
    return rest, experts


def _apply_trunk_decode(cfg, params, x, cache, pos):
    """Returns (x, new_cache, MoE counters int32[3]).  Scanned expert
    layers read their held experts from the whole stacked leaves, by
    layer index inside each expert's branch, so that the scan slices no
    expert that the step does not run."""
    pattern = tuple(decoder_kind(cfg, k) for k in cfg.pattern)
    counts = _no_counts()

    new_lead = {}
    for i, k in enumerate(cfg.lead_kinds):
        x, new_lead[f"l{i}"], _ = block_forward_decode(
            cfg, decoder_kind(cfg, k), params["lead"][f"l{i}"], x,
            cache["lead"][f"l{i}"], pos, dense=True)

    if cfg.n_superblocks > 0:
        blocks, experts = _split_experts(params["blocks"])

        def body(carry, xs):
            x, n = carry
            layer_params, layer_cache, li = xs
            new_caches = {}
            for i, kind in enumerate(pattern):
                name = f"p{i}"
                x, nc, n_l = block_forward_decode(
                    cfg, kind, layer_params[name], x, layer_cache[name], pos,
                    experts=experts.get(name), layer=li)
                new_caches[name] = nc
                n = n + n_l
            return (x, n), new_caches

        layer_ids = jnp.arange(cfg.n_superblocks)
        if runtime_flags.UNROLL_SCANS:
            ys_list = []
            for i in range(cfg.n_superblocks):
                xs_i = jax.tree.map(lambda a: a[i], (blocks, cache["blocks"]))
                (x, counts), y = body((x, counts), xs_i + (i,))
                ys_list.append(y)
            new_blocks = jax.tree.map(lambda *zs: jnp.stack(zs), *ys_list)
        else:
            (x, counts), new_blocks = jax.lax.scan(
                body, (x, counts), (blocks, cache["blocks"], layer_ids))
    else:
        new_blocks = None

    tail, tail_experts = _split_experts(params["tail"])
    new_tail = {}
    for i, k in enumerate(cfg.tail_kinds):
        kind = decoder_kind(cfg, k)
        x, nc, n = block_forward_decode(cfg, kind, tail[f"t{i}"], x,
                                        cache["tail"][f"t{i}"], pos,
                                        experts=tail_experts.get(f"t{i}"))
        new_tail[f"t{i}"] = nc
        counts = counts + n
    new_cache = {"tail": new_tail}
    if new_blocks is not None:
        new_cache["blocks"] = new_blocks
    if cfg.first_k_dense:
        new_cache["lead"] = new_lead
    return x, new_cache, counts


# ======================================================================
# Embedding / unembedding
# ======================================================================
def embed_tokens(cfg: ModelConfig, params, tokens, positions=None):
    x = jnp.take(params["embed"]["table"], tokens, axis=0)
    if cfg.embed_scale:
        x = x * jnp.sqrt(float(cfg.d_model)).astype(x.dtype)
    if not cfg.use_rope and positions is not None:
        x = x + sinusoidal_pos(positions, cfg.d_model).astype(x.dtype)
    return x


def unembed(cfg: ModelConfig, params, x):
    table = params["embed"]["table"] if cfg.tie_embeddings else None
    if table is not None:
        logits = jnp.einsum("bsd,vd->bsv", x, table)
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    logits = shard(logits, "batch", "seq", "vocab")
    if cfg.padded_vocab != cfg.vocab_size:
        mask = (jnp.arange(cfg.padded_vocab) < cfg.vocab_size)
        logits = jnp.where(mask, logits, -1e30)
    return logits


def _encode(cfg, params, frames):
    """Whisper encoder over precomputed frame embeddings (stub frontend)."""
    F = frames.shape[1]
    pos = jnp.broadcast_to(jnp.arange(F), frames.shape[:2])
    dt = params["embed"]["table"].dtype
    x = frames.astype(dt) + sinusoidal_pos(pos, cfg.d_model).astype(dt)

    def body(x, layer_params):
        x, _, _, _ = block_forward_full(cfg, "enc", layer_params, x, pos, None)
        return x, 0

    if runtime_flags.UNROLL_SCANS:
        for i in range(cfg.encdec.n_encoder_layers):
            x, _ = body(x, jax.tree.map(lambda a: a[i], params["encoder"]["blocks"]))
    else:
        x, _ = jax.lax.scan(body, x, params["encoder"]["blocks"])
    x = apply_norm(cfg.norm, x, params["encoder"]["final_norm"]["scale"], cfg.norm_eps)
    return x, pos


def _assemble_input(cfg, params, batch):
    """Returns (x, positions, enc_out, enc_pos)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    enc_out = enc_pos = None
    if cfg.vlm is not None:
        img = batch["image_embeds"].astype(params["embed"]["table"].dtype)
        n_img = img.shape[1]
        positions = jnp.broadcast_to(jnp.arange(S + n_img), (B, S + n_img))
        x = jnp.concatenate([img, embed_tokens(cfg, params, tokens)], axis=1)
    elif cfg.encdec is not None:
        enc_out, enc_pos = _encode(cfg, params, batch["frames"])
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        x = embed_tokens(cfg, params, tokens, positions)
    else:
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        x = embed_tokens(cfg, params, tokens, positions)
    x = shard(x, "batch", "seq", None)
    return x, positions, enc_out, enc_pos


# ======================================================================
# Public API: loss / prefill / decode
# ======================================================================
def forward_train(cfg: ModelConfig, params, batch, remat: bool = False):
    """batch: {'tokens', 'targets', ['image_embeds'|'frames']}.
    Returns (loss fp32, metrics)."""
    x, positions, enc_out, enc_pos = _assemble_input(cfg, params, batch)
    x, aux, _, _ = _apply_trunk_full(cfg, params, x, positions, None, enc_out,
                                     enc_pos, remat)
    x = apply_norm(cfg.norm, x, params["final_norm"]["scale"], cfg.norm_eps)
    if cfg.vlm is not None:  # predict only over text positions
        x = x[:, -batch["tokens"].shape[1]:]
    logits = unembed(cfg, params, x)
    targets = batch["targets"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    mask = (targets >= 0).astype(jnp.float32)
    tgt = jnp.maximum(targets, 0)
    nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = (nll * mask).sum() / denom
    total = loss + 0.01 * aux
    return total, {"loss": loss, "aux_loss": aux,
                   "ppl_proxy": jnp.exp(jnp.clip(loss, 0.0, 20.0))}


def prefill(cfg: ModelConfig, params, batch, cache_len: int):
    """Returns (cache, last_token_logits (B, V))."""
    x, positions, enc_out, enc_pos = _assemble_input(cfg, params, batch)
    x, _, caches, counts = _apply_trunk_full(cfg, params, x, positions, cache_len,
                                             enc_out, enc_pos, remat=False)
    if cfg.moe is not None:
        caches["moe_counts"] = jnp.stack([counts, _no_counts()])
    x = apply_norm(cfg.norm, x, params["final_norm"]["scale"], cfg.norm_eps)
    logits = unembed(cfg, params, x[:, -1:])[:, 0]
    return caches, logits


def decode_step(cfg: ModelConfig, params, cache, tokens, pos):
    """tokens: (B,) int32; pos: (B,) absolute positions. → (logits, cache)."""
    positions = pos[:, None]
    x = embed_tokens(cfg, params, tokens[:, None], positions)
    x = shard(x, "batch", None, None)
    x, new_cache, counts = _apply_trunk_decode(cfg, params, x, cache, pos)
    if cfg.moe is not None:
        new_cache["moe_counts"] = cache["moe_counts"].at[1].add(counts)
    x = apply_norm(cfg.norm, x, params["final_norm"]["scale"], cfg.norm_eps)
    logits = unembed(cfg, params, x)[:, 0]
    return logits, new_cache
