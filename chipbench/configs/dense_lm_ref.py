"""Plain float32 reference of a dense decoder pool member.

The forward pass of a pre-norm decoder with grouped-query attention,
rotary positions and a SwiGLU feed-forward, as the configuration file
states it: straightforward ``jax.numpy`` in float32 under the
``highest`` matmul precision, no kernels, no cache, no batching across
requests beyond padding.  It imports nothing of the program.

Weights are made here from the seed, by the seeded rule the served pool
documents (one key per member from ``split(PRNGKey(seed), members)``,
one key per parameter leaf in sorted-name order, each leaf a float32
normal times its scale, stored in bfloat16), so the reference reads the
same bfloat16 weights the pool serves and takes none of them from it.

``fp8=True`` gives the control: every matmul of a weight takes its
operands rounded to float8 (e4m3, one scale per weight tensor and per
activation row), the next precision below the bfloat16 the
configuration states.
"""
from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0                       # largest finite float8_e4m3fn


class Dims(NamedTuple):
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    padded_vocab: int
    rope_theta: float
    norm_eps: float
    qkv_bias: bool
    tie_embeddings: bool


def dims(member: dict) -> Dims:
    if member["partial_rotary_factor"] != 1.0 or member["rope_scaling"]:
        raise NotImplementedError("the reference rotates whole heads with "
                                  "plain rotary")
    v = member["vocab_size"]
    return Dims(member["n_layers"], member["d_model"], member["n_heads"],
                member["n_kv_heads"], member["head_dim"], member["d_ff"], v,
                -(-v // member["vocab_pad"]) * member["vocab_pad"],
                float(member["rope_theta"]), float(member["norm_eps"]),
                bool(member["qkv_bias"]), bool(member["tie_embeddings"]))


# ----------------------------------------------------------------------
# weights from the seed
# ----------------------------------------------------------------------
def leaf_specs(m: Dims) -> List[Tuple[Tuple[str, ...], tuple, str]]:
    """``(path, shape, init)`` of every parameter leaf, in sorted-name
    order.  ``init`` is ``normal`` (scale 1/sqrt of the last axis),
    ``normal_out`` (scale 0.02/sqrt(2), the residual-out projections) or
    ``zeros``.  Layer leaves carry a leading layer axis."""
    L, d, H, KV, hd, f = (m.n_layers, m.d_model, m.n_heads, m.n_kv_heads,
                          m.head_dim, m.d_ff)
    attn = {"wq": ((L, d, H * hd), "normal"),
            "wk": ((L, d, KV * hd), "normal"),
            "wv": ((L, d, KV * hd), "normal"),
            "wo": ((L, H * hd, d), "normal_out")}
    if m.qkv_bias:
        attn.update(bq=((L, H * hd), "zeros"), bk=((L, KV * hd), "zeros"),
                    bv=((L, KV * hd), "zeros"))
    block = {"attn": attn,
             "mlp": {"wi": ((L, d, f), "normal"), "wg": ((L, d, f), "normal"),
                     "wo": ((L, f, d), "normal_out")},
             "norm1": {"scale": ((L, d), "zeros")},
             "norm2": {"scale": ((L, d), "zeros")}}
    tree = {"blocks": {"p0": block},
            "embed": {"table": ((m.padded_vocab, d), "normal")},
            "final_norm": {"scale": ((d,), "zeros")}}
    if not m.tie_embeddings:
        tree["lm_head"] = ((d, m.padded_vocab), "normal")

    def flat(t, path=()):
        for k in sorted(t):
            if isinstance(t[k], dict):
                yield from flat(t[k], path + (k,))
            else:
                yield (path + (k,),) + t[k]
    return list(flat(tree))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _leaf(shape, init, key):
    if init == "zeros":
        return jnp.zeros(shape, jnp.bfloat16)
    if init == "normal_out":
        std = 0.02 / jnp.sqrt(2.0)
    else:
        std = 1.0 / jnp.sqrt(max(1, shape[-1]))
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(
        jnp.bfloat16)


def make_weights(m: Dims, seed: int, index: int, n_members: int
                 ) -> Dict[str, jax.Array]:
    """Member ``index`` of ``n_members``: ``{"attn.wq": ..., ...}`` in
    bfloat16, keyed by the leaf path below the layer block."""
    key = jax.random.split(jax.random.PRNGKey(seed), n_members)[index]
    specs = leaf_specs(m)
    keys = jax.random.split(key, len(specs))
    out = {}
    for (path, shape, init), k in zip(specs, keys):
        name = ".".join(p for p in path if p not in ("blocks", "p0"))
        out[name] = _leaf(shape, init, k)
    return out


# ----------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------
def _round_fp8(a, axis):
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, w, fp8: bool):
    """``a @ w`` in float32 at the highest precision; with ``fp8`` both
    operands are first rounded to float8 (per row of ``a``, per tensor
    of ``w``)."""
    a = a.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if fp8:
        a = _round_fp8(a, -1)
        w = _round_fp8(w, None)
    return jnp.matmul(a, w, precision=HIGHEST)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))


def _rope(x, theta):
    """Rotary positions on the two halves of each head (positions are
    the token indices)."""
    T, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("m", "fp8"))
def _layer(x, l, w, *, m: Dims, fp8: bool):
    B, T, _ = x.shape
    H, KV, hd = m.n_heads, m.n_kv_heads, m.head_dim
    at = {k: jax.lax.dynamic_index_in_dim(v, l, keepdims=False)
          for k, v in w.items()}
    h = _rms(x, at["norm1.scale"], m.norm_eps)
    q = _mm(h, at["attn.wq"], fp8)
    k = _mm(h, at["attn.wk"], fp8)
    v = _mm(h, at["attn.wv"], fp8)
    if m.qkv_bias:
        q = q + at["attn.bq"].astype(jnp.float32)
        k = k + at["attn.bk"].astype(jnp.float32)
        v = v + at["attn.bv"].astype(jnp.float32)
    q = _rope(q.reshape(B, T, H, hd), m.rope_theta)
    k = _rope(k.reshape(B, T, KV, hd), m.rope_theta)
    v = v.reshape(B, T, KV, hd)
    q = q.reshape(B, T, KV, H // KV, hd)
    s = jnp.einsum("btkgh,bskh->bkgts", q, k, precision=HIGHEST) * hd ** -0.5
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bkgts,bskh->btkgh", p, v, precision=HIGHEST)
    x = x + _mm(o.reshape(B, T, H * hd), at["attn.wo"], fp8)
    h = _rms(x, at["norm2.scale"], m.norm_eps)
    g = jax.nn.silu(_mm(h, at["mlp.wi"], fp8)) * _mm(h, at["mlp.wg"], fp8)
    return x + _mm(g, at["mlp.wo"], fp8)


@functools.partial(jax.jit, static_argnames=("m",))
def _embed(table, tokens, *, m: Dims):
    return jnp.take(table, tokens, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("m", "fp8"))
def _logits(x, pos, final, head, *, m: Dims, fp8: bool):
    """Logits over the real vocabulary at positions ``pos`` (B, P).  The
    head (vocabulary rows × d_model) is read in blocks of rows, so no
    float32 copy of it is ever whole."""
    xs = _rms(jnp.take_along_axis(x, pos[:, :, None], axis=1), final,
              m.norm_eps)
    if fp8:
        xs = _round_fp8(xs, -1)
        scale = jnp.max(jnp.abs(head)).astype(jnp.float32) / F8_MAX
    n = head.shape[0] // 256
    g = max(k for k in range(1, 65) if n % k == 0)

    def block(rows):
        rows = rows.astype(jnp.float32)
        if fp8:
            rows = (rows / scale).astype(jnp.float8_e4m3fn).astype(
                jnp.float32) * scale
        return jnp.einsum("bpd,vd->bpv", xs, rows, precision=HIGHEST)
    out = jax.lax.map(block, head.reshape(n // g, 256 * g, -1))
    out = jnp.moveaxis(out, 0, 2).reshape(xs.shape[:2] + (-1,))
    return out[..., :m.vocab_size]


@jax.jit
def _gaps(ref, tok):
    """How far the logit of ``tok`` lies below the best, per position."""
    return jnp.max(ref, -1) - jnp.take_along_axis(ref, tok[..., None], -1)[..., 0]


def forward_logits(m: Dims, w: Dict[str, jax.Array], tokens: np.ndarray,
                   pos: np.ndarray, fp8: bool = False) -> jax.Array:
    """(B, T) token ids → (B, P, vocab) float32 logits at ``pos`` (B, P)."""
    layer_w = {k: v for k, v in w.items()
               if k.split(".")[0] in ("attn", "mlp", "norm1", "norm2")}
    x = _embed(w["embed.table"], jnp.asarray(tokens), m=m)
    for l in range(m.n_layers):
        x = _layer(x, l, layer_w, m=m, fp8=fp8)
    head = w["embed.table"] if m.tie_embeddings else w["lm_head"].T
    return _logits(x, jnp.asarray(pos), w["final_norm.scale"], head,
                   m=m, fp8=fp8)


def served_gaps(m: Dims, w: Dict[str, jax.Array],
                requests: Sequence[Tuple[np.ndarray, Sequence[int]]], *,
                length: int, batch: int = 8, control: bool = False):
    """For each request ``(prompt, served)`` — the prompt's ids and the
    ``n + 1`` tokens served after it, each chosen after the one before
    was fed back — the gap of each served token's logit below the
    reference's best at its position.  With ``control``, also the gap of
    the token the float8 control puts first there.  Sequences are padded
    to ``length``; rows to a multiple of ``batch``."""
    out, ctrl = [], []
    for c in range(0, len(requests), batch):
        chunk = list(requests[c:c + batch])
        P = max(len(s) for _, s in chunk)
        tokens = np.zeros((batch, length), np.int32)
        pos = np.zeros((batch, P), np.int32)
        tok = np.zeros((batch, P), np.int32)
        for r, (prompt, served) in enumerate(chunk):
            seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
            tokens[r, :len(seq)] = seq
            n = len(served)
            pos[r, :n] = len(prompt) - 1 + np.arange(n)
            tok[r, :n] = served
        ref = forward_logits(m, w, tokens, pos)
        gaps = np.asarray(_gaps(ref, jnp.asarray(tok)))
        if control:
            low = forward_logits(m, w, tokens, pos, fp8=True)
            cgaps = np.asarray(_gaps(ref, jnp.argmax(low, -1)))
        for r, (_, served) in enumerate(chunk):
            out.append(gaps[r, :len(served)])
            if control:
                ctrl.append(cgaps[r, :len(served)])
    return (out, ctrl) if control else out
