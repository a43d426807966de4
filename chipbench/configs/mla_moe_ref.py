"""Plain float32 reference of the pool-moonlight-qwen2 members.

Moonlight-16B-A3B (DeepSeek-V3 block) as the configuration file states
it, at this chip's expert share: pre-norm decoder layers, latent
attention in its expanded form (keys and values rebuilt per head from
the RMS-normed latent, one rotary key shared by all heads, scores
scaled by 1/sqrt(qk_nope + qk_rope)), a dense SwiGLU first layer, then
expert layers that route over all published experts (sigmoid scores,
top-k chosen on scores plus the correction bias, gates the unbiased
scores normalised over the chosen and scaled by the routed scaling
factor) and add the part of the held experts, computed for every token
and weighted by its gate, plus the shared experts; an untied head.
Straightforward ``jax.numpy`` in float32 under the ``highest`` matmul
precision, no kernels, no cache.  It imports nothing of the program.
The dense member (qwen2-1.5b) is ``dense_lm_ref.py``'s.

Weights are made here from the seed by the program's documented rule
(one key per member from ``split(PRNGKey(seed), members)``, one key per
parameter leaf in sorted-path order, each leaf a float32 normal times
its scale, stored in bfloat16): normal leaves by 1/sqrt of their last
axis, the router by 1/sqrt of its fan-in (``hidden_size``),
residual-out projections by 0.02/sqrt(2), norms and the correction bias
zero.  Expert leaves are drawn at the held shape, stacked over the
expert layers.

``fp8=True`` gives the control: every matmul of a weight (the router's
included) takes its operands rounded to float8 e4m3.
"""
from __future__ import annotations

import functools
import importlib.util
import os
import sys
from typing import Dict, List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def _dense_module():
    name = "chipbench_configs_dense_lm_ref_py"     # the harness's own name
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(HERE, "dense_lm_ref.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


dense = _dense_module()
HIGHEST = dense.HIGHEST


class Dims(NamedTuple):
    n_layers: int
    first_k_dense: int
    d_model: int
    n_heads: int
    kv_lora_rank: int
    qk_nope: int
    qk_rope: int
    v_head: int
    d_ff: int
    d_ff_expert: int
    n_experts: int
    n_held: int
    top_k: int
    n_shared: int
    routed_scale: float
    vocab_size: int
    padded_vocab: int
    rope_theta: float
    norm_eps: float


def dims(member: dict):
    """The Moonlight member's dims, or the dense member's
    (``dense_lm_ref.dims``)."""
    if "kv_lora_rank" not in member:
        return dense.dims(member)
    rule = {k: member[k] for k in ("scoring_func", "topk_method", "n_group",
                                   "topk_group", "norm_topk_prob",
                                   "q_lora_rank", "rope_scaling",
                                   "tie_word_embeddings", "hidden_act")}
    if rule != {"scoring_func": "sigmoid", "topk_method": "noaux_tc",
                "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
                "q_lora_rank": None, "rope_scaling": None,
                "tie_word_embeddings": False, "hidden_act": "silu"}:
        raise NotImplementedError(f"the reference computes DeepSeek-V3 "
                                  f"blocks as Moonlight publishes them: {rule}")
    v = member["vocab_size"]
    return Dims(member["num_hidden_layers"], member["first_k_dense_replace"],
                member["hidden_size"], member["num_attention_heads"],
                member["kv_lora_rank"], member["qk_nope_head_dim"],
                member["qk_rope_head_dim"], member["v_head_dim"],
                member["intermediate_size"], member["moe_intermediate_size"],
                member["n_routed_experts"], member["experts_held"],
                member["num_experts_per_tok"], member["n_shared_experts"],
                float(member["routed_scaling_factor"]), v,
                -(-v // member["vocab_pad"]) * member["vocab_pad"],
                float(member["rope_theta"]), float(member["rms_norm_eps"]))


# ----------------------------------------------------------------------
# weights from the seed
# ----------------------------------------------------------------------
def leaf_specs(m: Dims) -> List[Tuple[Tuple[str, ...], tuple, str, int]]:
    """``(path, shape, init, scale axis)`` of every parameter leaf, in
    sorted-path order; expert-layer leaves carry a leading layer axis."""
    d, H, r, fe = m.d_model, m.n_heads, m.kv_lora_rank, m.d_ff_expert
    L = m.n_layers - m.first_k_dense

    def attn(lead):
        return {"kv_norm": {"scale": (lead + (r,), "zeros", -1)},
                "wkv_a": (lead + (d, r + m.qk_rope), "normal", -1),
                "wkv_b": (lead + (r, H * (m.qk_nope + m.v_head)), "normal", -1),
                "wo": (lead + (H * m.v_head, d), "normal_out", -1),
                "wq": (lead + (d, H * (m.qk_nope + m.qk_rope)), "normal", -1)}

    def swiglu(lead, f):
        return {"wg": (lead + (d, f), "normal", -1),
                "wi": (lead + (d, f), "normal", -1),
                "wo": (lead + (f, d), "normal_out", -1)}

    def norms(lead):
        return {"norm1": {"scale": (lead + (d,), "zeros", -1)},
                "norm2": {"scale": (lead + (d,), "zeros", -1)}}

    moe = {"bias": ((L, m.n_experts), "zeros", -1),
           "experts": {"wg": ((L, m.n_held, d, fe), "normal", -1),
                       "wi": ((L, m.n_held, d, fe), "normal", -1),
                       "wo": ((L, m.n_held, fe, d), "normal_out", -1)},
           "router": ((L, d, m.n_experts), "normal", -2),
           "shared": swiglu((L,), m.n_shared * fe)}
    tree = {"blocks": {"p0": {"attn": attn((L,)), "mlp": moe, **norms((L,))}},
            "embed": {"table": ((m.padded_vocab, d), "normal", -1)},
            "final_norm": {"scale": ((d,), "zeros", -1)},
            "lead": {f"l{i}": {"attn": attn(()), "mlp": swiglu((), m.d_ff),
                               **norms(())}
                     for i in range(m.first_k_dense)},
            "lm_head": ((d, m.padded_vocab), "normal", -1)}

    def flat(t, path=()):
        for k in sorted(t):
            if isinstance(t[k], dict):
                yield from flat(t[k], path + (k,))
            else:
                yield (path + (k,),) + t[k]
    return list(flat(tree))


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _leaf(shape, init, axis, key):
    if init == "zeros":
        return jnp.zeros(shape, jnp.bfloat16)
    std = 0.02 / jnp.sqrt(2.0) if init == "normal_out" else \
        1.0 / jnp.sqrt(max(1, shape[axis]))
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(jnp.bfloat16)


def make_weights(m, seed: int, index: int, n_members: int) -> Dict[str, jax.Array]:
    """Member ``index`` of ``n_members``, keyed by the dotted leaf path
    (``blocks.attn.wq``, ``lead.l0.mlp.wi``, ...), in bfloat16."""
    if not isinstance(m, Dims):
        return dense.make_weights(m, seed, index, n_members)
    key = jax.random.split(jax.random.PRNGKey(seed), n_members)[index]
    specs = leaf_specs(m)
    keys = jax.random.split(key, len(specs))
    return {".".join(p for p in path if p != "p0"): _leaf(shape, init, axis, k)
            for (path, shape, init, axis), k in zip(specs, keys)}


# ----------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------
def _mm(a, w, fp8):
    return dense._mm(a, w, fp8)


def _swiglu(h, w, fp8):
    return _mm(jax.nn.silu(_mm(h, w["wi"], fp8)) * _mm(h, w["wg"], fp8), w["wo"], fp8)


def _attention(x, w, m: Dims, fp8):
    B, T, _ = x.shape
    H, r, n = m.n_heads, m.kv_lora_rank, m.qk_nope
    h = dense._rms(x, w["norm1.scale"], m.norm_eps)
    q = _mm(h, w["attn.wq"], fp8).reshape(B, T, H, n + m.qk_rope)
    q = jnp.concatenate([q[..., :n], dense._rope(q[..., n:], m.rope_theta)], -1)
    kv_a = _mm(h, w["attn.wkv_a"], fp8)
    c = dense._rms(kv_a[..., :r], w["attn.kv_norm.scale"], m.norm_eps)
    k_pe = dense._rope(kv_a[:, :, None, r:], m.rope_theta)
    kv = _mm(c, w["attn.wkv_b"], fp8).reshape(B, T, H, n + m.v_head)
    k = jnp.concatenate([kv[..., :n], jnp.broadcast_to(k_pe, (B, T, H, m.qk_rope))], -1)
    s = jnp.einsum("bthd,bshd->bhts", q, k, precision=HIGHEST) \
        * (n + m.qk_rope) ** -0.5
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhts,bshd->bthd", p, kv[..., n:], precision=HIGHEST)
    return x + _mm(o.reshape(B, T, H * m.v_head), w["attn.wo"], fp8)


def route(h, w, m: Dims, fp8=False, given=None):
    """(B, T, D) → (gates on every expert (B, T, E), 0 where not chosen;
    the experts this computation chooses (B, T, K)).  With ``given``
    (B, T, K), the gates are taken on those experts instead: the
    program's choices, so that one near-tie flipped in bf16 does not
    carry into every later layer; how often the choices differ is
    compared on its own."""
    scores = jax.nn.sigmoid(_mm(h, w["mlp.router"], fp8))
    _, own = jax.lax.top_k(scores + w["mlp.bias"].astype(jnp.float32), m.top_k)
    idx = own if given is None else given.astype(jnp.int32)
    g = jnp.take_along_axis(scores, idx, -1)
    g = g / jnp.sum(g, -1, keepdims=True) * m.routed_scale
    return jnp.sum(jax.nn.one_hot(idx, m.n_experts) * g[..., None], -2), own


def _at(w, prefix, l=None):
    out = {}
    for k, v in w.items():
        if k.startswith(prefix):
            out[k[len(prefix):]] = (v if l is None else
                                    jax.lax.dynamic_index_in_dim(v, l, keepdims=False))
    return out


@functools.partial(jax.jit, static_argnames=("m", "fp8"))
def _lead_layer(x, w, *, m: Dims, fp8: bool):
    x = _attention(x, w, m, fp8)
    h = dense._rms(x, w["norm2.scale"], m.norm_eps)
    return x + _swiglu(h, _at(w, "mlp."), fp8)


@functools.partial(jax.jit, static_argnames=("m", "fp8"))
def _expert_layer(x, l, blocks, given, *, m: Dims, fp8: bool):
    w = _at(blocks, "", l)
    x = _attention(x, w, m, fp8)
    h = dense._rms(x, w["norm2.scale"], m.norm_eps)
    gates, own = route(h, w, m, fp8,
                       None if given is None else given[:, l])
    out = _swiglu(h, _at(w, "mlp.shared."), fp8)
    for e in range(m.n_held):
        ex = {k: v[e] for k, v in _at(w, "mlp.experts.").items()}
        out = out + gates[..., e:e + 1] * _swiglu(h, ex, fp8)
    return x + out, own


def forward_logits(m: Dims, w: Dict[str, jax.Array], tokens: np.ndarray,
                   pos: np.ndarray, fp8: bool = False, given=None):
    """(B, T) token ids → (B, P, vocab) float32 logits at ``pos`` (B, P).
    With ``given`` (B, L, T, K), the program's chosen experts per expert
    layer, the gates follow them, and the experts this computation
    chooses are returned too, (B, L, T, K)."""
    x = dense._embed(w["embed.table"], jnp.asarray(tokens), m=m)
    for i in range(m.first_k_dense):
        x = _lead_layer(x, _at(w, f"lead.l{i}."), m=m, fp8=fp8)
    blocks = _at(w, "blocks.")
    given = None if given is None else jnp.asarray(given)
    own = []
    for l in range(m.n_layers - m.first_k_dense):
        x, o = _expert_layer(x, l, blocks, given, m=m, fp8=fp8)
        own.append(o)
    logits = dense._logits(x, jnp.asarray(pos), w["final_norm.scale"],
                           w["lm_head"].T, m=m, fp8=fp8)
    return logits if given is None else (logits, jnp.stack(own, 1))


def _bucket(n: int, length: int) -> int:
    """Padded length of a pass: a power-of-two multiple of 256, at most
    ``length``."""
    t = 256
    while t < n:
        t *= 2
    return min(t, length)


class Served(NamedTuple):
    """Per request, the gap of each served token below the reference's
    best (``gaps``) and of the control's first choice (``ctrl``, or
    None); over every (position, expert layer) of the sample, how many
    chosen top-k sets differ from the program's (``differ``), out of
    ``total``, and how many of the control's differ from the
    reference's (``ctrl_differ``)."""
    gaps: list
    ctrl: list
    differ: int
    total: int
    ctrl_differ: int


def served_gaps(m, w: Dict[str, jax.Array],
                requests: Sequence[Tuple[np.ndarray, Sequence[int]]], *,
                length: int, batch: int = 2, control: bool = False,
                routes=None):
    """As ``dense_lm_ref.served_gaps``, for either member: for each
    request ``(prompt, served)``, the gap of each served token's logit
    below the reference's best at its position (with ``control``, also
    the gap of the token the float8 control puts first there).  The
    dense member pads every sequence to ``length``, four rows a pass.
    Here requests go shortest first, each pass padded to a power-of-two
    multiple of 256 positions (at most ``length``) and holding as many
    rows as ``batch`` rows of ``length`` would: every position a request
    attends to is computed, and padding only follows it.

    ``routes``, one array (expert layers, positions, K) per request, are
    the experts the program chose at each position of the request: the
    reference (and the control) then gate on them, and a ``Served`` is
    returned with the count of choices that differ."""
    if not isinstance(m, Dims):
        return dense.served_gaps(m, w, requests, length=length,
                                 batch=2 * batch, control=control)
    need = [len(p) + len(s) - 1 for p, s in requests]
    order = sorted(range(len(requests)), key=need.__getitem__)
    out, ctrl = [None] * len(requests), [None] * len(requests)
    differ = total = ctrl_differ = 0
    c = 0
    while c < len(order):
        T = _bucket(need[order[c]], length)
        while True:
            rows = order[c:c + max(1, batch * length // T)]
            if need[rows[-1]] <= T:
                break
            T = _bucket(need[rows[-1]], length)
        c += len(rows)
        B = max(1, batch * length // T)
        P = max(len(requests[i][1]) for i in rows)
        tokens = np.zeros((B, T), np.int32)
        pos = np.zeros((B, P), np.int32)
        tok = np.zeros((B, P), np.int32)
        given = None
        if routes is not None:
            given = np.zeros((B, m.n_layers - m.first_k_dense, T, m.top_k), np.int32)
        for r, i in enumerate(rows):
            prompt, served = requests[i]
            seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
            tokens[r, :len(seq)] = seq
            n = len(served)
            pos[r, :n] = len(prompt) - 1 + np.arange(n)
            tok[r, :n] = served
            if given is not None:
                given[r, :, :len(seq)] = routes[i][:, :len(seq)]
        ref = forward_logits(m, w, tokens, pos, given=given)
        if given is not None:
            ref, own = ref
        gaps = np.asarray(dense._gaps(ref, jnp.asarray(tok)))
        if control:
            low = forward_logits(m, w, tokens, pos, fp8=True, given=given)
            if given is not None:
                low, low_own = low
            cgaps = np.asarray(dense._gaps(ref, jnp.argmax(low, -1)))
        if given is not None:
            valid = np.arange(T)[None, None, :] < np.array([need[i] for i in rows]
                                                           + [0] * (B - len(rows)))[:, None, None]
            srt = lambda a: np.sort(np.asarray(a), -1)  # noqa: E731
            differ += int(((srt(own) != srt(given)).any(-1) & valid).sum())
            total += int(valid.sum()) * given.shape[1]
            if control:
                ctrl_differ += int(((srt(low_own) != srt(own)).any(-1) & valid).sum())
        for r, i in enumerate(rows):
            n = len(requests[i][1])
            out[i] = gaps[r, :n]
            if control:
                ctrl[i] = cgaps[r, :n]
    if routes is not None:
        return Served(out, ctrl if control else None, differ, total, ctrl_differ)
    return (out, ctrl) if control else out
