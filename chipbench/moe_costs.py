"""Operations and bytes of a latent-attention expert model's calls
(Moonlight's DeepSeek-V3 block at a chip's expert share), from shapes
and the program's own expert counters.

Least bytes: every weight outside the routed experts once (the head
whole, one embedding row per token), the latent cache (``c_kv`` and
``k_pe``) read to the position and written once, and each held expert
that the counter says ran, once.  Least operations: the projections,
causal attention (expanded in prefill, absorbed in decode), the dense
first layer, router and shared experts for every token, and the held
experts for the assignments they actually received.  Activations that
fit on chip are not counted.  ``m`` is the member's entry in the
configuration file (published key names).
"""
from __future__ import annotations

from chipbench.costs import Cost


def _dims(m: dict):
    return (m["hidden_size"], m["num_attention_heads"], m["kv_lora_rank"],
            m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"])


def attention_weights(m: dict) -> int:
    d, H, r, n, p, v = _dims(m)
    return d * H * (n + p) + d * (r + p) + r + r * H * (n + v) + H * v * d


def expert_weights(m: dict) -> int:
    """One routed (or shared) expert: SwiGLU of ``moe_intermediate_size``."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def outside_experts(m: dict) -> int:
    """Weights a step reads whatever the routing: attention of every
    layer, the dense layers, router, bias and shared experts of every
    expert layer, norms, and the head (not the embedding table)."""
    d, L, k = m["hidden_size"], m["num_hidden_layers"], m["first_k_dense_replace"]
    E = m["n_routed_experts"]
    per_moe = d * E + E + m["n_shared_experts"] * expert_weights(m)
    return (L * (attention_weights(m) + 2 * d) + k * 3 * d * m["intermediate_size"]
            + (L - k) * per_moe + d * m["vocab_size"] + d)


def _cache_row(m: dict, item: int) -> int:
    return m["num_hidden_layers"] * (m["kv_lora_rank"] + m["qk_rope_head_dim"]) * item


def _per_token_flops(m: dict) -> int:
    """Projections, dense layers, router and shared experts of one token."""
    d, H, r, n, p, v = _dims(m)
    L, k = m["num_hidden_layers"], m["first_k_dense_replace"]
    return 2 * (L * (d * H * (n + p) + d * (r + p) + H * v * d)
                + k * 3 * d * m["intermediate_size"]
                + (L - k) * (d * m["n_routed_experts"]
                             + m["n_shared_experts"] * expert_weights(m)))


def prefill(m: dict, seq: int, ran: float, assigned: float, item: int = 2) -> Cost:
    """Batch 1, ``seq`` tokens, logits of the last token only; ``ran``
    held experts ran and ``assigned`` assignments reached them, summed
    over the expert layers."""
    d, H, r, n, p, v = _dims(m)
    L = m["num_hidden_layers"]
    causal = seq * (seq + 1) // 2
    flops = (seq * (_per_token_flops(m) + 2 * L * r * H * (n + v))
             + L * 2 * H * (n + p + v) * causal
             + 2 * expert_weights(m) * assigned + 2 * d * m["vocab_size"])
    bytes_ = ((outside_experts(m) + ran * expert_weights(m)) * item
              + seq * _cache_row(m, item) + seq * d * item)
    return Cost(flops=flops, bytes=bytes_)


def decode(m: dict, pos: int, ran: float, assigned: float, item: int = 2) -> Cost:
    """Batch 1, one token at position ``pos`` (0-based) against the
    ``pos`` cached positions before it, absorbed form."""
    d, H, r, n, p, v = _dims(m)
    L = m["num_hidden_layers"]
    attn = 2 * L * H * (n * r + (r + p) * (pos + 1) + r * (pos + 1) + r * v)
    flops = (_per_token_flops(m) + attn + 2 * expert_weights(m) * assigned
             + 2 * d * m["vocab_size"])
    bytes_ = ((outside_experts(m) + ran * expert_weights(m)) * item
              + (pos + 1) * _cache_row(m, item) + d * item)
    return Cost(flops=flops, bytes=bytes_)


def moe_member(config: dict):
    """Index of the latent-attention expert member, or None."""
    for i, m in enumerate(config.get("members", [])):
        if "kv_lora_rank" in m:
            return i
    return None


def member_decode_times(run):
    """``[(call index among decode calls, device ns)]`` of the expert
    member's decode executions in the traced window: the trace's
    ``decode_step`` executions matched in order with the driver's decode
    calls.  None where there is no trace or no expert member, or where
    the counts differ (an execution dropped or one from elsewhere)."""
    mi = moe_member(run.config)
    if run.trace is None or mi is None:
        return None
    ev = sorted(run.trace.programs.get("decode_step", []))
    calls = [c for c in run.calls if c[0] == "decode"]
    if not ev or len(ev) != len(calls):
        return None
    return [(j, dur) for j, ((_, m, _), (_, dur)) in enumerate(zip(calls, ev))
            if m == mi]
