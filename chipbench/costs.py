"""Operations and bytes per call, from shapes alone, and the table of
chip peaks.

Counts are the least the algorithm needs: a causal prefill attends to
the positions before each token only, a decode step reads the cached
keys and values of the positions written so far, every weight is read
once per call, and activations that fit on chip are not counted.  So a
roofline share computed from them cannot pass 100% unless the time is
short of the work.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; an unknown
    kind is an error, not a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


@dataclass(frozen=True)
class Cost:
    flops: float
    bytes: float

    def seconds_at(self, p: dict) -> float:
        """The roofline time: the larger of the compute and memory bounds."""
        return max(self.flops / p["bf16_flops_per_s"],
                   self.bytes / p["hbm_bytes_per_s"])


def _layer_weights(m: dict) -> int:
    d, hd, H, KV, f = (m["d_model"], m["head_dim"], m["n_heads"],
                       m["n_kv_heads"], m["d_ff"])
    attn = d * hd * (H + 2 * KV) + H * hd * d
    bias = hd * (H + 2 * KV) if m["qkv_bias"] else 0
    return attn + bias + 3 * d * f + 2 * d


def _kv_row_bytes(m: dict, item: int) -> int:
    """Key and value bytes of one position in one layer."""
    return 2 * m["n_kv_heads"] * m["head_dim"] * item


def dense_lm_prefill(m: dict, seq: int, item: int = 2) -> Cost:
    """Batch 1, ``seq`` tokens, logits of the last token only."""
    d, hd, H, KV, f, L = (m["d_model"], m["head_dim"], m["n_heads"],
                          m["n_kv_heads"], m["d_ff"], m["n_layers"])
    proj = 2 * seq * (d * hd * (H + 2 * KV) + H * hd * d + 3 * d * f)
    attn = 2 * 2 * H * hd * seq * (seq + 1) // 2
    head = 2 * d * m["vocab_size"]
    weights = L * _layer_weights(m) + m["vocab_size"] * d + d
    bytes_ = (weights * item + L * seq * _kv_row_bytes(m, item)
              + seq * d * item)
    return Cost(flops=L * (proj + attn) + head, bytes=bytes_)


def dense_lm_decode(m: dict, pos: int, item: int = 2) -> Cost:
    """Batch 1, one token at position ``pos`` (0-based) against the
    ``pos`` cached positions before it."""
    d, hd, H, KV, f, L = (m["d_model"], m["head_dim"], m["n_heads"],
                          m["n_kv_heads"], m["d_ff"], m["n_layers"])
    proj = 2 * (d * hd * (H + 2 * KV) + H * hd * d + 3 * d * f)
    attn = 2 * 2 * H * hd * (pos + 1)
    head = 2 * d * m["vocab_size"]
    weights = L * _layer_weights(m) + m["vocab_size"] * d + d
    bytes_ = (weights * item
              + L * (pos + 1) * _kv_row_bytes(m, item)  # read all, write one
              + d * item)
    return Cost(flops=L * (proj + attn) + head, bytes=bytes_)


def charged_scan(batch: int, n_models: int, candidates: int,
                 n_replicas: int) -> Cost:
    """One charged tick: per request, the per-model wait (a minimum over
    each model's candidate replicas), the admission test and stages 1–3
    over the pool (about 24 operations per model), and one charge.
    Bytes: the four float32 request columns in, the five result columns
    out (two of them one byte), the pool columns and the replica ledger
    once."""
    per_row = n_models * candidates + 24 * n_models + 2
    bytes_ = batch * (4 * 4 + 3 * 4 + 2 * 1) + 5 * 4 * n_models \
        + n_models * n_replicas + 4 * n_replicas
    return Cost(flops=batch * per_row, bytes=bytes_)
