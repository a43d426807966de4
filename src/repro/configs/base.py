"""Config system for the repro framework.

Every assigned architecture is expressed as a :class:`ModelConfig` built
from block *patterns* (superblocks) so that models with interleaved layer
types (gemma3 5:1 local:global, recurrentgemma 2:1 rglru:local) lower to a
`lax.scan` over superblocks plus a small unrolled tail — keeping HLO size
(and therefore XLA compile time) independent of depth.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field, replace
from typing import Optional, Tuple

# Block kinds understood by the model substrate.
BLOCK_KINDS = ("attn", "local", "mla", "rglru", "ssd")


def _ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff_expert: int
    capacity_factor: float = 1.25
    # Token-group size for the GShard-style one-hot dispatch einsum.  Kept
    # modest so the (g, E, C) dispatch tensor stays VMEM/HBM friendly.
    group_size: int = 512
    n_shared: int = 0              # shared experts, run for every token
    scoring: str = "softmax"       # softmax | sigmoid (with a correction bias)
    routed_scale: float = 1.0      # multiplies the top-k gates, renormalised to sum 1
    # Experts [0, n_held) live here (one chip's expert-parallel share);
    # None holds all n_experts.  Routing always runs over all n_experts.
    n_held: Optional[int] = None

    @property
    def held(self) -> int:
        return self.n_experts if self.n_held is None else self.n_held


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2/V3): keys and values are
    rebuilt per head from one shared latent of ``kv_lora_rank`` values,
    and one ``qk_rope_head_dim`` rotary key is shared by all heads.
    Queries come from one full projection (no query latent)."""
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


@dataclass(frozen=True)
class SSMConfig:
    """Mamba-2 (SSD) mixer parameters."""
    d_state: int = 128
    head_dim: int = 64
    expand: int = 2
    chunk_size: int = 256
    conv_width: int = 4
    n_groups: int = 1

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def n_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


@dataclass(frozen=True)
class RGLRUConfig:
    """RecurrentGemma RG-LRU recurrent block parameters."""
    lru_width: Optional[int] = None  # default: d_model
    conv_width: int = 4
    c_exponent: float = 8.0

    def width(self, d_model: int) -> int:
        return self.lru_width or d_model


@dataclass(frozen=True)
class EncDecConfig:
    """Encoder-decoder (whisper) extras; frontend is a stub that provides
    precomputed frame embeddings."""
    n_encoder_layers: int = 4
    n_frames: int = 1500  # whisper 30s @ 50Hz after conv frontend


@dataclass(frozen=True)
class VLMConfig:
    """VLM extras; ViT frontend is a stub providing patch embeddings."""
    n_image_tokens: int = 256


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None  # default d_model // n_heads
    # Superblock pattern of block kinds; layers = pattern repeated + tail.
    pattern: Tuple[str, ...] = ("attn",)
    window: int = 1024  # sliding window for "local" blocks
    rope_theta: float = 10_000.0
    use_rope: bool = True  # False → sinusoidal absolute positions at embed
    qkv_bias: bool = False
    mlp: str = "swiglu"  # swiglu | gelu
    norm: str = "rms"  # rms | layer
    tie_embeddings: bool = True
    embed_scale: bool = False  # multiply embeddings by sqrt(d_model)
    norm_eps: float = 1e-6
    logit_softcap: float = 0.0
    moe: Optional[MoEConfig] = None
    # Leading layers whose FFN is a dense MLP of width d_ff even where
    # the rest route to experts; they run unrolled ahead of the scan.
    first_k_dense: int = 0
    mla: Optional[MLAConfig] = None
    ssm: Optional[SSMConfig] = None
    rglru: Optional[RGLRUConfig] = None
    encdec: Optional[EncDecConfig] = None
    vlm: Optional[VLMConfig] = None
    dtype: str = "bfloat16"
    kv_cache_dtype: str = "bf16"  # bf16 | int8 (per-slot-scaled quantized KV)
    # Accuracy proxy used by ModiPick pools (top-1-style score in [0,1]).
    quality: float = 0.0

    # ------------------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def padded_vocab(self) -> int:
        """Vocab padded to a lane-aligned multiple so it TP-shards over 16
        cleanly (vLLM/MaxText pad the same way)."""
        return _ceil_to(self.vocab_size, 256)

    @property
    def block_kinds(self) -> Tuple[str, ...]:
        """Per-layer kinds: the leading dense layers, then the pattern
        repeated with the remainder as a tail."""
        n = self.n_layers - self.first_k_dense
        reps = n // len(self.pattern)
        tail = n - reps * len(self.pattern)
        return (self.lead_kinds + self.pattern * reps
                + self.pattern[:tail])

    @property
    def lead_kinds(self) -> Tuple[str, ...]:
        return (self.pattern[0],) * self.first_k_dense

    @property
    def n_superblocks(self) -> int:
        return (self.n_layers - self.first_k_dense) // len(self.pattern)

    @property
    def tail_kinds(self) -> Tuple[str, ...]:
        n = self.n_layers - self.first_k_dense
        return self.pattern[: n - self.n_superblocks * len(self.pattern)]

    @property
    def is_attention_free(self) -> bool:
        return all(k in ("ssd", "rglru") for k in self.block_kinds)

    @property
    def has_global_attention(self) -> bool:
        return any(k in ("attn", "mla") for k in self.block_kinds)

    @property
    def sub_quadratic(self) -> bool:
        """Eligible for long_500k: no *dense* full-attention majority.

        SSM / hybrid / mostly-local archs qualify; sparse global layers
        (gemma3 1-in-6) are handled with context-parallel KV."""
        kinds = self.block_kinds
        n_global = sum(1 for k in kinds if k in ("attn", "mla"))
        return n_global == 0 or (n_global / len(kinds)) <= 0.25

    def _attn_params(self) -> int:
        d, hd = self.d_model, self.resolved_head_dim
        if self.mla is not None:
            a, H = self.mla, self.n_heads
            return (d * H * a.qk_head_dim                       # wq
                    + d * (a.kv_lora_rank + a.qk_rope_head_dim)  # wkv_a
                    + a.kv_lora_rank                             # kv_norm
                    + a.kv_lora_rank * H * (a.qk_nope_head_dim + a.v_head_dim)
                    + H * a.v_head_dim * d)                      # wo
        return d * hd * (self.n_heads + 2 * self.n_kv_heads) + self.n_heads * hd * d

    def _moe_params(self, held: bool = True) -> int:
        """One expert layer: router (and its bias), routed experts (the
        held share where ``held``), shared experts."""
        d, e = self.d_model, self.moe
        n = d * e.n_experts + (e.n_experts if e.scoring == "sigmoid" else 0)
        n += (e.held if held else e.n_experts) * 3 * d * e.d_ff_expert
        return n + e.n_shared * 3 * d * e.d_ff_expert

    def param_count(self) -> int:
        """Analytic parameter count (embedding + blocks), for rooflines.
        An expert share counts the experts it holds."""
        d = self.d_model
        n = self.padded_vocab * d  # embedding
        if not self.tie_embeddings:
            n += self.padded_vocab * d
        for li, kind in enumerate(self.block_kinds):
            if kind in ("attn", "local", "mla"):
                n += self._attn_params()
            elif kind == "ssd":
                s = self.ssm
                di = s.d_inner(d)
                nh = s.n_heads(d)
                conv_ch = di + 2 * s.n_groups * s.d_state
                n += d * (2 * di + 2 * s.n_groups * s.d_state + nh)
                n += conv_ch * s.conv_width + nh + nh  # conv, A_log, D
                n += di * d  # out proj
            elif kind == "rglru":
                w = self.rglru.width(d)
                n += 2 * d * w + w * self.rglru.conv_width + 2 * w * w + 4 * w + w * d
            if kind != "ssd":  # MLP for every non-ssd block
                if self.moe is not None and li >= self.first_k_dense:
                    n += self._moe_params()
                else:
                    mults = 3 if self.mlp == "swiglu" else 2
                    n += mults * d * self.d_ff
            n += 2 * d  # two norms
        if self.encdec is not None:
            hd = self.resolved_head_dim
            enc_block = d * hd * (self.n_heads + 2 * self.n_kv_heads) + self.n_heads * hd * d
            enc_block += (3 if self.mlp == "swiglu" else 2) * d * self.d_ff + 2 * d
            n += self.encdec.n_encoder_layers * enc_block
            # decoder cross-attention per layer
            n += self.n_layers * (d * hd * (self.n_heads + 2 * self.n_kv_heads) + self.n_heads * hd * d + d)
        return n

    @property
    def n_moe_layers(self) -> int:
        if self.moe is None:
            return 0
        return sum(1 for li, k in enumerate(self.block_kinds)
                   if k != "ssd" and li >= self.first_k_dense)

    def active_param_count(self) -> int:
        """Params touched per token (MoE: only routed experts)."""
        if self.moe is None:
            return self.param_count()
        e = self.moe
        per_layer_delta = (e.held - e.top_k) * 3 * self.d_model * e.d_ff_expert
        return self.param_count() - self.n_moe_layers * per_layer_delta

    def expert_share(self, n_held: int) -> "ModelConfig":
        """This chip's share of an expert-parallel deployment: experts
        ``[0, n_held)`` of every expert layer, routed over all
        ``n_experts`` as published; everything else whole."""
        if self.moe is None or not 0 < n_held <= self.moe.n_experts:
            raise ValueError(f"{self.name}: cannot hold {n_held} experts")
        return replace(self, moe=replace(self.moe, n_held=n_held))

    # ------------------------------------------------------------------
    def reduced(self) -> "ModelConfig":
        """Small same-family variant for CPU smoke tests."""
        pat = len(self.pattern)
        n_layers = (max(2 * pat, pat + 1) if pat > 1 else 2) + self.first_k_dense
        kw = dict(
            name=self.name + "-reduced",
            n_layers=n_layers,
            d_model=128,
            n_heads=4,
            n_kv_heads=min(self.n_kv_heads, 2),
            head_dim=32,
            d_ff=256,
            vocab_size=512,
            window=min(self.window, 64),
        )
        cfg = replace(self, **kw)
        if self.moe is not None:
            e = self.moe
            cfg = replace(cfg, moe=replace(
                e, n_experts=4, top_k=2, d_ff_expert=64, group_size=32,
                n_held=None if e.n_held is None else min(e.n_held, 2)))
        if self.mla is not None:
            cfg = replace(cfg, mla=MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16,
                                             qk_rope_head_dim=8, v_head_dim=16))
        if self.ssm is not None:
            cfg = replace(cfg, ssm=SSMConfig(d_state=16, head_dim=16, chunk_size=32))
        if self.rglru is not None:
            cfg = replace(cfg, rglru=RGLRUConfig(lru_width=128))
        if self.encdec is not None:
            cfg = replace(cfg, encdec=EncDecConfig(n_encoder_layers=2, n_frames=64))
        if self.vlm is not None:
            cfg = replace(cfg, vlm=VLMConfig(n_image_tokens=16))
        return cfg

    def with_padded_heads(self, multiple: int) -> "ModelConfig":
        """Pad query heads up to a multiple so attention head-shards over a
        TP axis that doesn't divide the native head count (the same trick
        as vocab padding: spend a little extra compute to unlock even
        sharding).  KV heads are left as-is (small, replicated)."""
        padded = _ceil_to(self.n_heads, multiple)
        if padded == self.n_heads or padded > self.n_heads * 1.34:
            # only worth it when the extra attention FLOPs stay ≤ ~1/3
            # (qwen2 12→16, phi4 24→32; not whisper 6→16 or rg 10→16)
            return self
        return replace(self, n_heads=padded, head_dim=self.resolved_head_dim,
                       name=self.name + f"-hpad{padded}")

    def scaled(self, width_mult: float, depth_mult: float = 1.0, name: str = "") -> "ModelConfig":
        """Scale width/depth — used to build ModiPick accuracy/latency pools."""
        d_model = _ceil_to(int(self.d_model * width_mult), 64)
        return replace(
            self,
            name=name or f"{self.name}-x{width_mult:g}",
            d_model=d_model,
            n_layers=max(len(self.pattern), int(self.n_layers * depth_mult)),
            d_ff=_ceil_to(int(self.d_ff * width_mult), 64),
            head_dim=max(16, _ceil_to(int(self.resolved_head_dim * width_mult), 16)),
        )


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    mode: str  # train | prefill | decode


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


@dataclass(frozen=True)
class TrainConfig:
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    grad_clip: float = 1.0
    schedule: str = "cosine"  # cosine | linear | constant
    remat: str = "full"  # none | full | dots
    grad_accum: int = 1
    opt_moments: str = "fp32"  # fp32 | int8 (8-bit Adam moments)
    compress_grads: bool = False  # int8 + error-feedback all-reduce
    seed: int = 0


def shape_for(name: str) -> ShapeConfig:
    return SHAPES[name]
