"""moonlight-16b-a3b [moe]: DeepSeek-V3 block — latent attention, one
leading dense layer, then 64 sigmoid-routed experts (top-6) with 2 shared.

27L d_model=2048 16H MLA (kv_lora 512, qk 128+64 rope, v 128)
dense d_ff=11264, expert d_ff=1408, vocab=163840, untied
[hf:moonshotai/Moonlight-16B-A3B config.json, model_type deepseek_v3]

Routing is ``noaux_tc`` with one group (n_group = topk_group = 1): the
experts are chosen on sigmoid scores plus a correction bias, and the
gates are the unbiased scores of the chosen six, normalised
(norm_topk_prob) and scaled by 2.446.  Queries take no latent
(q_lora_rank null).  ``n_kv_heads`` is the published ``num_key_value_heads``; latent
attention does not read it.
"""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="moonlight-16b-a3b",
        family="moe",
        n_layers=27,
        d_model=2048,
        n_heads=16,
        n_kv_heads=16,
        d_ff=11_264,
        vocab_size=163_840,
        pattern=("mla",),
        rope_theta=50_000.0,
        mlp="swiglu",
        norm="rms",
        norm_eps=1e-5,
        tie_embeddings=False,
        first_k_dense=1,
        mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                      qk_rope_head_dim=64, v_head_dim=128),
        moe=MoEConfig(n_experts=64, top_k=6, d_ff_expert=1408, n_shared=2,
                      scoring="sigmoid", routed_scale=2.446),
        quality=0.74,
    )
