"""Per-architecture smoke tests: every assigned arch instantiates a
REDUCED same-family config and runs forward / train / prefill+decode on
CPU, asserting output shapes and finiteness."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ShapeConfig, TrainConfig
from repro.configs.registry import ARCH_IDS, get_config
from repro.models import api, model as M
from repro.training.train_step import init_train_state, make_train_step

KEY = jax.random.PRNGKey(0)


def _batch(cfg, seq, batch, key=KEY, with_targets=True):
    b = api.make_train_batch(cfg, ShapeConfig("t", seq, batch, "train"), key)
    if not with_targets:
        b.pop("targets", None)
    return b


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_reduced_forward_and_shapes(arch):
    cfg = get_config(arch).reduced()
    params = M.init_params(cfg, KEY, jnp.float32)
    batch = _batch(cfg, 64, 2)
    loss, metrics = M.forward_train(cfg, params, batch)
    assert np.isfinite(float(loss))
    assert np.isfinite(float(metrics["loss"]))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_reduced_train_step(arch):
    cfg = get_config(arch).reduced()
    tcfg = TrainConfig(total_steps=2, warmup_steps=1, learning_rate=1e-3)
    params, opt = init_train_state(cfg, KEY, jnp.float32)
    step = jax.jit(make_train_step(cfg, tcfg))
    batch = _batch(cfg, 32, 2)
    params, opt, m1 = step(params, opt, batch)
    params, opt, m2 = step(params, opt, batch)
    assert np.isfinite(float(m1["loss"])) and np.isfinite(float(m2["loss"]))
    # one repeated batch: loss must decrease
    assert float(m2["loss"]) < float(m1["loss"])


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_matches_full_forward(arch):
    """Prefill S tokens + decode token S ≡ full forward over S+1 tokens.
    Validates every cache kind (KV ring, SSM state, RG-LRU state, cross)."""
    cfg = get_config(arch).reduced()
    if cfg.moe is not None:  # avoid capacity-drop divergence
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    params = M.init_params(cfg, KEY, jnp.float32)
    B, S = 2, 37
    n_img = cfg.vlm.n_image_tokens if cfg.vlm else 0
    full = _batch(cfg, S + 1 + n_img, B, with_targets=False)
    toks = full["tokens"]
    pre = dict(full)
    pre["tokens"] = toks[:, :S]
    cache, _ = M.prefill(cfg, params, pre, cache_len=64)
    pos = jnp.full((B,), S + n_img, jnp.int32)
    lg_dec, _ = M.decode_step(cfg, params, cache, toks[:, S], pos)
    _, lg_full = M.prefill(cfg, params, full, cache_len=64)
    a = np.asarray(lg_dec, np.float32)
    b = np.asarray(lg_full, np.float32)
    rel = np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-9)
    assert rel < 2e-3, f"{arch}: rel={rel:.2e}"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_exact(arch):
    """The full (non-reduced) configs carry the exact assigned shapes."""
    cfg = get_config(arch)
    expected = {
        "recurrentgemma-2b": (26, 2560, 10, 1, 7680, 256000),
        "mamba2-1.3b": (48, 2048, 1, 1, 0, 50280),
        "qwen2-1.5b": (28, 1536, 12, 2, 8960, 151936),
        "phi4-mini-3.8b": (32, 3072, 24, 8, 8192, 200064),
        "command-r-35b": (40, 8192, 64, 8, 22528, 256000),
        "gemma3-4b": (34, 2560, 8, 4, 10240, 262144),
        "whisper-tiny": (4, 384, 6, 6, 1536, 51865),
        "dbrx-132b": (40, 6144, 48, 8, 10752, 100352),
        "moonlight-16b-a3b": (27, 2048, 16, 16, 11264, 163840),
        "internvl2-2b": (24, 2048, 16, 8, 8192, 92553),
    }[arch]
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
           cfg.d_ff, cfg.vocab_size)
    assert got == expected


def test_moe_configs():
    dbrx = get_config("dbrx-132b").moe
    assert (dbrx.n_experts, dbrx.top_k) == (16, 4)
    moon = get_config("moonlight-16b-a3b").moe
    assert (moon.n_experts, moon.top_k) == (64, 6)


def test_long_context_skip_list():
    """long_500k runs only for sub-quadratic archs (DESIGN.md skip list)."""
    from repro.configs.registry import applicable_shapes
    runs_500k = {a for a in ARCH_IDS
                 if any(s.name == "long_500k"
                        for s in applicable_shapes(get_config(a)))}
    assert runs_500k == {"recurrentgemma-2b", "mamba2-1.3b", "gemma3-4b"}
