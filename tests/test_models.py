"""Unit tests for model components (hypothesis where it pays off)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.base import MoEConfig, SSMConfig
from repro.configs.registry import get_config
from repro.kernels import ref
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import materialize, rmsnorm
from repro.models.rglru import rglru_scan_xla

KEY = jax.random.PRNGKey(0)


def test_windowed_attention_equals_masked_full():
    B, S, H, KV, hd, W = 2, 192, 4, 2, 32, 48
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, hd), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    banded = attn_mod.attention_windowed(q, k, v, pos, pos, window=W, q_chunk=64)
    naive = ref.flash_attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=True, window=W).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(banded), np.asarray(naive),
                               rtol=2e-5, atol=2e-5)


def test_moe_dispatch_matches_dense_oracle():
    cfg = dataclasses.replace(
        get_config("dbrx-132b").reduced(),
        moe=MoEConfig(n_experts=4, top_k=2, d_ff_expert=32,
                      capacity_factor=8.0, group_size=16))
    params = materialize(moe_mod.moe_template(cfg), KEY, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model), jnp.float32)
    out, aux = moe_mod.moe_ffn(params, x, cfg)
    oracle = moe_mod.moe_ffn_dense_eval(params, x, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=1e-4, atol=1e-4)
    assert float(aux) > 0.0


def test_moe_drops_bounded_by_capacity():
    cfg = dataclasses.replace(
        get_config("dbrx-132b").reduced(),
        moe=MoEConfig(n_experts=4, top_k=2, d_ff_expert=32,
                      capacity_factor=0.5, group_size=16))
    params = materialize(moe_mod.moe_template(cfg), KEY, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model), jnp.float32)
    out, _ = moe_mod.moe_ffn(params, x, cfg)
    assert np.isfinite(np.asarray(out)).all()


def test_ssd_chunked_matches_sequential_ref():
    B, H, G, S, hd, N = 2, 4, 1, 96, 16, 24
    ks = jax.random.split(KEY, 5)
    xh = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H), jnp.float32))
    A = -jnp.exp(jax.random.normal(ks[2], (H,), jnp.float32) * 0.3)
    B_ = jax.random.normal(ks[3], (B, S, G, N), jnp.float32) * 0.3
    C_ = jax.random.normal(ks[4], (B, S, G, N), jnp.float32) * 0.3
    y = ssm_mod.ssd_chunked(xh, dt, A, B_, C_, chunk=32)
    y_ref = ref.ssd_scan_ref(
        xh.transpose(0, 2, 1, 3), dt.transpose(0, 2, 1),
        A, B_.transpose(0, 2, 1, 3), C_.transpose(0, 2, 1, 3))
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(y_ref.transpose(0, 2, 1, 3)),
                               rtol=1e-4, atol=1e-4)


def test_ssd_final_state_matches_decode_continuation():
    """Prefill final state + one decode step ≡ longer sequential scan."""
    B, H, G, S, hd, N = 1, 2, 1, 64, 8, 16
    ks = jax.random.split(KEY, 5)
    xh = jax.random.normal(ks[0], (B, S + 1, H, hd), jnp.float32) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S + 1, H), jnp.float32))
    A = -jnp.exp(jax.random.normal(ks[2], (H,), jnp.float32) * 0.3)
    B_ = jax.random.normal(ks[3], (B, S + 1, G, N), jnp.float32) * 0.3
    C_ = jax.random.normal(ks[4], (B, S + 1, G, N), jnp.float32) * 0.3
    _, state = ssm_mod.ssd_chunked(xh[:, :S], dt[:, :S], A, B_[:, :S],
                                   C_[:, :S], chunk=32, return_final_state=True)
    # manual single-step with the recurrence h = exp(dtA) h + dt·B⊗x
    decay = jnp.exp(dt[:, S] * A)  # (B,H)
    Bh = jnp.repeat(B_[:, S], H // G, axis=1)
    upd = dt[:, S][..., None, None] * xh[:, S][..., None] * Bh[:, :, None, :]
    h_next = state * decay[..., None, None] + upd
    y_full = ref.ssd_scan_ref(
        xh.transpose(0, 2, 1, 3), dt.transpose(0, 2, 1), A,
        B_.transpose(0, 2, 1, 3), C_.transpose(0, 2, 1, 3))
    Ch = jnp.repeat(C_[:, S], H // G, axis=1)
    y_step = jnp.einsum("bhpn,bhn->bhp", h_next, Ch)
    np.testing.assert_allclose(np.asarray(y_step),
                               np.asarray(y_full[:, :, S]), rtol=1e-4, atol=1e-4)


@given(st.integers(1, 3), st.integers(2, 64), st.integers(1, 32),
       st.integers(0, 2**31 - 1))
@settings(max_examples=30, deadline=None)
def test_rglru_scan_property(B, S, W, seed):
    """Associative-scan path ≡ sequential recurrence for random shapes."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, W), jnp.float32))
    b = jax.random.normal(ks[1], (B, S, W), jnp.float32)
    h = rglru_scan_xla(a, b)
    h_ref = ref.rglru_scan_ref(a, b)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                               rtol=1e-5, atol=1e-5)


def test_rmsnorm_scale_identity():
    x = jax.random.normal(KEY, (2, 8, 16), jnp.float32)
    out = rmsnorm(x, jnp.zeros(16), 1e-6)
    norm = np.sqrt(np.mean(np.asarray(out) ** 2, axis=-1))
    np.testing.assert_allclose(norm, 1.0, rtol=1e-3)


def test_vocab_padding_masked():
    cfg = get_config("internvl2-2b").reduced()  # vocab 512 (already padded)
    assert cfg.padded_vocab % 256 == 0
    full = get_config("internvl2-2b")
    assert full.padded_vocab >= full.vocab_size
    assert full.padded_vocab % 256 == 0


def test_int8_kv_cache_decode_close_to_bf16():
    """int8 KV (per-slot scales): decode agrees with full forward to
    quantization noise, and cache leaves are actually int8."""
    import jax
    from repro.configs.base import ShapeConfig
    from repro.models import api, model as M

    cfg = dataclasses.replace(get_config("qwen2-1.5b").reduced(),
                              kv_cache_dtype="int8")
    params = M.init_params(cfg, KEY, jnp.float32)
    B, S = 2, 37
    full = api.make_train_batch(cfg, ShapeConfig("x", S + 1, B, "prefill"), KEY)
    full.pop("targets", None)
    toks = full["tokens"]
    pre = dict(full)
    pre["tokens"] = toks[:, :S]
    cache, _ = M.prefill(cfg, params, pre, cache_len=64)
    dtypes = {str(l.dtype) for l in jax.tree.leaves(cache)}
    assert "int8" in dtypes
    lg_dec, _ = M.decode_step(cfg, params, cache, toks[:, S],
                              jnp.full((B,), S, jnp.int32))
    _, lg_full = M.prefill(cfg, params, full, cache_len=64)
    a = np.asarray(lg_dec, np.float32)
    b = np.asarray(lg_full, np.float32)
    rel = np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-9)
    assert rel < 5e-2, rel
