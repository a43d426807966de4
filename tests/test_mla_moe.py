"""Latent attention, the held-expert MoE layer and the leading dense
layer (Moonlight's DeepSeek-V3 block), at a reduced size on the CPU, on
seeded random weights in float32, against plain forms written here."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.models import mla, model as M, moe
from repro.models.layers import materialize, rope
from repro.obs import MODEL_SCOPES

KEY = jax.random.PRNGKey(0)
F32 = jnp.float32


def moon(**moe_kw):
    cfg = dataclasses.replace(get_config("moonlight-16b-a3b").reduced(),
                              dtype="float32")
    if moe_kw:
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, **moe_kw))
    return cfg


def rnd(key, shape, scale=1.0):
    return jax.random.normal(key, shape, F32) * scale


# ----------------------------------------------------------------------
# latent attention
# ----------------------------------------------------------------------
def plain_mla(p, x, cfg):
    """Expanded-form causal MLA over the whole sequence, written out."""
    a, H = cfg.mla, cfg.n_heads
    B, S, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    q = (x @ p["wq"]).reshape(B, S, H, a.qk_head_dim)
    q = jnp.concatenate([q[..., :a.qk_nope_head_dim],
                         rope(q[..., a.qk_nope_head_dim:], pos, cfg.rope_theta)], -1)
    kv_a = x @ p["wkv_a"]
    c = kv_a[..., :a.kv_lora_rank]
    c = c / jnp.sqrt(jnp.mean(c * c, -1, keepdims=True) + cfg.norm_eps) \
        * (1.0 + p["kv_norm"]["scale"])
    k_pe = rope(kv_a[:, :, None, a.kv_lora_rank:], pos, cfg.rope_theta)
    kv = (c @ p["wkv_b"]).reshape(B, S, H, -1)
    k = jnp.concatenate([kv[..., :a.qk_nope_head_dim],
                         jnp.broadcast_to(k_pe, (B, S, H, a.qk_rope_head_dim))], -1)
    v = kv[..., a.qk_nope_head_dim:]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(a.qk_head_dim)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    return o.reshape(B, S, -1) @ p["wo"]


def mla_params(cfg):
    p = materialize(mla.mla_template(cfg), KEY, F32)
    p["kv_norm"]["scale"] = rnd(jax.random.PRNGKey(5), p["kv_norm"]["scale"].shape, 0.1)
    return p


def test_mla_prefill_then_decode_matches_expanded_forward():
    """Prefill of S tokens, then two steps through the latent cache, give
    what the plain expanded form gives at those positions (float32
    throughout: 1e-4 covers the order of summation)."""
    cfg = moon()
    p = mla_params(cfg)
    B, S = 2, 11
    x = rnd(jax.random.PRNGKey(1), (B, S + 2, cfg.d_model))
    want = plain_mla(p, x, cfg)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    out, cache = mla.prefill_mla(p, x[:, :S], pos, cfg, cache_len=16)
    assert cache["c_kv"].shape == (B, 16, cfg.mla.kv_lora_rank)
    assert cache["k_pe"].shape == (B, 16, cfg.mla.qk_rope_head_dim)
    np.testing.assert_allclose(out, want[:, :S], rtol=1e-4, atol=1e-4)
    for t in (S, S + 1):
        step, cache = mla.decode_mla(p, cache, x[:, t:t + 1],
                                     jnp.full((B,), t, jnp.int32), cfg)
        np.testing.assert_allclose(step[:, 0], want[:, t], rtol=1e-4, atol=1e-4)


def test_absorbed_decode_matches_expanded_decode():
    """One decode step in the absorbed form equals the same step with
    keys and values rebuilt per head from the cached latent (1e-4: the
    two forms sum in different orders)."""
    cfg = moon()
    a, H = cfg.mla, cfg.n_heads
    p = mla_params(cfg)
    B, S, C = 2, 9, 12
    x = rnd(jax.random.PRNGKey(2), (B, S + 1, cfg.d_model))
    _, cache = mla.prefill_mla(p, x[:, :S], jnp.broadcast_to(jnp.arange(S), (B, S)),
                               cfg, cache_len=C)
    pos = jnp.full((B,), S, jnp.int32)
    out, new = mla.decode_mla(p, cache, x[:, S:], pos, cfg)

    q_nope, q_pe, _, _ = mla._project(p, x[:, S:], pos[:, None], cfg)
    kv = (new["c_kv"] @ p["wkv_b"]).reshape(B, C, H, -1)
    k = jnp.concatenate([kv[..., :a.qk_nope_head_dim],
                         jnp.broadcast_to(new["k_pe"][:, :, None], (B, C, H, a.qk_rope_head_dim))], -1)
    q = jnp.concatenate([q_nope, q_pe], -1)[:, 0]
    s = jnp.einsum("bhd,bkhd->bhk", q, k) / np.sqrt(a.qk_head_dim)
    s = jnp.where(jnp.arange(C) <= S, s, -jnp.inf)
    o = jnp.einsum("bhk,bkhd->bhd", jax.nn.softmax(s, -1), kv[..., a.qk_nope_head_dim:])
    np.testing.assert_allclose(out[:, 0], o.reshape(B, -1) @ p["wo"], rtol=1e-4, atol=1e-4)


# ----------------------------------------------------------------------
# routing and the held-expert layer
# ----------------------------------------------------------------------
def moe_params(cfg, key=KEY):
    p = materialize(moe.moe_template(cfg), key, F32)
    if "bias" in p:
        p["bias"] = rnd(jax.random.PRNGKey(7), p["bias"].shape, 0.3)
    return p


def test_routing_rule_bias_chooses_unbiased_gates_scaled():
    cfg = moon(n_experts=16, top_k=4)
    e = cfg.moe
    p = moe_params(cfg)
    x = rnd(jax.random.PRNGKey(3), (32, cfg.d_model))
    idx, gates, _ = moe.route(p, x, cfg)
    scores = np.asarray(jax.nn.sigmoid(x @ p["router"]))
    choice = scores + np.asarray(p["bias"])
    want_idx = np.argsort(-choice, axis=-1)[:, :e.top_k]
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(want_idx, -1))
    g = np.take_along_axis(scores, np.asarray(idx), -1)
    np.testing.assert_allclose(gates, g / g.sum(-1, keepdims=True) * e.routed_scale,
                               rtol=1e-6)
    assert e.routed_scale == 2.446
    # the bias moved the choice somewhere: gates are not the top scores
    assert (np.sort(idx, -1) != np.sort(np.argsort(-scores, -1)[:, :e.top_k], -1)).any()


def test_expert_shares_add_up_to_the_whole_layer():
    """16 experts in 4 shares of 4: each share routes over all 16 and
    computes its own experts' part; the parts, with the shared experts
    counted once, add up to the whole layer (prefill and decode forms)."""
    whole = moon(n_experts=16, top_k=4)
    p = moe_params(whole)
    x = rnd(jax.random.PRNGKey(4), (2, 6, whole.d_model))
    want = moe.moe_ffn_dense_eval(p, x, whole)
    share = whole.expert_share(4)
    shared = moe._shared(p, x, whole)
    total_pre, total_dec = shared, shared
    for s in range(4):
        # share s holds experts 4s..4s+3: relabel them 0..3
        perm = np.r_[np.arange(4 * s, 4 * s + 4), np.delete(np.arange(16), np.s_[4 * s:4 * s + 4])]
        ps = dict(p, router=p["router"][:, perm], bias=p["bias"][perm],
                  experts={k: v[4 * s:4 * s + 4] for k, v in p["experts"].items()})
        part, _, _ = moe.held_ffn_grouped(ps, x, share)
        total_pre = total_pre + part - shared
        dec = [moe.held_ffn_decode(ps, x[:, t:t + 1], share, ps["experts"])[0]
               for t in range(x.shape[1])]
        total_dec = total_dec + jnp.concatenate(dec, 1) - shared
    np.testing.assert_allclose(total_pre, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(total_dec, want, rtol=1e-4, atol=1e-5)


def test_dropless_under_a_skewed_router():
    """A router that sends every token to the same experts: the held
    layer computes every assignment (GShard's capacity would drop most)."""
    cfg = moon(n_experts=8, top_k=2)
    p = moe_params(cfg)
    p["bias"] = jnp.zeros(8).at[jnp.array([1, 5])].set(100.0)
    x = rnd(jax.random.PRNGKey(6), (1, 64, cfg.d_model))
    out, counts, chosen = moe.held_ffn_grouped(p, x, cfg)
    np.testing.assert_allclose(out, moe.moe_ffn_dense_eval(p, x, cfg), rtol=1e-4, atol=1e-5)
    assert counts.tolist() == [2, 128, 128]
    assert sorted(set(np.asarray(chosen).ravel().tolist())) == [1, 5]
    gshard, _ = moe.moe_ffn(p, x, cfg)
    assert not np.allclose(gshard, out, atol=1e-3)


def test_decode_runs_no_expert_that_no_token_chose():
    """Held experts no token chose are not computed: their weights set
    to NaN leave the step finite and right, and the counter counts the
    experts that ran."""
    cfg = moon(n_experts=16, top_k=2).expert_share(8)
    p = moe_params(cfg)
    x = rnd(jax.random.PRNGKey(8), (1, 1, cfg.d_model))
    idx, _, _ = moe.route(p, x[0], cfg)
    chosen = sorted(set(int(i) for i in np.asarray(idx).ravel() if i < 8))
    idle = [i for i in range(8) if i not in chosen]
    want = moe.moe_ffn_dense_eval(p, x, cfg)
    poisoned = dict(p, experts={k: v.at[jnp.array(idle)].set(jnp.nan)
                                for k, v in p["experts"].items()})
    out, counts, picked = jax.jit(
        lambda q, x: moe.held_ffn_decode(q, x, cfg, q["experts"]))(poisoned, x)
    np.testing.assert_array_equal(picked[0], idx)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)
    assert counts.tolist() == [len(chosen), len(chosen), 2]


# ----------------------------------------------------------------------
# the model: leading dense layer, counters, spans, scopes
# ----------------------------------------------------------------------
def test_leading_dense_layer():
    cfg = moon()
    assert cfg.first_k_dense == 1 and cfg.n_superblocks == cfg.n_layers - 1
    params = M.init_params(cfg, KEY, F32)
    lead = params["lead"]["l0"]
    assert set(lead["mlp"]) == {"wi", "wg", "wo"}
    assert lead["mlp"]["wi"].shape == (cfg.d_model, cfg.d_ff)
    assert "experts" in params["blocks"]["p0"]["mlp"]
    tokens = jax.random.randint(KEY, (1, 8), 0, cfg.vocab_size)
    cache, logits = M.prefill(cfg, params, {"tokens": tokens}, 16)
    assert set(cache["lead"]["l0"]) == {"c_kv", "k_pe"}
    # expert layers keep each position's chosen experts
    route = cache["blocks"]["p0"]["route"]
    assert route.shape == (cfg.n_superblocks, 1, 16, cfg.moe.top_k)
    assert route.dtype == jnp.int8 and (np.asarray(route)[:, :, 8:] == 0).all()
    # the dense layer runs: a change to its MLP moves the logits
    moved = jax.tree.map(lambda a: a, params)
    moved["lead"]["l0"]["mlp"]["wo"] = lead["mlp"]["wo"] * 3.0
    _, other = M.prefill(cfg, moved, {"tokens": tokens}, 16)
    assert not np.allclose(logits, other)
    # every leaf but the final norm's scale is counted
    assert cfg.param_count() + cfg.d_model == sum(
        a.size for a in jax.tree.leaves(params))


def test_published_config_and_share_sizes():
    cfg = get_config("moonlight-16b-a3b")
    assert abs(cfg.param_count() - 15.96e9) < 0.02e9
    share = cfg.expert_share(8)
    assert share.moe.n_experts == 64 and share.moe.held == 8
    assert abs(share.param_count() - 3.365e9) < 0.01e9
    assert M.param_template(share)["blocks"]["p0"]["mlp"]["router"].shape == (26, 2048, 64)


def test_counters_and_span_on_the_cpu_profiler(tmp_path):
    from repro.core.netmodel import NetworkModel
    from repro.core.policy import ModiPick
    from repro.launch.serve import build_pool
    from repro.obs import SERVING_SPANS
    from repro.serving.executor import PoolExecutor
    from test_obs import host_spans, inside, traced

    cfg = moon(n_experts=16, top_k=4).expert_share(8)
    (v,) = build_pool([cfg], cache_len=32, seed=0)
    ex = PoolExecutor([v], NetworkModel(15.0, 7.0), ModiPick(20.0), seed=0,
                      warmup_requests=1)
    tokens = np.arange(10, dtype=np.int32)[None] % cfg.vocab_size
    ex.warm_up(tokens, 3)
    res = traced(tmp_path, lambda: ex.execute(tokens, 5000.0, 3))
    spans = host_spans(tmp_path, SERVING_SPANS)
    (run,), (sync,), (read,) = (spans["pool.run"], spans["pool.run.sync"],
                                spans["pool.run.counters"])
    assert inside(read, run) and sync[1] <= read[0]
    layers, K = cfg.n_moe_layers, cfg.moe.top_k
    c = res.moe_counts
    assert c["prefill"]["assignments"] == 10 * K * layers
    assert c["decode"]["assignments"] == 3 * K * layers
    for ph, steps in (("prefill", 1), ("decode", 3)):
        assert 0 < c[ph]["experts_ran"] <= min(8, 10 * K) * layers * steps
        assert c[ph]["experts_ran"] <= c[ph]["held_assignments"] <= c[ph]["assignments"]
    s = ex.summary()
    assert s["experts_ran_by_member"] == {v.name: {"prefill": c["prefill"]["experts_ran"],
                                                   "decode": c["decode"]["experts_ran"]}}
    held = c["prefill"]["held_assignments"] + c["decode"]["held_assignments"]
    assert s["held_assignment_share_by_member"][v.name] == pytest.approx(
        held / (13 * K * layers))


def test_named_scopes_in_the_lowered_hlo():
    from repro.serving import pool
    cfg = moon(n_experts=8, top_k=2).expert_share(4)
    params = M.init_params(cfg, KEY, F32)
    tokens = jnp.zeros((1, 8), jnp.int32)
    pre = pool.prefill_step.lower(cfg, params, tokens, cache_len=16).as_text(
        debug_info=True)
    cache, _ = pool.prefill_step(cfg, params, tokens, cache_len=16)
    vec = jnp.zeros((1,), jnp.int32)
    dec = pool.decode_step.lower(cfg, params, cache, vec, vec + 8).as_text(
        debug_info=True)
    for text in (pre, dec):
        for scope in MODEL_SCOPES:
            assert scope in text, scope
