"""The Moonlight member's float32 reference and its costs, tied to the
program at a small width on the CPU: the same weights from the seed,
the same logits as ``models/model.py``'s prefill plus decode in
float32, a float8 control that lands farther off than the bf16
program, a whole small run of ``moonlight.steady`` that comes out
correct, and a member check that refuses a program that differs."""
import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import small
from chipbench import moe_costs
from chipbench.bench import HERE, cell_files, load_json, load_module, run_cell

ref = load_module(f"{HERE}/configs/mla_moe_ref.py")
SEED = 2**31 + 17
MOON = {"num_hidden_layers": 3, "hidden_size": 128, "num_attention_heads": 4,
        "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "intermediate_size": 256, "moe_intermediate_size": 64,
        "n_routed_experts": 16, "experts_held": 4, "vocab_size": 32768}


def program_cfg(member, dtype="bfloat16", whole=False):
    """The program's config of the small member, as the driver builds it
    (``whole``: as the registry gives it, all experts held)."""
    from repro.configs.base import MLAConfig
    from repro.configs.registry import _FACTORIES
    cfg = _FACTORIES[member["arch"]]()
    if "kv_lora_rank" not in member:
        return dataclasses.replace(cfg, dtype=dtype, **small.WIDTHS)
    m = member
    cfg = dataclasses.replace(
        cfg, dtype=dtype, n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"], d_ff=m["intermediate_size"],
        vocab_size=m["vocab_size"],
        mla=MLAConfig(m["kv_lora_rank"], m["qk_nope_head_dim"],
                      m["qk_rope_head_dim"], m["v_head_dim"]),
        moe=dataclasses.replace(cfg.moe, n_experts=m["n_routed_experts"],
                                d_ff_expert=m["moe_intermediate_size"]))
    return cfg if whole else cfg.expert_share(m["experts_held"])


def small_cell():
    bench = load_json(small.ROOT, "BENCHMARK.json")
    _, config, traffic = cell_files(bench, "moonlight.steady")
    config, traffic = copy.deepcopy(config), copy.deepcopy(traffic)
    config["members"][0].update(small.WIDTHS)
    config["members"][1].update(MOON)
    traffic["token_ids"] = MOON["vocab_size"]
    traffic["fields"]["prompt_len"]["values"] = [8, 16, 32, 64, 96, 128]
    traffic["warmup"] = {"prompt_len": 16, "n_decode": 2}
    traffic["arrivals"]["rate_rps"] = 6.0
    config["cache_len"] = 144
    return bench, config, traffic


@pytest.fixture(scope="module")
def moon():
    return small_cell()[1]["members"][1]


def small_registry(monkeypatch):
    from repro.configs import registry
    members = {m["arch"]: m for m in small_cell()[1]["members"]}
    monkeypatch.setattr(registry, "get_config",
                        lambda arch: program_cfg(members[arch], whole=True))


def test_weights_are_the_served_members(moon):
    from repro.launch import serve
    (v,) = serve.build_pool([program_cfg(moon)], seed=SEED, cache_len=32)
    w = ref.make_weights(ref.dims(moon), SEED, 0, 1)
    flat = jax.tree_util.tree_flatten_with_path(v.params)[0]
    assert len(flat) == len(w)
    for path, leaf in flat:
        name = ".".join(k.key for k in path if k.key != "p0")
        assert leaf.dtype == w[name].dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(leaf, np.float32),
                                      np.asarray(w[name], np.float32))


def test_reference_is_prefill_plus_decode(moon):
    """Program in float32 with the reference's weights: the prefill's
    last logits and three decode steps match the reference (2e-4: the
    absorbed decode and the expanded reference sum in different
    orders)."""
    from repro.models import model as M
    d = ref.dims(moon)
    w = ref.make_weights(d, SEED, 0, 1)
    cfg = program_cfg(moon, "float32")
    params = M.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    params = tree.unflatten([
        w[".".join(k.key for k in p if k.key != "p0")].astype(jnp.float32)
        for p, _ in flat])
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, moon["vocab_size"], 20).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        cache, logits = M.prefill(cfg, params, {"tokens": prompt[None]}, 32)
        got, fed = [logits[0, :moon["vocab_size"]]], []
        for j in range(3):
            tok = jnp.argmax(got[-1])[None].astype(jnp.int32)
            fed.append(int(tok[0]))
            logits, cache = M.decode_step(cfg, params, cache, tok,
                                          jnp.array([20 + j], jnp.int32))
            got.append(logits[0, :moon["vocab_size"]])
    tokens = np.zeros((1, 32), np.int32)
    tokens[0, :23] = np.concatenate([prompt, fed])
    pos = (19 + np.arange(4))[None].astype(np.int32)
    want = ref.forward_logits(d, w, tokens, pos)[0]
    np.testing.assert_allclose(np.stack(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_control_lands_farther_than_the_program(moon):
    """The float8 control's tokens lie farther below the reference's
    best than the bf16 program's served tokens do, and its routing
    differs from the reference's more often, with the reference and the
    control gating on the program's routing (which the cache holds)."""
    from repro.launch import serve
    (v,) = serve.build_pool([program_cfg(moon)], seed=SEED, cache_len=80)
    rng = np.random.default_rng(1)
    reqs, routes = [], []
    for s in (8, 16, 32, 64) * 2:
        prompt = rng.integers(0, moon["vocab_size"], s).astype(np.int32)
        cache, logits = v.prefill_fn(v.params, jnp.asarray(prompt[None]))
        served = [int(jnp.argmax(logits[0]))]
        for j in range(8):
            logits, cache = v.decode_fn(v.params, cache,
                                        jnp.array(served[-1:], jnp.int32),
                                        jnp.array([s + j], jnp.int32))
            served.append(int(jnp.argmax(logits[0])))
        reqs.append((prompt, served))
        routes.append(np.asarray(cache["blocks"]["p0"]["route"])[:, 0])
    d = ref.dims(moon)
    r = ref.served_gaps(d, ref.make_weights(d, SEED, 0, 1), reqs, length=80,
                        control=True, routes=routes)
    prog = max(float(g.max()) for g in r.gaps)
    low = max(float(g.max()) for g in r.ctrl)
    assert low > 3 * prog and low > 0.02, (prog, low)
    assert r.total == sum(len(p) + len(t) - 1 for p, t in reqs) * (d.n_layers - 1)
    assert r.ctrl_differ > 3 * r.differ, (r.differ, r.ctrl_differ, r.total)


def test_small_run_is_correct_and_refuses_another_program(monkeypatch):
    bench, config, traffic = small_cell()
    small_registry(monkeypatch)
    result, checks = run_cell("moonlight.steady", SEED, 2.0, False, bench=bench,
                              config=config, traffic=traffic, chip=False)
    assert result["correct"], checks
    assert result["attempted"] > 5 and result["failed"] == 0
    wrong = copy.deepcopy(config)
    wrong["members"][1]["num_experts_per_tok"] = 8
    with pytest.raises(RuntimeError, match="num_experts_per_tok"):
        run_cell("moonlight.steady", SEED, 2.0, False, bench=bench,
                 config=wrong, traffic=traffic, chip=False)


def test_costs_of_the_published_share():
    _, config, _ = cell_files(load_json(small.ROOT, "BENCHMARK.json"),
                              "moonlight.steady")
    m = config["members"][moe_costs.moe_member(config)]
    assert m["n_routed_experts"] == 64 and m["experts_held"] == 8
    assert moe_costs.attention_weights(m) == 13_763_072
    assert moe_costs.expert_weights(m) == 8_650_752
    assert abs(moe_costs.outside_experts(m) - 1.23e9) < 0.005e9
    # a decode step bound by bytes: the held experts that ran add theirs
    a = moe_costs.decode(m, 100, ran=0, assigned=0)
    b = moe_costs.decode(m, 100, ran=19.5, assigned=19.5)
    assert b.bytes - a.bytes == pytest.approx(19.5 * 2 * 8_650_752)
    assert b.flops - a.flops == pytest.approx(19.5 * 2 * 8_650_752)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert b.bytes / peaks["hbm_bytes_per_s"] > b.flops / peaks["bf16_flops_per_s"]
    p = moe_costs.prefill(m, 2048, ran=26 * 8, assigned=2048 * 6 * 26 / 8)
    assert p.flops / peaks["bf16_flops_per_s"] > p.bytes / peaks["hbm_bytes_per_s"]
