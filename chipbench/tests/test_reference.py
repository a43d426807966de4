"""The float32 reference of the pool members, tied to the program at a
small width on the CPU: the same weights from the seed, the same logits
as ``models/model.py``'s prefill plus decode in float32, and a float8
control that lands farther from it than the bf16 program does."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import small
from chipbench.bench import HERE, load_module

ref = load_module(f"{HERE}/configs/dense_lm_ref.py")
SEED = 2**31 + 11


@pytest.fixture(scope="module")
def members():
    _, config, _ = small.small_cell("pool2.steady")
    return config["members"]


def _program_cfg(member, dtype="bfloat16"):
    from repro.configs.registry import _FACTORIES
    return dataclasses.replace(_FACTORIES[member["arch"]](), dtype=dtype,
                               **small.WIDTHS)


def test_weights_are_the_served_pools(members):
    from repro.launch import serve
    pool = serve.build_pool([_program_cfg(m) for m in members], seed=SEED,
                            cache_len=32)
    for i, (m, v) in enumerate(zip(members, pool)):
        w = ref.make_weights(ref.dims(m), SEED, i, len(members))
        flat = jax.tree_util.tree_flatten_with_path(v.params)[0]
        assert len(flat) == len(w)
        for path, leaf in flat:
            name = ".".join(k.key for k in path
                            if k.key not in ("blocks", "p0"))
            assert leaf.dtype == w[name].dtype == jnp.bfloat16
            np.testing.assert_array_equal(np.asarray(leaf, np.float32),
                                          np.asarray(w[name], np.float32))


@pytest.mark.parametrize("index", [0, 1])
def test_reference_is_prefill_plus_decode(members, index):
    """Program in float32 with the reference's weights: the prefill's
    last logits and three decode steps match the reference's positions."""
    from repro.models import model as M
    m = members[index]
    d = ref.dims(m)
    w = ref.make_weights(d, SEED, index, len(members))
    cfg = _program_cfg(m, "float32")
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          M.init_params(cfg, jax.random.PRNGKey(0),
                                        jnp.float32))
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    params = tree.unflatten([
        w[".".join(k.key for k in p if k.key not in ("blocks", "p0"))]
        .astype(jnp.float32) for p, _ in flat])
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, m["vocab_size"], 20).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        cache, logits = M.prefill(cfg, params, {"tokens": prompt[None]}, 32)
        got, fed = [logits[0, :m["vocab_size"]]], []
        for j in range(3):
            tok = jnp.argmax(got[-1])[None].astype(jnp.int32)
            fed.append(int(tok[0]))
            logits, cache = M.decode_step(cfg, params, cache, tok,
                                          jnp.array([20 + j], jnp.int32))
            got.append(logits[0, :m["vocab_size"]])
    tokens = np.zeros((1, 32), np.int32)
    tokens[0, :23] = np.concatenate([prompt, fed])
    pos = (19 + np.arange(4))[None].astype(np.int32)
    want = ref.forward_logits(d, w, tokens, pos)[0]
    np.testing.assert_allclose(np.stack(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_control_lands_farther_than_the_program(members):
    """The float8 control's tokens lie farther below the reference's
    best than the bf16 program's served tokens do."""
    from repro.launch import serve
    pool = serve.build_pool([_program_cfg(m) for m in members], seed=SEED,
                            cache_len=80)
    rng = np.random.default_rng(1)
    for i, (m, v) in enumerate(zip(members, pool)):
        reqs = []
        for s in (8, 16, 32, 64) * 3:
            prompt = rng.integers(0, m["vocab_size"], s).astype(np.int32)
            cache, logits = v.prefill_fn(v.params, jnp.asarray(prompt[None]))
            served = [int(jnp.argmax(logits[0]))]
            for j in range(10):
                logits, cache = v.decode_fn(
                    v.params, cache, jnp.array(served[-1:], jnp.int32),
                    jnp.array([s + j], jnp.int32))
                served.append(int(jnp.argmax(logits[0])))
            reqs.append((prompt, served))
        w = ref.make_weights(ref.dims(m), SEED, i, len(members))
        gaps, ctrl = ref.served_gaps(ref.dims(m), w, reqs, length=80,
                                     control=True)
        prog = max(float(g.max()) for g in gaps)
        low = max(float(g.max()) for g in ctrl)
        print(m["arch"], prog, low)
        assert low > 3 * prog and low > 0.05, (m["arch"], prog, low)
