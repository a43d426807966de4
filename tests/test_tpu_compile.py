"""Compile the served path for a described TPU v5e chip, with shapes only.

Nothing runs and no chip is needed: the TPU compiler installed with JAX
compiles for a topology it is told about, and refuses what the chip
would refuse (unaligned kernel blocks, too much fast memory, programs
that do not fit).  The topology is described inside a fixture, never at
import, so pytest-xdist workers all collect the same tests and only the
worker that runs this file loads the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.kernels import policy_select
from repro.launch.serve import DEFAULT_ARCHS
from repro.models import model as M
from repro.models.layers import abstract
from repro.serving import pool

HBM_BYTES = 16e9        # one v5e chip
TOKENS = (4, 128)       # the served shape
CACHE_LEN = TOKENS[1] + 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """The first described chip, with the persistent compilation cache
    off: a program compiled for an absent chip is written to the cache
    but cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(tree, sharding):
    return jax.tree.map(lambda s: _sds(s.shape, s.dtype, sharding), tree)


@pytest.mark.parametrize("batch", [4096, 102_400])
def test_fused_selection_compiles_with_pallas_kernel(one_chip, batch):
    """The fused stage 1–3 selection (plain XLA on every backend) at a
    full tick and at the largest bucketed batch."""
    lanes = policy_select.LANES
    fn = policy_select._fused_jit(lanes, 1.0)
    pool_arg = _sds((lanes,), jnp.float32, one_chip)
    req = _sds((batch,), jnp.float32, one_chip)
    compiled = fn.lower(pool_arg, pool_arg, pool_arg, pool_arg, req, req,
                        _sds((), jnp.uint32, one_chip)).compile()
    assert compiled.as_text()


def _lower_charged(fn, sharding, lanes, replicas, batch):
    """The charged program on the pool columns and one packed buffer."""
    vec = _sds((lanes,), jnp.float32, sharding)
    size = policy_select._charged_layout(lanes, replicas, batch)[-1][1]
    return fn.lower(vec, vec, vec, vec, _sds((size,), jnp.uint32, sharding),
                    n_replicas=replicas, bpad=batch)


def test_charged_select_compiles(one_chip):
    lanes, replicas, batch = policy_select.LANES, 11, 4096
    fn = policy_select._charged_jit(lanes, 1.0, 0.0, False, 0)
    compiled = _lower_charged(fn, one_chip, lanes, replicas,
                              batch).compile()
    assert compiled.as_text()


@pytest.mark.parametrize("batch", [4096, 1200])
def test_charged_select_kernel_compiles(one_chip, batch):
    """The zoo's charged pass (128 lanes × 176 replicas) as the Pallas
    kernel: one ``tpu_custom_call``, at a full tick and at one whose rows
    are not a multiple of the kernel's row block."""
    lanes, replicas = policy_select.LANES, 176
    fn = policy_select._charged_jit(lanes, 1.0, 0.0, True, 1, True)
    compiled = _lower_charged(fn, one_chip, lanes, replicas,
                              batch).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


def test_pool_members_compile_and_fit_one_chip(one_chip):
    """qwen2-1.5b and phi4-mini-3.8b at published widths in bf16: the
    served prefill and decode step compile, and the two members'
    parameters fit one chip's HBM together."""
    arg_bytes = 0
    for arch in DEFAULT_ARCHS:
        cfg = get_config(arch)
        dtype = jnp.dtype(cfg.dtype)
        params = _on(abstract(M.param_template(cfg), dtype), one_chip)
        tokens = _sds(TOKENS, jnp.int32, one_chip)
        prefill = pool.prefill_step.lower(
            cfg, params, tokens, cache_len=CACHE_LEN).compile()
        cache_shapes, logits_shape = jax.eval_shape(
            lambda p, t: M.prefill(cfg, p, {"tokens": t}, CACHE_LEN),
            params, tokens)
        assert logits_shape.shape == (TOKENS[0], cfg.padded_vocab)
        vec = _sds((TOKENS[0],), jnp.int32, one_chip)
        decode = pool.decode_step.lower(
            cfg, params, _on(cache_shapes, one_chip), vec, vec).compile()
        assert decode.as_text()
        arg_bytes += prefill.memory_analysis().argument_size_in_bytes
    assert arg_bytes < HBM_BYTES


def test_moonlight_share_pool_compiles_and_fits_one_chip(one_chip):
    """qwen2-1.5b beside one chip's share of Moonlight-16B-A3B (8 of its
    64 experts per layer, latent attention, a leading dense layer) at
    published widths in bf16: a 2048-token prefill into a 2064-position
    cache and the decode step compile, and the two members' parameters
    fit one chip's HBM together."""
    tokens, cache_len = (1, 2048), 2064
    arg_bytes = 0
    for cfg in (get_config("qwen2-1.5b"),
                get_config("moonlight-16b-a3b").expert_share(8)):
        params = _on(abstract(M.param_template(cfg), jnp.dtype(cfg.dtype)),
                     one_chip)
        tok = _sds(tokens, jnp.int32, one_chip)
        prefill = pool.prefill_step.lower(cfg, params, tok,
                                          cache_len=cache_len).compile()
        cache_shapes, _ = jax.eval_shape(
            lambda p, t: M.prefill(cfg, p, {"tokens": t}, cache_len),
            params, tok)
        vec = _sds((1,), jnp.int32, one_chip)
        decode = pool.decode_step.lower(
            cfg, params, _on(cache_shapes, one_chip), vec, vec).compile()
        assert decode.as_text()
        arg_bytes += prefill.memory_analysis().argument_size_in_bytes
    assert arg_bytes < HBM_BYTES
