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
    lanes = policy_select.LANES
    fn = policy_select._fused_jit(lanes, 1.0, 256, True)
    pool_arg = _sds((lanes,), jnp.float32, one_chip)
    req = _sds((batch,), jnp.float32, one_chip)
    compiled = fn.lower(pool_arg, pool_arg, pool_arg, pool_arg, req, req,
                        _sds((), jnp.uint32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_charged_select_compiles(one_chip):
    lanes, replicas, batch = policy_select.LANES, 11, 4096
    fn = policy_select._charged_jit(lanes, 1.0, 0.0, False, 0)
    f32 = jnp.float32
    vec = _sds((lanes,), f32, one_chip)
    rep = _sds((replicas,), f32, one_chip)
    req = _sds((batch,), f32, one_chip)
    compiled = fn.lower(vec, vec, vec, vec, vec,
                        _sds((lanes, replicas), jnp.bool_, one_chip),
                        rep, rep, req, req, req, req).compile()
    assert compiled.as_text()


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
