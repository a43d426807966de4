"""Distribution-layer unit tests: logical-axis rules, divisibility
fallbacks, HLO collective parsing, gradient compression."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.distributed import compression as comp
from repro.distributed import hlo as hlo_mod
from repro.distributed.sharding import logical_to_spec
from repro.launch.mesh import make_mesh


class FakeMesh:
    def __init__(self, shape):
        self.shape = dict(shape)


def test_divisible_dims_shard():
    mesh = FakeMesh({"data": 16, "model": 16})
    spec = logical_to_spec(("embed_fsdp", "ff"),
                           {"embed_fsdp": ("data",), "ff": "model"},
                           shape=(2560, 7680), mesh=mesh)
    assert spec == P(("data",), "model")


def test_indivisible_dim_falls_back_to_replication():
    mesh = FakeMesh({"data": 16, "model": 16})
    spec = logical_to_spec(("batch", "seq", "heads", None),
                           {"batch": ("data",), "heads": "model", "seq": None},
                           shape=(32, 128, 10, 256), mesh=mesh)  # 10 heads!
    assert spec == P(("data",), None, None, None)


def test_duplicate_mesh_axes_dropped():
    mesh = FakeMesh({"data": 4, "model": 4})
    spec = logical_to_spec(("batch", "cache_seq"),
                           {"batch": ("data",), "cache_seq": "data"},
                           shape=(16, 64), mesh=mesh)
    assert spec == P(("data",), None)  # 'data' already used by batch


@given(st.integers(1, 64), st.integers(1, 64), st.integers(1, 32))
@settings(max_examples=100, deadline=None)
def test_fallback_never_breaks_divisibility(dim0, dim1, axis):
    mesh = FakeMesh({"x": axis})
    spec = logical_to_spec(("a", "b"), {"a": "x", "b": "x"},
                           shape=(dim0, dim1), mesh=mesh)
    for d, s in zip((dim0, dim1), spec):
        if s is not None:
            assert d % axis == 0


# ----------------------------------------------------------------------
HLO_SAMPLE = """
  %all-gather.1 = f32[384,96]{1,0} all-gather(%x), channel_id=1, replica_groups=[4,2]<=[2,4]T(1,0), dimensions={0}
  %all-reduce.7 = bf16[1024]{0} all-reduce(%y), channel_id=2, replica_groups=[2,4]<=[8], to_apply=%add
  %all-reduce-done.1 = bf16[8]{0} all-reduce-done(%all-reduce-start.1)
  %rs = f32[128,8]{1,0} reduce-scatter(%z), channel_id=3, replica_groups=[1,8]<=[8], dimensions={1}, to_apply=%add
  %cp = u8[64]{0} collective-permute(%w), channel_id=4, source_target_pairs={{0,1}}
"""


def test_collective_parser_counts_and_bytes():
    stats = hlo_mod.collective_bytes(HLO_SAMPLE)
    assert stats.by_kind_count == {"all-gather": 1, "all-reduce": 1,
                                   "reduce-scatter": 1,
                                   "collective-permute": 1}
    ag = 384 * 96 * 4 * (1 / 2)          # group size 2 → (n-1)/n = 1/2
    ar = 1024 * 2 * 2.0 * (3 / 4)        # group size 4
    rs = 128 * 8 * 4 * 7                  # result × (n-1), group 8
    cp = 64
    assert stats.by_kind["all-gather"] == pytest.approx(ag)
    assert stats.by_kind["all-reduce"] == pytest.approx(ar)
    assert stats.by_kind["reduce-scatter"] == pytest.approx(rs)
    assert stats.by_kind["collective-permute"] == pytest.approx(cp)


def test_roofline_terms():
    r = hlo_mod.Roofline(n_chips=256, hlo_flops=1e18, hlo_bytes=1e15,
                         coll_bytes_per_chip=1e9, model_flops=6e17)
    assert r.compute_s == pytest.approx(1e18 / (256 * hlo_mod.PEAK_FLOPS_BF16))
    assert r.memory_s == pytest.approx(1e15 / (256 * hlo_mod.HBM_BW))
    assert r.collective_s == pytest.approx(1e9 / hlo_mod.ICI_BW)
    assert r.dominant == "compute"
    assert 0 < r.mfu <= 1.0


# ----------------------------------------------------------------------
def test_quantize_roundtrip_error_bound():
    x = np.random.default_rng(0).normal(size=(256,)).astype(np.float32)
    q, scale = comp.quantize_int8(jnp.asarray(x))
    back = comp.dequantize_int8(q, scale)
    assert float(jnp.max(jnp.abs(back - x))) <= float(scale) * 0.5 + 1e-6


def test_error_feedback_reduces_bias():
    """With EF, the accumulated quantization error stays bounded and the
    long-run mean of the compressed signal matches the true mean."""
    rng = np.random.default_rng(1)
    err = jnp.zeros(64)
    total_true = np.zeros(64)
    total_sent = np.zeros(64)
    for _ in range(200):
        g = jnp.asarray(rng.normal(size=64).astype(np.float32)) * 1e-3
        q, scale, err = comp.ef_compress(g, err)
        total_true += np.asarray(g)
        total_sent += np.asarray(comp.dequantize_int8(q, scale))
    # EF guarantees sent ≈ true up to the residual error buffer
    np.testing.assert_allclose(total_sent + np.asarray(err), total_true,
                               rtol=1e-4, atol=1e-5)


def test_compressed_psum_under_shard_map():
    if jax.device_count() < 2:
        pytest.skip("needs >1 device")
    mesh = make_mesh((jax.device_count(),), ("d",))
    x = jnp.arange(jax.device_count() * 4, dtype=jnp.float32).reshape(
        jax.device_count(), 4)
    f = jax.shard_map(lambda v: comp.compressed_psum(v[0], "d")[None],
                      mesh=mesh, in_specs=P("d", None),
                      out_specs=P("d", None))
    out = f(x)
    expect = x.mean(axis=0)
    np.testing.assert_allclose(np.asarray(out)[0], np.asarray(expect),
                               rtol=0.02, atol=0.02)
