"""Per-kernel allclose vs the pure-jnp oracles: shape/dtype sweeps in
interpret mode (the kernel bodies execute on CPU through the JAX
interpreter)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KV,S,hd,window", [
    (1, 4, 4, 128, 64, 0),      # MHA causal
    (2, 4, 2, 256, 64, 0),      # GQA causal
    (2, 4, 1, 256, 32, 64),     # MQA sliding window
    (1, 8, 4, 512, 128, 128),   # GQA window, MXU-aligned head dim
])
def test_flash_attention(dtype, B, H, KV, S, hd, window):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, S, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, KV, S, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, KV, S, hd), jnp.float32).astype(dtype)
    out = ops.flash_attention(q, k, v, causal=True, window=window,
                              block_q=64, block_k=64)
    expect = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,KV,G,S,hd,window", [
    (2, 2, 2, 256, 64, 0),
    (1, 4, 1, 128, 128, 0),
    (3, 2, 4, 256, 32, 96),
])
def test_decode_attention(dtype, B, KV, G, S, hd, window):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, KV, G, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, KV, S, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, KV, S, hd), jnp.float32).astype(dtype)
    pos = jnp.asarray(np.random.default_rng(0).integers(1, S, B), jnp.int32)
    out = ops.decode_attention(q, k, v, pos, window=window, block_k=64)
    expect = ref.decode_attention_ref(q, k, v, pos, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,G,S,hd,N,chunk", [
    (1, 2, 1, 128, 16, 16, 32),
    (2, 4, 2, 256, 32, 64, 64),
    (1, 4, 4, 128, 64, 128, 128),
])
def test_ssd_scan(dtype, B, H, G, S, hd, N, chunk):
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    x = (jax.random.normal(ks[0], (B, H, S, hd), jnp.float32) * 0.5).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, H, S), jnp.float32))
    A = -jnp.exp(jax.random.normal(ks[2], (H,), jnp.float32) * 0.3)
    B_ = (jax.random.normal(ks[3], (B, G, S, N), jnp.float32) * 0.3).astype(dtype)
    C_ = (jax.random.normal(ks[4], (B, G, S, N), jnp.float32) * 0.3).astype(dtype)
    out = ops.ssd_scan(x, dt.astype(dtype), A, B_, C_, chunk=chunk)
    expect = ref.ssd_scan_ref(x, dt.astype(dtype), A, B_, C_)
    scale = np.maximum(np.abs(np.asarray(expect, np.float32)).max(), 1.0)
    np.testing.assert_allclose(np.asarray(out, np.float32) / scale,
                               np.asarray(expect, np.float32) / scale,
                               **tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,W,block", [
    (1, 128, 64, 32),
    (2, 256, 128, 64),
    (2, 512, 256, 256),
])
def test_rglru_scan(dtype, B, S, W, block):
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    a = (jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, W), jnp.float32)) * 0.98).astype(dtype)
    b = (jax.random.normal(ks[1], (B, S, W), jnp.float32) * 0.1).astype(dtype)
    out = ops.rglru_scan(a, b, block_s=block)
    expect = ref.rglru_scan_ref(a, b)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **tol(dtype))


# ----------------------------------------------------------------------
# device-resident stages 1–2: fused masks vs the numpy reference
# ----------------------------------------------------------------------

def _grid_pool_and_budgets(seed, n, B):
    """Random pool + budgets quantized to a 0.25 grid in [0, 512]: every
    value — and every sum/difference stages 1–2 form from them — is
    exactly representable in BOTH float32 and float64, so the device
    masks must equal the f64 numpy reference bit for bit (no
    precision-boundary flakes by construction)."""
    rng = np.random.default_rng(seed)
    q = lambda x: np.round(np.asarray(x) * 4.0) / 4.0
    mu = q(rng.uniform(1.0, 200.0, n))
    sigma = q(rng.uniform(0.0, 20.0, n))
    acc = rng.uniform(0.05, 1.0, n)          # not used by stages 1–2
    t_u = q(rng.uniform(-20.0, 400.0, B))
    t_l = t_u - q(rng.uniform(0.0, 50.0))
    return mu, sigma, acc, t_u, t_l


@pytest.mark.parametrize("seed,n,B", [
    (0, 11, 64),     # Table-2-sized pool
    (1, 1, 16),      # singleton pool
    (2, 12, 256),    # one full batch block
    (3, 7, 1000),    # ragged batch
])
def test_device_masks_match_numpy_reference(seed, n, B):
    """Property: the fused pipeline's stage 1–2 masks and base indices
    (computed in jitted jnp through ``masks_device``) equal the
    ``policy_vec.modipick_masks`` numpy reference over randomized pools
    and budgets — including fallback rows."""
    from repro.core.policy_vec import modipick_masks
    from repro.core.profiles import ProfileTable
    from repro.kernels import policy_select

    mu, sigma, acc, t_u, t_l = _grid_pool_and_budgets(seed, n, B)
    tab = ProfileTable(names=tuple(f"m{i}" for i in range(n)),
                       accuracy=acc, mu=mu, sigma=sigma,
                       queue_mu=np.zeros(n))
    base, has_base, eligible, _ = modipick_masks(tab, t_u, t_l)
    d_base, d_has, d_elig = policy_select.masks_device(
        tab.device_pool(), t_u, t_l)
    np.testing.assert_array_equal(has_base, d_has)
    np.testing.assert_array_equal(base[has_base], d_base[has_base])
    np.testing.assert_array_equal(eligible, d_elig)
    # the pure-jnp oracle in kernels.ref agrees with the traced stages
    rank = np.empty(n, np.float32)
    rank[tab.acc_order] = np.arange(n, dtype=np.float32)
    r_base, r_has, r_elig = ref.modipick_masks_ref(
        jnp.asarray(mu, jnp.float32), jnp.asarray(sigma, jnp.float32),
        jnp.asarray(rank), jnp.asarray(t_u, jnp.float32),
        jnp.asarray(t_l, jnp.float32))
    np.testing.assert_array_equal(np.asarray(r_has), has_base)
    np.testing.assert_array_equal(np.asarray(r_base)[has_base],
                                  base[has_base])
    np.testing.assert_array_equal(np.asarray(r_elig), eligible)


def test_select_fused_device_resident_picks():
    """``select_fused`` goes from raw pool operands to sampled indices
    in one jit: every pick must land inside the request's stage-2
    eligible set, fallback rows must route to the fastest model, and a
    degenerate single-model pool must pick it always."""
    from repro.core.policy_vec import modipick_masks
    from repro.core.profiles import ProfileTable
    from repro.kernels import policy_select

    mu, sigma, acc, t_u, t_l = _grid_pool_and_budgets(7, 11, 512)
    tab = ProfileTable(names=tuple(f"m{i}" for i in range(11)),
                       accuracy=acc, mu=mu, sigma=sigma,
                       queue_mu=np.zeros(11))
    _, has_base, eligible, _ = modipick_masks(tab, t_u, t_l)
    idx, d_has = policy_select.select_fused(tab.device_pool(), t_u, t_l,
                                            gamma=1.0, seed=5)
    np.testing.assert_array_equal(has_base, d_has)
    assert all(eligible[b, idx[b]] for b in np.flatnonzero(has_base))
    assert (idx[~has_base] == tab.fastest).all()
    # distribution sanity on a repeated budget row: empirical frequency
    # tracks the reference probability vector
    from repro.core.policy_vec import modipick_probs
    t1 = np.full(20000, 150.0)
    tl1 = t1 - 20.0
    _, _, e1, _ = modipick_masks(tab, t1, tl1)
    p_ref = modipick_probs(tab, t1, tl1, e1, 1.0)[0]
    picks, _ = policy_select.select_fused(tab.device_pool(), t1, tl1,
                                          gamma=1.0, seed=11)
    emp = np.bincount(picks, minlength=11) / len(picks)
    np.testing.assert_allclose(emp, p_ref, atol=0.015)


def test_fused_jit_cache_reused_across_calls():
    """The compiled selection is cached per (pool_size, gamma):
    repeated calls must hit the same callable, and distinct gammas must
    not collide."""
    from repro.kernels import policy_select
    a = policy_select._fused_jit(128, 1.0)
    assert policy_select._fused_jit(128, 1.0) is a
    assert policy_select._fused_jit(128, 4.0) is not a


@pytest.mark.parametrize("n,gamma", [(11, 1.0), (5, 4.0)])
@pytest.mark.parametrize("seed", [7, 8])
def test_device_paths_share_one_selection_body(seed, n, gamma):
    """The other callers of the one stage 1–3 body and draw agree pick
    for pick with ``select_fused`` on the same seed and budgets:
    ``select_classed`` over K identical class views (any class ids), and
    ``charged_select``'s ``lax.scan`` with a zero charge and admit-all
    (the ledger never moves, so every row sees the uncharged pool)."""
    from repro.core.zoo import TABLE2, make_store
    from repro.premodel.conditional import StackedClassPools
    from repro.router.charging import ChargedWaits
    from repro.kernels import policy_select

    tab = make_store(TABLE2[:n]).table()
    pool = tab.device_pool()
    rng = np.random.default_rng(seed)
    B = 300
    t_u = rng.uniform(0.0, 400.0, B)
    t_l = t_u - 20.0
    idx, has = policy_select.select_fused(pool, t_u, t_l, gamma=gamma,
                                          seed=seed)
    assert has.any() and (~has).any()

    cls = rng.integers(0, 3, B)
    c_idx, c_has = policy_select.select_classed(
        StackedClassPools([tab] * 3), cls, t_u, t_l, gamma=gamma, seed=seed)
    np.testing.assert_array_equal(c_has, has)
    np.testing.assert_array_equal(c_idx, idx)

    state = ChargedWaits(np.zeros(n), [[m] for m in range(n)], np.ones(n),
                         np.zeros(n), tab.names)
    picks, admitted, s_has, _, _ = policy_select.charged_select(
        pool, t_u, t_l, state, gamma=gamma, seed=seed)
    assert admitted.all()
    np.testing.assert_array_equal(s_has, has)
    np.testing.assert_array_equal(picks, idx)


def test_flash_vs_model_xla_path():
    """The model's chunked XLA attention and the Pallas kernel agree."""
    from repro.models.attention import attention_full
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    B, H, KV, S, hd = 2, 4, 2, 256, 64
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, hd), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    xla_out = attention_full(q, k, v, pos, pos, causal=True, q_chunk=64)
    pl_out = ops.flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=True, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(xla_out, np.float32),
                               np.asarray(pl_out.transpose(0, 2, 1, 3), np.float32),
                               rtol=2e-5, atol=2e-5)
