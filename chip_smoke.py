"""Chip smoke test: drive the served path once on a TPU and check what
comes out.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the fleet's cell-sharded selection

One chip runs, in order and in this one process:

1. device check — JAX's first device must be a TPU, else exit non-zero
   before anything is built;
2. pool — ``launch.serve.build_pool`` over qwen2-1.5b and phi4-mini-3.8b
   at their published widths in bf16, seeded random weights;
3. cache agreement — per member, prefill(S) plus one decode step against
   the last logits of prefill(S+1), at the served shape;
4. serve — 32 requests through ``launch.serve.serve`` (queue-aware
   ``PoolExecutor`` → ``Router`` → ModiPick) over the campus-WiFi uplink
   with a seeded 150–600 ms SLA mix;
5. selection — the fused selection at batch 4096 and 100k, its masks
   against the numpy reference and its picks inside the eligible sets,
   and the charged routing path (the Pallas charged kernel) once at
   4096.

``--four-chips`` runs only the fleet selection sharded over a 4-device
``cell`` mesh, against the single-device call.

Every phase raises on a failed check, so the script exits non-zero and
prints no result.  The last line of standard output is one JSON object
naming the device JAX reports.  Per-request timings printed here are a
smoke run's, not benchmark metrics.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.registry import get_config  # noqa: E402
from repro.core.netmodel import campus_wifi  # noqa: E402
from repro.core.policy import ModiPick  # noqa: E402
from repro.core.policy_vec import modipick_masks  # noqa: E402
from repro.core.profiles import ProfileTable  # noqa: E402
from repro.core.zoo import TABLE2, make_store  # noqa: E402
from repro.kernels import policy_select  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.router import Router, SlaAwareAdmission  # noqa: E402

SEED = 0
TOKENS_SHAPE = (4, 128)          # the served shape: batch 4, 128 tokens
N_DECODE = 2
N_REQUESTS = 32
SLA_RANGE_MS = (150.0, 600.0)
THRESHOLD_MS = 20.0              # ModiPick's T_threshold
SELECT_BATCHES = (4096, 100_000)
CHARGED_BATCH = 4096
FLEET_DEVICES, FLEET_CELLS, FLEET_BATCH = 4, 8, 4096

# Cache agreement compares decode logits with prefill(S+1)'s last logits
# as max|Δ| / max|logit| over the real vocabulary.  The float32 CPU test
# (tests/test_archs.py) holds 2e-3.  In bf16 every activation is rounded
# to 8 mantissa bits (2^-8 ≈ 3.9e-3 relative), and the two paths round
# at different places: prefill attends in float32 over the whole
# sequence, decode attends against the bf16 cache.  Those per-layer
# differences add up through ~30 residual blocks.  On the CPU, in bf16
# at full depth and reduced width (qwen2 x0.25, phi4 x0.125), the
# correct cache gives 9.5e-3 and 9.8e-3; a cache shifted by one slot
# gives 3.5e-2 to 5.9e-2, a wrong decode position 0.16 to 0.26, and an
# empty cache 1.3.  The bound sits between the first two.
CACHE_TOL = 2.5e-2


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def require_device(n_chips: int):
    """Exit non-zero unless JAX's devices are ``n_chips`` or more TPUs."""
    devs = jax.devices()
    d = devs[0]
    say("device", f"platform={d.platform} kind={d.device_kind} "
                  f"count={len(devs)}")
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; JAX found {d.platform}")
    if len(devs) < n_chips:
        raise SystemExit(f"chip_smoke needs {n_chips} TPU chips; JAX found "
                         f"{len(devs)}")
    return d


def _peak_bytes():
    stats = jax.devices()[0].memory_stats()
    return stats["peak_bytes_in_use"] if stats else "not reported"


def _tokens(shape, seed):
    return np.random.default_rng(seed).integers(0, 500, shape,
                                                dtype=np.int32)


# ----------------------------------------------------------------------
# pool
# ----------------------------------------------------------------------
def phase_pool(cfgs, tokens, *, seed: int = SEED, n_decode: int = N_DECODE):
    """Build the pool through ``launch.serve.build_pool`` and compile each
    member's prefill and decode step at the served shape."""
    t0 = time.perf_counter()
    variants = serve.build_pool(cfgs, seed=seed,
                                cache_len=tokens.shape[1] + 16)
    jax.block_until_ready([v.params for v in variants])
    say("pool", f"built {len(variants)} members in "
                f"{time.perf_counter() - t0:.3f}s; "
                f"peak_bytes_in_use={_peak_bytes()}")
    for v in variants:
        leaves = jax.tree.leaves(v.params)
        dtypes = sorted({str(x.dtype) for x in leaves})
        if dtypes != [v.cfg.dtype]:
            raise RuntimeError(f"{v.name}: parameter dtypes {dtypes}, "
                               f"config says {v.cfg.dtype}")
        c = v.cfg
        t0 = time.perf_counter()
        v.run(tokens, n_decode)      # first call compiles prefill + decode
        compile_s = time.perf_counter() - t0
        say("pool", f"{v.name}: layers={c.n_layers} d_model={c.d_model} "
                    f"d_ff={c.d_ff} heads={c.n_heads}/{c.n_kv_heads} "
                    f"vocab={c.vocab_size} dtype={dtypes[0]} "
                    f"param_bytes={sum(x.nbytes for x in leaves)} "
                    f"quality={v.quality} warmup_compile_s={compile_s:.3f} "
                    f"peak_bytes_in_use={_peak_bytes()}")
    return variants


# ----------------------------------------------------------------------
# cache agreement
# ----------------------------------------------------------------------
def phase_cache_agreement(variants, tokens_ext, *, tol: float = CACHE_TOL):
    """Per member: prefill(S) + decode(token S) against prefill(S+1),
    through the member's own compiled prefill/decode functions."""
    B, S1 = tokens_ext.shape
    S = S1 - 1
    worst = {}
    for v in variants:
        cache, _ = v.prefill_fn(v.params, jnp.asarray(tokens_ext[:, :S]))
        dec, _ = v.decode_fn(v.params, cache, jnp.asarray(tokens_ext[:, S]),
                             jnp.full((B,), S, jnp.int32))
        _, full = v.prefill_fn(v.params, jnp.asarray(tokens_ext))
        V = v.cfg.vocab_size     # padded vocab lanes carry -1e30
        a = np.asarray(dec, np.float32)[:, :V]
        b = np.asarray(full, np.float32)[:, :V]
        if not (np.isfinite(a).all() and np.isfinite(b).all()):
            raise RuntimeError(f"{v.name}: non-finite logits")
        rel = float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
        argmax_agree = float(np.mean(a.argmax(-1) == b.argmax(-1)))
        say("cache", f"{v.name}: prefill({S})+decode vs prefill({S1}) "
                     f"logits {a.shape} max|d|/max|logit|={rel:.3e} "
                     f"(tol {tol:g}) argmax_agree={argmax_agree:.2f}")
        if not rel < tol:
            raise RuntimeError(f"{v.name}: cache disagreement {rel:.3e} "
                               f">= {tol:g}")
        worst[v.name] = rel
    return worst


# ----------------------------------------------------------------------
# serve
# ----------------------------------------------------------------------
def phase_serve(variants, tokens, *, n_requests: int = N_REQUESTS,
                n_decode: int = N_DECODE, seed: int = SEED):
    """Route and run ``n_requests`` through the queue-aware executor."""
    slas = serve.sla_mix(n_requests, *SLA_RANGE_MS, seed)
    ex = serve.serve(variants, ModiPick(THRESHOLD_MS), slas, tokens,
                     network=campus_wifi(), n_decode=n_decode, seed=seed)
    names = {v.name for v in variants}
    if len(ex.results) != n_requests:
        raise RuntimeError(f"{len(ex.results)} results for {n_requests} "
                           "requests")
    used = {}
    for i, r in enumerate(ex.results):
        if r.admitted:
            if r.variant not in names or not np.isfinite(r.t_infer_ms) \
                    or r.t_infer_ms <= 0:
                raise RuntimeError(f"request {i}: bad result {r}")
            used[r.variant] = used.get(r.variant, 0) + 1
            what = f"-> {r.variant}"
        else:
            if not r.reject_reason:
                raise RuntimeError(f"request {i} shed with no reason")
            what = f"shed ({r.reject_reason})"
        say("serve", f"req {i:2d} sla={r.t_sla_ms:.1f}ms "
                     f"uplink={r.t_input_ms:.1f}ms {what} "
                     f"w_queue={r.w_queue_ms:.3f}ms "
                     f"infer={r.t_infer_ms:.3f}ms e2e={r.t_e2e_ms:.3f}ms "
                     f"met={r.met_sla}")
    say("serve", f"summary {json.dumps(ex.summary())}")
    say("serve", f"requests per member {json.dumps(used)}; "
                 f"shed {n_requests - sum(used.values())}")
    return ex


# ----------------------------------------------------------------------
# selection on the device
# ----------------------------------------------------------------------
def _grid(x):
    """Round to a 0.25 grid: every value, and every sum and difference
    stages 1–2 form from them, is exact in float32 and float64, so the
    device masks must equal the float64 numpy reference bit for bit."""
    return np.round(np.asarray(x, np.float64) * 4.0) / 4.0


def _grid_table() -> ProfileTable:
    n = len(TABLE2)
    return ProfileTable(
        names=tuple(e.name for e in TABLE2),
        accuracy=np.array([e.top1 / 100.0 for e in TABLE2]),
        mu=_grid([e.mu_ms for e in TABLE2]),
        sigma=_grid([max(e.sigma_ms, 0.25) for e in TABLE2]),
        queue_mu=np.zeros(n))


def _budget_columns(B: int, seed: int):
    """Per-request SLA and one-way uplink columns (campus WiFi)."""
    rng = np.random.default_rng(seed)
    t_sla = _grid(rng.uniform(*SLA_RANGE_MS, B))
    t_input = _grid(campus_wifi().sample(rng, B))
    return t_sla, t_input


def phase_selection(batches=SELECT_BATCHES, charged_batch=CHARGED_BATCH, *,
                    seed: int = SEED):
    """The fused selection, its masks against the numpy reference and
    its picks inside the eligible sets, and the charged routing path."""
    on_tpu = jax.default_backend() == "tpu"
    tab = _grid_table()
    pool = tab.device_pool()
    say("select", f"DevicePool n={pool.n} lanes={pool.npad}")
    for B in batches:
        t_sla, t_input = _budget_columns(B, seed + B)
        t_u = t_sla - 2.0 * t_input
        t_l = t_u - THRESHOLD_MS
        base, has_base, eligible, _ = modipick_masks(tab, t_u, t_l)
        d_base, d_has, d_elig = policy_select.masks_device(pool, t_u, t_l)
        if not (np.array_equal(has_base, d_has)
                and np.array_equal(base[has_base], d_base[has_base])
                and np.array_equal(eligible, d_elig)):
            raise RuntimeError(f"batch {B}: device masks != numpy masks")

        t0 = time.perf_counter()
        idx, fused_has = policy_select.select_fused(pool, t_u, t_l,
                                                    seed=seed)
        first_s = time.perf_counter() - t0
        rows = np.arange(B)
        if not (np.array_equal(fused_has, has_base)
                and eligible[rows[has_base], idx[has_base]].all()
                and (idx[~has_base] == tab.fastest).all()):
            raise RuntimeError(f"batch {B}: fused picks off the "
                               "eligible sets")

        say("select", f"batch {B} (padded "
                      f"{policy_select._bucket(B, 256)}): masks equal numpy, "
                      f"{int(has_base.sum())} with a base, picks eligible; "
                      f"first call {first_s:.3f}s")

    store = make_store(TABLE2)
    router = Router(store, ModiPick(THRESHOLD_MS),
                    admission=SlaAwareAdmission(), queue_aware=True,
                    trace_detail=False, backend="jax")
    t_sla, t_input = _budget_columns(charged_batch, seed)
    calls = policy_select._charged_jit.cache_info()
    res = router.route_batch_arrays(
        t_sla, t_input, np.random.default_rng(seed),
        w_queue_map={n: 0.0 for n in store.profiles})
    after = policy_select._charged_jit.cache_info()
    if after.hits + after.misses == calls.hits + calls.misses:
        raise RuntimeError("the charged batch did not ride charged_select")
    kernel = router.stats()["n_charged_kernel_batches"] == 1
    if on_tpu and not kernel:
        raise RuntimeError("the charged batch did not run the Pallas kernel")
    n = len(store.profiles)
    picks = res.model_idx[res.admitted]
    n_shed = int((~res.admitted).sum())
    if not (((picks >= 0) & (picks < n)).all()
            and np.isfinite(res.w_queue_ms).all()):
        raise RuntimeError("charged picks out of range or waits not finite")
    say("select", f"charged routing at {charged_batch} (kernel={kernel}): "
                  f"{len(picks)} admitted, {n_shed} shed, "
                  f"{len(np.unique(picks))} distinct models")


# ----------------------------------------------------------------------
# four chips: the fleet's cell-sharded selection
# ----------------------------------------------------------------------
def phase_fleet_sharded(n_devices: int = FLEET_DEVICES,
                        n_cells: int = FLEET_CELLS,
                        batch: int = FLEET_BATCH, *, seed: int = SEED):
    """``fleet.device.select_fleet`` with the cell axis sharded over an
    ``n_devices`` mesh must pick bit-identically to the single-device
    ``select_fleet_stacked``, with its output spread over every device."""
    from repro.distributed.shardmap_ops import sharded_fleet_select
    from repro.fleet.device import select_fleet, stack_cell_tables
    from repro.launch.mesh import make_mesh

    # Heterogeneous cells: each serves its own slice of the zoo.
    tables = [make_store(TABLE2[c % 4: c % 4 + 3 + c % 5]).table()
              for c in range(n_cells)]
    stacked = stack_cell_tables(tables)
    cols = [_budget_columns(batch, seed + c) for c in range(n_cells)]
    t_u = np.stack([s - 2.0 * i for s, i in cols]).astype(np.float32)
    t_l = t_u - np.float32(THRESHOLD_MS)

    single = select_fleet(stacked, t_u, t_l, seed=seed)
    mesh = make_mesh((n_devices,), ("cell",))
    sharded = select_fleet(stacked, t_u, t_l, seed=seed, mesh=mesh)
    keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        jax.random.PRNGKey(seed), jnp.arange(stacked.C, dtype=jnp.uint32))
    out = sharded_fleet_select(stacked.mu, stacked.sigma, stacked.acc,
                               stacked.rank, jnp.asarray(t_u),
                               jnp.asarray(t_l), keys, mesh)
    devices = {s.device for s in out.addressable_shards}
    say("fleet", f"{n_cells} cells x batch {batch} on a {n_devices}-device "
                 f"cell mesh: output shards on {len(devices)} devices "
                 f"{sorted(str(d) for d in devices)}")
    if len(devices) != n_devices:
        raise RuntimeError(f"output on {len(devices)} devices, "
                           f"expected {n_devices}")
    for name, picks in (("select_fleet(mesh)", sharded),
                        ("sharded_fleet_select", np.asarray(out))):
        if not np.array_equal(picks, single):
            raise RuntimeError(f"{name} picks differ from the single-device "
                               f"call in {int((picks != single).sum())} "
                               "places")
    say("fleet", f"picks bit-identical to select_fleet_stacked "
                 f"({int((single >= 0).sum())} served, "
                 f"{int((single < 0).sum())} without a base)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the fleet selection sharded over four "
                         "chips, against the single-device call")
    args = ap.parse_args(argv)

    device = require_device(FLEET_DEVICES if args.four_chips else 1)
    say("compile-cache", f"directory {enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.four_chips:
        phase_fleet_sharded()
    else:
        cfgs = [get_config(a) for a in serve.DEFAULT_ARCHS]
        tokens = _tokens(TOKENS_SHAPE, SEED)
        variants = phase_pool(cfgs, tokens)
        phase_cache_agreement(
            variants, _tokens((TOKENS_SHAPE[0], TOKENS_SHAPE[1] + 1),
                              SEED + 1))
        phase_serve(variants, tokens)
        phase_selection()
    say("done", f"all phases passed in {time.perf_counter() - t0:.3f}s; "
                f"peak_bytes_in_use={_peak_bytes()}")
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
