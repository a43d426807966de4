"""A run of each cell with the timed path broken underneath must come
out not correct.  The runs skip the look for a chip and run on the CPU
at a small width; everything else is the cell's own run."""
import functools

import jax.numpy as jnp
import numpy as np
import pytest

import small
from chipbench.bench import run_cell

SEED = 2**31 + 5


def _run(workload, monkeypatch, seconds=2.0):
    small.small_registry(monkeypatch)
    bench, config, traffic = small.small_cell(workload)
    if config["driver"] == "pool":     # some 12 requests, or 4 bursts
        arr = traffic["arrivals"]
        arr["rate_rps"] = 6.0 if arr["burst"] == 1 else 2.0 * arr["burst"]
    result, checks = run_cell(workload, SEED, seconds, False, bench=bench,
                              config=config, traffic=traffic, chip=False)
    print(checks)
    return result


# ----------------------------------------------------------------------
# pool cells
# ----------------------------------------------------------------------
def _patch_decode(monkeypatch, fault):
    from repro.serving import pool
    real = pool.decode_step

    def decode_step(cfg, params, cache, tok, pos):
        logits, new_cache = real(cfg, params, cache, tok, pos)
        return fault(logits, cache, new_cache)
    monkeypatch.setattr(pool, "decode_step", decode_step)


@pytest.mark.parametrize("workload", ["pool2.steady", "pool2.burst"])
def test_pool_cell_is_correct(workload, monkeypatch):
    assert _run(workload, monkeypatch)["correct"]


def test_pool_token_altered(monkeypatch):
    def fault(logits, cache, new_cache):
        top = jnp.argmax(logits, axis=-1)
        wrong = (top + 1 + top % 97) % small.WIDTHS["vocab_size"]
        return logits.at[jnp.arange(logits.shape[0]), wrong].set(1e4), new_cache
    _patch_decode(monkeypatch, fault)
    assert not _run("pool2.steady", monkeypatch)["correct"]


def test_pool_state_unchanged(monkeypatch):
    _patch_decode(monkeypatch, lambda logits, cache, new_cache: (logits, cache))
    assert not _run("pool2.steady", monkeypatch)["correct"]


def test_pool_requests_left_out(monkeypatch):
    from repro.serving.executor import PoolExecutor, RequestResult
    real = PoolExecutor.execute

    def execute(self, tokens, t_sla, n_decode=2):
        if len(self.results) % 2:
            res = RequestResult(variant="", t_input_ms=0.0, t_infer_ms=0.0,
                                t_e2e_ms=0.0, t_sla_ms=t_sla, met_sla=False,
                                quality=0.0, admitted=False)
            self.results.append(res)
            return res
        return real(self, tokens, t_sla, n_decode)
    monkeypatch.setattr(PoolExecutor, "execute", execute)
    result = _run("pool2.steady", monkeypatch)
    assert not result["correct"] and result["failed"] > 0


# ----------------------------------------------------------------------
# router cell
# ----------------------------------------------------------------------
@pytest.fixture
def fresh_scan():
    from repro.kernels import policy_select
    policy_select._charged_jit.cache_clear()
    yield policy_select
    policy_select._charged_jit.cache_clear()


def test_router_cell_is_correct(monkeypatch, fresh_scan):
    assert _run("zoo11.route4096", monkeypatch, 1.0)["correct"]


def test_router_state_unchanged(monkeypatch, fresh_scan):
    real = fresh_scan._charged_step

    def step(rep_wait, xs, **kw):
        return rep_wait, real(rep_wait, xs, **kw)[1]
    monkeypatch.setattr(fresh_scan, "_charged_step", step)
    assert not _run("zoo11.route4096", monkeypatch, 1.0)["correct"]


def _patch_select(monkeypatch, policy_select, fault):
    real = policy_select.charged_select

    @functools.wraps(real)
    def charged_select(pool, t_u, t_l, state, **kw):
        return fault(real, pool, t_u, t_l, state, kw)
    monkeypatch.setattr(policy_select, "charged_select", charged_select)


def test_router_half_batch_left_out(monkeypatch, fresh_scan):
    def fault(real, pool, t_u, t_l, state, kw):
        h = len(t_u) // 2
        kw["adm_limit"] = kw["adm_limit"][:h]
        out = real(pool, t_u[:h], t_l[:h], state, **kw)
        return tuple(np.concatenate([x, np.zeros(len(t_u) - h, x.dtype)])
                     for x in out)
    _patch_select(monkeypatch, fresh_scan, fault)
    assert not _run("zoo11.route4096", monkeypatch, 1.0)["correct"]


def test_router_answer_altered(monkeypatch, fresh_scan):
    def fault(real, pool, t_u, t_l, state, kw):
        picks, admitted, has_base, rep, w = real(pool, t_u, t_l, state, **kw)
        picks = picks.copy()
        picks[::97] = (picks[::97] + 1) % pool.n
        return picks, admitted, has_base, rep, w
    _patch_select(monkeypatch, fresh_scan, fault)
    assert not _run("zoo11.route4096", monkeypatch, 1.0)["correct"]
