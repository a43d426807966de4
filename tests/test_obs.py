"""Program spans and counters: the spans a profiler session records
around a router tick and a served request, the counters beside them,
and the executor repairs that came with them (prefill-only runs, the
queue wait before ``execute``)."""
import glob
import os
import re
import subprocess
import sys
import time
from dataclasses import dataclass

import numpy as np
import pytest

from repro.core.netmodel import NetworkModel
from repro.core.policy import ModiPick
from repro.core.zoo import TABLE2, make_store
from repro.obs import ROUTER_SPANS, SERVING_SPANS
from repro.router import ChargedWaits, Router, SlaAwareAdmission
from repro.serving.executor import PoolExecutor

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")


def host_spans(log_dir, names):
    """``{name: [(start_ns, end_ns), ...]}`` of the program spans in the
    one trace under ``log_dir``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1
    out = {}
    for plane in ProfileData.from_file(files[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in names:
                    s = int(e.start_ns)
                    out.setdefault(e.name, []).append(
                        (s, s + int(e.duration_ns)))
    return out


def traced(tmp_path, fn):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        out = fn()
    return out


def inside(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


# ----------------------------------------------------------------------
# the router's charged tick
# ----------------------------------------------------------------------
def zoo_tick(backend, B=256):
    store = make_store(TABLE2)
    router = Router(store, ModiPick(20.0), admission=SlaAwareAdmission(),
                    queue_aware=True, trace_detail=False, backend=backend)
    tab = store.table()
    n = len(tab.names)
    state = ChargedWaits(
        rep_wait=np.random.default_rng(0).uniform(0, 100, 2 * n),
        cand=[[2 * m, 2 * m + 1] for m in range(n)], speed=[1.0] * (2 * n),
        mu=tab.mu, names=tab.names)
    rng = np.random.default_rng(1)
    sla, up = rng.uniform(150, 600, B), rng.uniform(10, 100, B)
    return router, lambda: router.route_batch_arrays(
        sla, up, np.random.default_rng(2), charged=state)


@pytest.mark.parametrize("backend, phases", [
    ("jax", ("router.select.pack", "router.select.readback",
             "router.apply")),
    ("numpy", ("router.charged_loop",)),
])
def test_router_tick_spans_nest(tmp_path, backend, phases):
    router, tick = zoo_tick(backend)
    tick()                                  # compile outside the trace
    router.window_stats()
    res = traced(tmp_path, tick)
    spans = host_spans(tmp_path, ROUTER_SPANS)
    assert set(spans) == {"router.route_batch", *phases}
    assert all(len(v) == 1 for v in spans.values())
    outer = spans["router.route_batch"][0]
    for name in phases:
        assert inside(spans[name][0], outer), name
    # the device phases run in order, one after the other
    ends = [spans[p][0] for p in phases]
    assert all(a[1] <= b[0] for a, b in zip(ends, ends[1:]))
    scan = 1 if backend == "jax" else 0
    win = router.window_stats()
    assert win["n_scan_batches"] == scan and win["n_batches"] == 1
    assert router.stats()["n_scan_batches"] == 2 * scan
    assert res.admitted.any() and not res.admitted.all()


# ----------------------------------------------------------------------
# the live pool
# ----------------------------------------------------------------------
def test_execute_spans_nest_and_count_tokens(tmp_path):
    from repro.configs.registry import get_config
    from repro.serving.pool import scaled_family
    pool = scaled_family(get_config("qwen2-1.5b"), widths=(0.25, 0.5),
                         cache_len=32)
    tokens = np.zeros((1, 8), np.int32)
    ex = PoolExecutor(pool, NetworkModel(15.0, 7.0), ModiPick(20.0),
                      seed=0, warmup_requests=1)
    ex.warm_up(tokens, 3)
    res = traced(tmp_path, lambda: ex.execute(tokens, 5000.0, 3))
    spans = host_spans(tmp_path, SERVING_SPANS)
    # a dense pool reads no expert counters
    assert set(spans) == set(SERVING_SPANS) - {"pool.run.counters"}
    assert all(len(v) == 1 for v in spans.values())
    (exec_,), (run,) = spans["pool.exec"], spans["pool.run"]
    for name in ("pool.exec.route", "pool.run", "pool.exec.observe"):
        assert inside(spans[name][0], exec_), name
    for name in ("pool.run.upload", "pool.run.sync"):
        assert inside(spans[name][0], run), name
    assert spans["pool.exec.route"][0][1] <= run[0] <= run[1] \
        <= spans["pool.exec.observe"][0][0]
    assert res.admitted and res.tokens_served == 4
    s = ex.summary()
    assert s["tokens_served"] == 4
    assert s["tokens_served_by_member"] == {res.variant: 4}


def test_prefill_only_run_syncs_on_prefill():
    from repro.configs.registry import get_config
    from repro.serving.pool import scaled_family
    (v,) = scaled_family(get_config("qwen2-1.5b"), widths=(0.25,),
                         cache_len=32)
    calls = []
    decode = v.decode_fn
    v.decode_fn = lambda *a: calls.append(1) or decode(*a)
    assert v.run(np.zeros((1, 8), np.int32), 0) > 0.0
    assert calls == []
    v.run(np.zeros((1, 8), np.int32), 2)
    assert len(calls) == 2


@dataclass
class FixedVariant:
    name: str
    quality: float
    ms: float

    def run(self, tokens, n_decode=2) -> float:
        return self.ms


def fixed_executor(**kw):
    pool = [FixedVariant("small", 0.5, 10.0), FixedVariant("large", 0.9, 40.0)]
    ex = PoolExecutor(pool, NetworkModel(15.0, 0.0), ModiPick(5.0), seed=0,
                      **kw)
    ex.warm_up(np.zeros((1, 4), np.int32))
    return ex


def test_tokens_served_counts_served_requests_only():
    ex = fixed_executor(admission=SlaAwareAdmission())
    served = ex.execute(np.zeros((1, 4), np.int32), 500.0, n_decode=5)
    shed = ex.execute(np.zeros((1, 4), np.int32), 1.0, n_decode=5)
    assert served.admitted and served.tokens_served == 6
    assert not shed.admitted and shed.tokens_served == 0
    s = ex.summary()
    assert s["tokens_served"] == 6 and s["shed"] == 1
    assert s["tokens_served_by_member"] == {served.variant: 6}


def test_queue_wait_before_execute_counts_toward_the_sla():
    ex = fixed_executor()
    tokens = np.zeros((1, 4), np.int32)
    plain = ex.execute(tokens, 200.0)
    assert plain.t_queue_ms == 0.0
    assert plain.t_e2e_ms == 2.0 * plain.t_input_ms + plain.t_infer_ms
    assert plain.met_sla
    late = ex.execute(tokens, 200.0, arrival_s=time.perf_counter() - 0.3)
    assert 300.0 <= late.t_queue_ms < 400.0
    assert late.t_e2e_ms == pytest.approx(
        2.0 * late.t_input_ms + late.t_queue_ms + late.t_infer_ms)
    assert not late.met_sla
    assert ex.summary()["p95_queue_ms"] == pytest.approx(
        np.percentile([0.0, late.t_queue_ms], 95))


# ----------------------------------------------------------------------
# the span helper
# ----------------------------------------------------------------------
def test_numpy_modules_import_no_jax():
    code = ("import sys, repro.router, repro.sim\n"
            "from repro.obs import span\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n"
            "assert span('pool.exec') is span('router.apply')\n"
            "with span('pool.exec'):\n"
            "    pass\n"
            "assert 'jax' not in sys.modules, 'jax imported by span'\n")
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr


def test_every_span_call_uses_a_listed_name():
    listed = set(SERVING_SPANS) | set(ROUTER_SPANS)
    assert len(listed) == len(SERVING_SPANS) + len(ROUTER_SPANS)
    used = set()
    for path in glob.glob(os.path.join(SRC, "repro", "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            text = f.read()
        for name in re.findall(r"\bspan\(([^)]*)\)", text):
            if path.endswith("obs.py"):
                continue
            assert re.fullmatch(r'"[\w.]+"', name), (path, name)
            used.add(name.strip('"'))
    assert used == listed
