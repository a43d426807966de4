"""Driver of the router alone: ``Router.route_batch_arrays`` on whole
ticks, each routed against a fresh ``ChargedWaits`` ledger, closed loop.

The router is built as ``chip_smoke.py`` builds it over the paper's
Table 2 zoo: ModiPick, SLA-aware admission, queue-aware, the jax
backend, no per-request traces.  At 4096 requests a tick rides the
device ``charged_select`` ``lax.scan``.  The traffic pre-draws a few
distinct ticks (SLA and uplink columns, and each replica's initial
wait: the same values for every seed, in an order drawn from the seed)
and the window cycles through them for its whole length.

For the check, a sample of the window's ticks, drawn from the seed, is
compared with the plain sequential rule (``configs/charged_router_ref.py``)
request by request.
"""
from __future__ import annotations

import os
import time

import numpy as np

from chipbench import costs, traffic as traffic_gen
from chipbench.bench import HERE, load_module


class Run:
    span_prefixes = ("router.",)
    # A traced run's window: some 8 ticks.  Each tick's scan puts about
    # 4096 steps of ops in the trace, which takes seconds to read back.
    trace_seconds = 1.0

    def __init__(self, config, traffic, seed, seconds, spans):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.seconds, self.spans = seconds, spans
        arr = traffic["arrivals"]
        if arr["process"] != "closed":
            raise ValueError("the router driver runs closed-loop ticks")
        self.batch = arr["batch"]
        self.n_models = len(config["models"])
        self.n_replicas = self.n_models * config["replicas_per_model"]
        self.ticks = []
        fixed = traffic_gen.SET_STREAM
        for k in range(arr["distinct_ticks"]):
            cols = traffic_gen.shuffled(traffic_gen.columns(
                traffic["fields"], self.batch, [fixed, 5, k]), [seed, 5, k])
            rep = traffic_gen.shuffled(traffic_gen.columns(
                traffic["replica_fields"], self.n_replicas, [fixed, 6, k]),
                [seed, 6, k])
            self.ticks.append((cols["sla_ms"], cols["uplink_ms"],
                               rep["initial_wait_ms"]))
        self.rng = np.random.default_rng([seed, 7])

    def setup(self):
        from repro.core.policy import ModiPick
        from repro.core.zoo import TABLE2, make_store
        from repro.kernels import policy_select
        from repro.router import Router, SlaAwareAdmission

        table = [{"name": e.name, "top1": e.top1, "mu_ms": e.mu_ms,
                  "sigma_ms": e.sigma_ms} for e in TABLE2]
        if table != self.config["models"]:
            raise RuntimeError("core.zoo.TABLE2 is not the zoo the "
                               "configuration states")
        pol, adm = self.config["policy"], self.config["admission"]
        self.router = Router(
            make_store(TABLE2), ModiPick(pol["t_threshold_ms"], pol["gamma"]),
            admission=SlaAwareAdmission(
                slack_ms=adm["slack_ms"],
                include_service_time=adm["include_service_time"]),
            queue_aware=self.config["queue_aware"], trace_detail=False,
            backend="jax")
        rpm = self.config["replicas_per_model"]
        self.cand = [list(range(m * rpm, (m + 1) * rpm))
                     for m in range(self.n_models)]
        before = policy_select._charged_jit.cache_info()
        warm = np.random.default_rng([self.seed, 8])
        for sla, up, rep in self.ticks[:2]:
            self.router.route_batch_arrays(sla, up, warm,
                                           charged=self.state(rep))
        after = policy_select._charged_jit.cache_info()
        if after.hits + after.misses == before.hits + before.misses:
            raise RuntimeError("the tick did not ride charged_select")
        select = self._select = policy_select.charged_select
        spans = self.spans

        def charged_select(*a, **k):
            with spans("router.charged_select", keep=False):
                return select(*a, **k)
        policy_select.charged_select = charged_select

    def state(self, rep_wait):
        from repro.router.charging import ChargedWaits
        tab = self.router.store.table()
        return ChargedWaits(rep_wait, self.cand, np.full(
            self.n_replicas, self.config["replica_speed"]), tab.mu, tab.names)

    def window(self):
        spans, router, ticks = self.spans, self.router, self.ticks
        self.log = []
        t0 = time.perf_counter()
        end = t0 + self.seconds
        while True:
            k = len(self.log) % len(ticks)
            sla, up, rep = ticks[k]
            rng_state = self.rng.bit_generator.state
            with spans("router.tick", keep=False):
                res = router.route_batch_arrays(sla, up, self.rng,
                                                charged=self.state(rep))
            self.log.append((k, rng_state, res))
            if time.perf_counter() >= end:
                break
        self.window_s = time.perf_counter() - t0
        self.rows_routed = self.attempted = len(self.log) * self.batch
        self.failed = 0

    def notes(self) -> dict:
        """Counts behind the metrics, for the run's standard error."""
        admitted = sum(int(res.admitted.sum()) for _, _, res in self.log)
        return {"ticks": len(self.log), "window_s": self.window_s,
                "admitted": admitted, "shed": self.rows_routed - admitted,
                "rows_too_close_to_call": self.close_rows}

    def scan_cost(self):
        return costs.charged_scan(self.batch, self.n_models,
                                  self.config["replicas_per_model"],
                                  self.n_replicas)

    def release(self):
        from repro.kernels import policy_select
        policy_select.charged_select = self._select

    def check(self):
        """Rows of the sampled ticks where the router's decision differs
        from the reference rule's."""
        ref = load_module(os.path.join(HERE, "configs",
                                       self.config["reference"]))
        p = ref.pool(self.config)
        n = min(self.config["check"]["ticks"], len(self.log))
        picks = np.random.default_rng([self.seed, 9]).choice(
            len(self.log), n, replace=False)
        bad = close = 0
        for j in sorted(picks):
            k, rng_state, res = self.log[j]
            sla, up, rep = self.ticks[k]
            g = np.random.Generator(np.random.PCG64())
            g.bit_generator.state = rng_state
            r01 = ref.draws(int(g.integers(np.iinfo(np.int64).max)),
                            self.batch)
            b, c = ref.mismatches(p, rep, sla - 2.0 * up, r01,
                                  (res.admitted, res.model_idx, res.fallback,
                                   res.replica_idx, res.w_queue_ms))
            bad, close = bad + b, close + c
        self.close_rows = close
        return [("mismatched_decisions", bad,
                 self.config["check"]["limits"]["mismatched_decisions"])]
