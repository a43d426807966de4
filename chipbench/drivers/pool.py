"""Driver of the live pool: the members ``launch.serve.build_pool``
builds, behind a queue-aware ``PoolExecutor`` built as
``launch.serve.serve`` builds it, fed open-loop requests at their due
times.

Each request goes to ``PoolExecutor.execute`` when it is due (its
arrival at the server) or, where the executor is still busy, as soon
as it is free.  Its end-to-end time is the round-trip uplink plus the
time from its due time to the end of ``execute``: the wait before
``execute`` started counts.  The executor's own draws (ModiPick's pick
among eligible members) are seeded from the run's seed, like the
requests (see ``traffic.py``).

For the check, each member's ``prefill_fn``/``decode_fn`` is wrapped
from outside: for the requests of a sample drawn from the seed, the
wrapper keeps the token fed to each decode step and the last decode
step's logits (device arrays; nothing is read back inside the window).
After the window the served tokens are compared with the float32
reference (``configs/dense_lm_ref.py``), which makes its own weights
from the seed.
"""
from __future__ import annotations

import gc
import os
import time

import numpy as np

from chipbench import costs, traffic as traffic_gen
from chipbench.bench import HERE, load_module

# Registry fields the configuration file states for each member.
MEMBER_FIELDS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "d_ff",
                 "vocab_size", "rope_theta", "norm_eps", "qkv_bias",
                 "tie_embeddings", "mlp", "norm", "dtype", "kv_cache_dtype",
                 "quality")


def member_config(member: dict):
    """The registry config of ``member["arch"]``; raises where it is not
    what the configuration file states."""
    from repro.configs.registry import get_config
    cfg = get_config(member["arch"])
    got = {k: getattr(cfg, k) for k in MEMBER_FIELDS}
    got.update(head_dim=cfg.resolved_head_dim, pattern=list(cfg.pattern),
               padded_vocab=cfg.padded_vocab, moe=cfg.moe)
    want = {k: member[k] for k in MEMBER_FIELDS + ("head_dim",)}
    want.update(pattern=["attn"], moe=None,
                padded_vocab=-(-member["vocab_size"] // member["vocab_pad"])
                * member["vocab_pad"])
    diff = {k: (want[k], got[k]) for k in want if want[k] != got[k]}
    if diff:
        raise RuntimeError(f"{member['arch']}: the program runs "
                           f"{ {k: g for k, (_, g) in diff.items()} }, the "
                           f"configuration states { {k: w for k, (w, _) in diff.items()} }")
    return cfg


class Run:
    span_prefixes = ("pool.",)
    # A traced run's window: some 100 requests, whose trace the run can
    # still read back within its time.
    trace_seconds = 10.0

    def __init__(self, config, traffic, seed, seconds, spans):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.spans = spans
        self.req = traffic_gen.open_loop(traffic, seconds, seed)
        rng = np.random.default_rng([seed, 3])
        vocab = traffic["token_ids"]
        self.tokens = [rng.integers(0, vocab, (1, int(s)), dtype=np.int32)
                       for s in self.req["prompt_len"]]
        self.attempted = len(self.tokens)
        self.sample = self._draw_sample(config["check"]["served_tokens"])

    def _draw_sample(self, served_tokens: int):
        """Request indices: the longest request, then others in an order
        drawn from the seed, until they hold ``served_tokens`` served
        tokens (each request serves ``n_decode + 1``)."""
        size = self.req["prompt_len"] + self.req["n_decode"]
        order = np.random.default_rng([self.seed, 2]).permutation(len(size))
        picked = [int(np.argmax(size))]
        total = int(self.req["n_decode"][picked[0]]) + 1
        for i in order:
            if total >= served_tokens:
                break
            if i != picked[0]:
                picked.append(int(i))
                total += int(self.req["n_decode"][i]) + 1
        return sorted(picked)

    # ------------------------------------------------------------------
    def setup(self):
        import jax
        from repro.core.policy import ModiPick
        from repro.launch import serve
        from repro.serving.executor import PoolExecutor

        cfgs = [member_config(m) for m in self.config["members"]]
        self.variants = serve.build_pool(cfgs, seed=self.seed,
                                         cache_len=self.config["cache_len"])
        jax.block_until_ready([v.params for v in self.variants])
        rng = np.random.default_rng([self.seed, 4])
        vocab = self.traffic["token_ids"]
        for v in self.variants:     # compile every prompt length served
            for s in self.traffic["fields"]["prompt_len"]["values"]:
                v.run(rng.integers(0, vocab, (1, s), dtype=np.int32), 1)
        self.ex = PoolExecutor(
            self.variants,
            traffic_gen.ScheduledUplink(self.req["uplink_ms"]),
            ModiPick(self.config["policy"]["t_threshold_ms"]),
            seed=self.seed,
            queue_aware=self.config["queue_aware"])
        w = self.traffic["warmup"]
        self.ex.warm_up(rng.integers(0, vocab, (1, w["prompt_len"]),
                                     dtype=np.int32), w["n_decode"])
        self._wrap()

    def _wrap(self):
        """Spans around the calls into the router and the model, and the
        capture of the sampled requests' served tokens."""
        spans = self.spans
        self.cur = -1
        self.calls = []                      # (kind, member, request)
        self.captured = {i: {"fed": []} for i in self.sample}
        for mi, v in enumerate(self.variants):
            def prefill(params, tok, _f=v.prefill_fn, _mi=mi):
                with spans("pool.prefill", keep=False):
                    out = _f(params, tok)
                self.calls.append(("prefill", _mi, self.cur))
                cap = self.captured.get(self.cur)
                if cap is not None:
                    cap["member"] = _mi
                return out

            def decode(params, cache, tok, pos, _f=v.decode_fn, _mi=mi):
                with spans("pool.decode", keep=False):
                    logits, cache = _f(params, cache, tok, pos)
                self.calls.append(("decode", _mi, self.cur))
                cap = self.captured.get(self.cur)
                if cap is not None:
                    cap["fed"].append(tok)
                    cap["last"] = logits
                return logits, cache

            v.prefill_fn, v.decode_fn = prefill, decode
        route = self.ex.router.route

        def routed(*a, **k):
            with spans("pool.route"):
                return route(*a, **k)
        self.ex.router.route = routed

    # ------------------------------------------------------------------
    def window(self):
        spans, ex, req = self.spans, self.ex, self.req
        n = self.attempted
        due, start, done = np.zeros(n), np.zeros(n), np.zeros(n)
        results = [None] * n
        t0 = time.perf_counter()
        for i in range(n):
            due[i] = t0 + req["arrival_s"][i]
            now = time.perf_counter()
            if now < due[i]:
                with spans("pool.idle", keep=False):
                    time.sleep(due[i] - now)
            self.cur = i
            start[i] = time.perf_counter()
            with spans("pool.execute", keep=False):
                results[i] = ex.execute(self.tokens[i], float(req["sla_ms"][i]),
                                        int(req["n_decode"][i]))
            done[i] = time.perf_counter()
        self.window_s = done[-1] - t0
        names = [v.name for v in self.variants]
        served = np.array([r.admitted and r.variant in names for r in results])
        t_input = np.array([r.t_input_ms for r in results])
        e2e = 2.0 * t_input + (done - due) * 1e3
        free = np.concatenate([[True], done[:-1] <= due[1:]])
        took = (done - start) * 1e3
        slow = int(np.argmax(took))
        # the slowest call and the profiles the router ends on, to tell a
        # stall of the host or chip from a profile that keeps a member out
        self.slowest = {"ms": float(took[slow]), "at_s": float(start[slow] - t0),
                        "member": results[slow].variant,
                        "profile_mu_ms": {v: float(ex.store[v].mu) for v in names}}
        self.requests = {
            "served": served,
            "e2e_ms": e2e,
            "met": served & (e2e <= req["sla_ms"]),
            "queue_wait_ms": (start - due) * 1e3,
            # how late the generator ran where nothing held it back
            "lateness_ms": ((start - due) * 1e3)[free],
            "quality": np.array([r.quality if s else np.nan
                                 for r, s in zip(results, served)]),
            "member": np.array([names.index(r.variant) if s else -1
                                for r, s in zip(results, served)]),
        }
        self.failed = int((~served).sum())

    def notes(self) -> dict:
        """Counts behind the metrics, for the run's standard error."""
        r = self.requests
        late = r["lateness_ms"]
        out = {}
        if self.trace is not None:
            # calls made, against the executions the trace holds
            out["calls_and_traced_programs"] = {
                kind: [sum(k == kind for k, _, _ in self.calls),
                       len(self.trace.programs.get(f"{kind}_step", []))]
                for kind in ("prefill", "decode")}
        return {**out, "requests": self.attempted, "window_s": self.window_s,
                "per_member": np.bincount(
                    r["member"][r["served"]],
                    minlength=len(self.config["members"])).tolist(),
                "generator_lateness_p95_ms":
                    float(np.percentile(late, 95)) if len(late) else None,
                "sla_attainment": float(r["met"].mean()),
                "queue_wait_mean_ms": float(r["queue_wait_ms"].mean()),
                "slowest_execute": self.slowest,
                "sampled_requests": len(self.sample),
                "sampled_tokens": sum(len(t) for _, t in self.served.values())}

    # ------------------------------------------------------------------
    def model_costs(self):
        """``(kind, Cost)`` of each model call in the window, from its
        shapes, in call order; ``kind`` is ``prefill`` or ``decode``."""
        members = self.config["members"]
        step = {}
        out = []
        for kind, mi, i in self.calls:
            s = int(self.req["prompt_len"][i])
            if kind == "prefill":
                step[i] = 0
                out.append((kind, costs.dense_lm_prefill(members[mi], s)))
            else:
                out.append((kind, costs.dense_lm_decode(members[mi],
                                                        s + step[i])))
                step[i] += 1
        return out

    def release(self):
        """Read the sampled requests' served tokens back, then free the
        pool."""
        vocab = [m["vocab_size"] for m in self.config["members"]]
        self.served = {}
        for i, cap in self.captured.items():
            if "last" not in cap:
                continue
            fed = [int(np.asarray(t)[0]) for t in cap["fed"]]
            last = np.asarray(cap["last"], np.float32)[0, :vocab[cap["member"]]]
            self.served[i] = (cap["member"], fed + [int(np.argmax(last))])
        self.captured = self.ex = self.variants = None
        gc.collect()

    def check(self):
        """The widest gap by which a served token's logit lies below the
        reference's best, over the sample; and the requests sent that got
        no result from a pool member."""
        ref = load_module(os.path.join(HERE, "configs",
                                       self.config["reference"]))
        members = self.config["members"]
        worst = 0.0 if self.served else float("inf")
        for mi, member in enumerate(members):
            reqs = [(self.tokens[i][0], toks)
                    for i, (m, toks) in sorted(self.served.items()) if m == mi]
            if not reqs:
                continue
            d = ref.dims(member)
            w = ref.make_weights(d, self.seed, mi, len(members))
            gaps = ref.served_gaps(d, w, reqs, length=self.config["cache_len"])
            worst = max(worst, max(float(g.max()) for g in gaps))
            del w
            gc.collect()
        missing = len(self.sample) - len(self.served)
        limits = self.config["check"]["limits"]
        return [("max_logit_gap", worst, limits["max_logit_gap"]),
                ("unserved_requests", self.failed + missing, 0)]
