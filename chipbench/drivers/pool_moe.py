"""Driver of a live pool with a latent-attention expert member: the pool
driver (``drivers/pool.py``) with three things replaced.

- The member check.  The expert member (Moonlight-16B-A3B) is built as
  this chip's share, ``get_config(arch).expert_share(experts_held)``,
  and every latent-attention and expert key the configuration file
  states is compared with what the program runs, before any weight is
  built: a program that cannot run the member fails within seconds.
  The dense member is checked as the pool driver checks it.
- ``model_costs()``: the expert member's calls are costed by
  ``moe_costs.py`` from shapes and the program's own counters (each
  request's ``RequestResult.moe_counts``: held experts that ran and the
  assignments they received, per phase; a decode call takes its
  request's decode counts shared evenly over its steps).
- The check of the expert member.  With random weights, a near-tie in
  the 64-way top-6 that bf16 breaks one way and float32 the other
  changes that layer's output, and every later layer's routing after
  it: served tokens then drift from the reference by as much as the
  float8 control's do.  So the sampled requests' routing is kept too
  (the last decode step's cache holds each position's chosen experts,
  read back after the window), the reference gates on the program's
  choices, and two numbers are compared: the widest logit gap of a
  served token (``max_logit_gap``), and the share of (position, expert
  layer) whose top-6 set the reference would have chosen otherwise
  (``route_mismatch_share``).  The dense member is checked as before.

Everything else — traffic, window, capture of served tokens, release —
is the pool driver's.
"""
from __future__ import annotations

import gc
import os
from contextlib import contextmanager

import numpy as np

from chipbench import costs, moe_costs
from chipbench.bench import HERE, load_module

pool = load_module(os.path.join(HERE, "drivers", "pool.py"))


def member_config(member: dict):
    """The program's config of ``member``, as this chip holds it; raises
    where it is not what the configuration file states."""
    if "kv_lora_rank" not in member:
        return pool.member_config(member)
    from repro.configs.registry import get_config
    cfg = get_config(member["arch"]).expert_share(member["experts_held"])
    a, e = cfg.mla, cfg.moe
    got = {
        "num_hidden_layers": cfg.n_layers, "first_k_dense_replace": cfg.first_k_dense,
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "q_lora_rank": None,
        "kv_lora_rank": a.kv_lora_rank, "qk_nope_head_dim": a.qk_nope_head_dim,
        "qk_rope_head_dim": a.qk_rope_head_dim, "v_head_dim": a.v_head_dim,
        "intermediate_size": cfg.d_ff, "moe_intermediate_size": e.d_ff_expert,
        "n_routed_experts": e.n_experts, "experts_held": e.held,
        "num_experts_per_tok": e.top_k, "n_shared_experts": e.n_shared,
        "scoring_func": e.scoring, "norm_topk_prob": True,
        "routed_scaling_factor": e.routed_scale, "vocab_size": cfg.vocab_size,
        "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
        "tie_word_embeddings": cfg.tie_embeddings, "dtype": cfg.dtype,
        "kv_cache_dtype": cfg.kv_cache_dtype, "quality": cfg.quality,
        "padded_vocab": cfg.padded_vocab, "pattern": list(cfg.pattern),
        "hidden_act": "silu" if cfg.mlp == "swiglu" else cfg.mlp,
    }
    want = {k: member[k] for k in got if k in member}
    want.update(pattern=["mla"], padded_vocab=-(-member["vocab_size"]
                                                // member["vocab_pad"]) * member["vocab_pad"])
    diff = {k: (want[k], got[k]) for k in want if want[k] != got[k]}
    if diff:
        raise RuntimeError(f"{member['arch']}: the program runs "
                           f"{ {k: g for k, (_, g) in diff.items()} }, the "
                           f"configuration states { {k: w for k, (w, _) in diff.items()} }")
    return cfg


@contextmanager
def _checked_members(cfgs):
    """The pool driver's set-up takes each member's config from
    ``pool.member_config``; here it is handed the configs checked above."""
    by_arch = {c.name: c for c in cfgs}
    plain = pool.member_config
    pool.member_config = lambda m: by_arch[m["arch"]]
    try:
        yield
    finally:
        pool.member_config = plain


class Run(pool.Run):
    def setup(self):
        cfgs = [member_config(m) for m in self.config["members"]]
        with _checked_members(cfgs):
            super().setup()

    def _wrap(self):
        """The pool driver's wrappers, and for the sampled requests of the
        expert member the routing the last decode step's cache holds
        (a device array; nothing is read back inside the window)."""
        super()._wrap()
        v = self.variants[moe_costs.moe_member(self.config)]
        decode = v.decode_fn

        def keep(params, cache, tok, pos):
            logits, cache = decode(params, cache, tok, pos)
            cap = self.captured.get(self.cur)
            if cap is not None:
                cap["route"] = cache["blocks"]["p0"]["route"]
            return logits, cache
        v.decode_fn = keep

    def release(self):
        self.routes = {i: np.asarray(cap["route"])[:, 0]
                       for i, cap in self.captured.items() if "route" in cap}
        super().release()

    def readings(self, control: bool = False) -> dict:
        """The compared numbers over the sample, for the program and,
        with ``control``, for the float8 control in its place."""
        ref = load_module(os.path.join(HERE, "configs", self.config["reference"]))
        members = self.config["members"]
        out = {"max_logit_gap": 0.0 if self.served else float("inf"),
               "route_mismatch_share": 0.0}
        ctrl = dict.fromkeys(out, 0.0)
        for mi, member in enumerate(members):
            ids = [i for i, (m, _) in sorted(self.served.items()) if m == mi]
            if not ids:
                continue
            reqs = [(self.tokens[i][0], self.served[i][1]) for i in ids]
            d = ref.dims(member)
            w = ref.make_weights(d, self.seed, mi, len(members))
            length = self.config["cache_len"]
            if "kv_lora_rank" in member:
                r = ref.served_gaps(d, w, reqs, length=length, control=control,
                                    routes=[self.routes[i] for i in ids])
                gaps, cgaps = r.gaps, r.ctrl
                out["route_mismatch_share"] = r.differ / r.total
                ctrl["route_mismatch_share"] = r.ctrl_differ / r.total
            else:
                got = ref.served_gaps(d, w, reqs, length=length, control=control)
                gaps, cgaps = got if control else (got, None)
            out["max_logit_gap"] = max(out["max_logit_gap"],
                                       max(float(g.max()) for g in gaps))
            if control:
                ctrl["max_logit_gap"] = max(ctrl["max_logit_gap"],
                                            max(float(g.max()) for g in cgaps))
            del w
            gc.collect()
        return {"program": out, "control": ctrl if control else None}

    def check(self):
        """The widest gap of a served token's logit below the reference's
        best and the share of routing choices that differ from the
        reference's, over the sample; and the requests sent that got no
        result from a pool member."""
        got = self.readings()["program"]
        limits = self.config["check"]["limits"]
        missing = len(self.sample) - len(self.served)
        return [(k, got[k], limits[k]) for k in ("max_logit_gap", "route_mismatch_share")] \
            + [("unserved_requests", self.failed + missing, 0)]

    def model_costs(self):
        """``(kind, Cost)`` of each model call in the window, in call
        order, from shapes and (expert member) the request's counters."""
        members = self.config["members"]
        out, step = [], {}
        for kind, mi, i in self.calls:
            m = members[mi]
            s = int(self.req["prompt_len"][i])
            if kind == "prefill":
                step[i] = 0
            pos = s + step[i]
            if "kv_lora_rank" not in m:
                out.append((kind, costs.dense_lm_prefill(m, s) if kind == "prefill"
                            else costs.dense_lm_decode(m, pos)))
            else:
                c = self.ex.results[i].moe_counts[kind]
                n = 1 if kind == "prefill" else int(self.req["n_decode"][i])
                f = moe_costs.prefill if kind == "prefill" else moe_costs.decode
                out.append((kind, f(m, s if kind == "prefill" else pos,
                                    c["experts_ran"] / n, c["held_assignments"] / n)))
            if kind == "decode":
                step[i] += 1
        return out
