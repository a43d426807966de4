"""Serving launcher: a live pool of registry models behind the Router.

Each pool member is a registry config at its published widths, in the
config's own dtype (bf16), with seeded random weights.  Requests cross
the paper's measured campus-WiFi uplink and carry their own SLA, drawn
uniformly from ``[--sla-min-ms, --sla-max-ms]``.

  PYTHONPATH=src python -m repro.launch.serve \
      --archs qwen2-1.5b,phi4-mini-3.8b --policy modipick --requests 32

An expert member is built as one chip's share of an expert-parallel
deployment (``--experts-held`` of its experts, ``ModelConfig.expert_share``):
``--archs qwen2-1.5b,moonlight-16b-a3b`` holds 8 of Moonlight's 64 experts
per layer, the share of one chip in a group of 8.
"""
from __future__ import annotations

import argparse
import json
from typing import List, Sequence

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.configs.registry import get_config
from repro.core.netmodel import NetworkModel, campus_wifi
from repro.core.policy import Policy, make_policy
from repro.launch.compile_cache import enable_compile_cache
from repro.serving.executor import PoolExecutor
from repro.serving.pool import Variant

# Two dense registry models that share one v5e chip's 16 GB in bf16:
# 3.09 GB + 7.67 GB of weights.
DEFAULT_ARCHS = ("qwen2-1.5b", "phi4-mini-3.8b")


def build_pool(cfgs: Sequence[ModelConfig], *, cache_len: int,
               seed: int = 0) -> List[Variant]:
    """One pool member per config, each with its own fold of ``seed``."""
    keys = jax.random.split(jax.random.PRNGKey(seed), len(cfgs))
    return [Variant.from_config(cfg, k, cache_len=cache_len)
            for cfg, k in zip(cfgs, keys)]


def sla_mix(n: int, lo_ms: float, hi_ms: float, seed: int) -> np.ndarray:
    """Seeded per-request SLAs, uniform over ``[lo_ms, hi_ms]``."""
    return np.random.default_rng(seed).uniform(lo_ms, hi_ms, n)


def serve(variants: List[Variant], policy: Policy, t_sla: Sequence[float],
          tokens: np.ndarray, *, network: NetworkModel, n_decode: int = 2,
          seed: int = 0) -> PoolExecutor:
    """Warm every member up, then route and run one request per SLA in
    ``t_sla`` through a queue-aware :class:`PoolExecutor`; returns the
    executor, whose ``results`` and ``summary()`` hold the outcome."""
    ex = PoolExecutor(variants, network, policy, seed=seed, queue_aware=True)
    ex.warm_up(tokens, n_decode)
    for t in t_sla:
        ex.execute(tokens, float(t), n_decode)
    return ex


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--archs", default=",".join(DEFAULT_ARCHS),
                    help="comma-separated registry arch ids")
    ap.add_argument("--policy", default="modipick")
    ap.add_argument("--policy-kwargs", type=json.loads,
                    default={"t_threshold": 20.0},
                    help="policy constructor kwargs as JSON")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--sla-min-ms", type=float, default=150.0)
    ap.add_argument("--sla-max-ms", type=float, default=600.0)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--decode", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--experts-held", type=int, default=8,
                    help="experts per layer an expert member holds here")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfgs = [get_config(a) for a in args.archs.split(",")]
    cfgs = [c.expert_share(min(args.experts_held, c.moe.n_experts)) if c.moe else c
            for c in cfgs]
    variants = build_pool(cfgs, seed=args.seed, cache_len=args.seq + 16)
    tokens = np.random.default_rng(args.seed).integers(
        0, 500, (args.batch, args.seq), dtype=np.int32)
    ex = serve(variants, make_policy(args.policy, **args.policy_kwargs),
               sla_mix(args.requests, args.sla_min_ms, args.sla_max_ms,
                       args.seed),
               tokens, network=campus_wifi(), n_decode=args.decode,
               seed=args.seed)
    for i, r in enumerate(ex.results):
        print(f"req {i:4d} -> {r.variant or 'SHED':16s} "
              f"sla={r.t_sla_ms:6.1f}ms infer={r.t_infer_ms:6.1f}ms "
              f"e2e={r.t_e2e_ms:6.1f}ms met={r.met_sla}")
    print(json.dumps(ex.summary(), indent=1))


if __name__ == "__main__":
    main()
