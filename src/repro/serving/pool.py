"""Live model pool: JAX-served variants exposing accuracy/latency
trade-offs (the LLM analogue of the paper's CNN zoo).

Each variant owns compiled prefill/decode functions.
``Variant.from_config`` builds one member from a registry config at its
published widths in the config's dtype (the served pool,
``launch.serve.build_pool``); ``scaled_family`` builds a reduced-width
float32 pool from one architecture at several widths — the
MobileNet-vs-Inception spectrum ModiPick exploits, at a size the CPU
tests can run.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import api, model as M
from repro.obs import span


@functools.partial(jax.jit, static_argnames=("cfg", "cache_len"))
def prefill_step(cfg: ModelConfig, params, tokens, *, cache_len: int):
    """A pool member's compiled prefill: (cache, last-token logits)."""
    return M.prefill(cfg, params, {"tokens": tokens}, cache_len)


@functools.partial(jax.jit, static_argnames=("cfg",))
def decode_step(cfg: ModelConfig, params, cache, tok, pos):
    """A pool member's compiled decode step: (logits, new cache)."""
    return M.decode_step(cfg, params, cache, tok, pos)


@dataclass
class Variant:
    name: str
    cfg: ModelConfig
    quality: float
    params: object = None
    prefill_fn: Callable = None
    decode_fn: Callable = None
    cache_len: int = 128
    inflight: int = 0           # requests dispatched but not finished
    # An expert member's on-device counters from its last run, read once
    # after the final sync (``model.MOE_COUNTS`` rows); None otherwise.
    moe_counts: Optional[np.ndarray] = None

    def estimated_wait_ms(self, profile) -> float:
        """Queue-wait estimate for one more request on this variant.
        The two signals overlap — observed queue waits (queue_mu, see
        ProfileStore.observe_queue) already include time spent behind
        in-flight work — so take the max rather than the sum."""
        return max(self.inflight * max(profile.mu, 0.0), profile.queue_mu)

    @classmethod
    def from_config(cls, cfg: ModelConfig, key, *,
                    cache_len: int = 128) -> "Variant":
        """A pool member at the config's own widths and dtype (bf16 for
        every registry config); its quality is the config's declared
        ``quality``."""
        return cls(name=cfg.name, cfg=cfg, quality=cfg.quality,
                   cache_len=cache_len).build(key, jnp.dtype(cfg.dtype))

    def build(self, key, dtype=jnp.float32):
        self.params = M.init_params(self.cfg, key, dtype)
        self.prefill_fn = functools.partial(prefill_step, self.cfg,
                                            cache_len=self.cache_len)
        self.decode_fn = functools.partial(decode_step, self.cfg)
        return self

    def run(self, tokens: np.ndarray, n_decode: int = 4) -> float:
        """Execute prefill + n_decode steps; returns wall ms (blocking).
        With ``n_decode == 0`` the prefill alone runs and its logits are
        waited for.  An expert member's counters are read after the
        wait, into ``moe_counts``."""
        with span("pool.run"):
            t0 = time.perf_counter()
            with span("pool.run.upload"):
                tok = jnp.asarray(tokens)
            cache, logits = self.prefill_fn(self.params, tok)
            # positions are made on the device once the prefill is queued
            B, S = tokens.shape
            pos = jnp.full((B,), S, jnp.int32)
            for _ in range(n_decode):
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                logits, cache = self.decode_fn(self.params, cache, nxt, pos)
                pos = pos + 1
            with span("pool.run.sync"):
                jax.block_until_ready(logits)
            ms = (time.perf_counter() - t0) * 1e3
            if "moe_counts" in cache:
                with span("pool.run.counters"):
                    self.moe_counts = np.asarray(cache["moe_counts"])
            return ms


def scaled_family(base: ModelConfig, *, widths=(0.25, 0.5, 1.0),
                  qualities=None, seed: int = 0,
                  cache_len: int = 128) -> List[Variant]:
    """Build a pool of width-scaled variants of one family."""
    reduced = base.reduced()
    out = []
    key = jax.random.PRNGKey(seed)
    for i, w in enumerate(widths):
        cfg = reduced.scaled(w, name=f"{base.name}-w{w:g}")
        q = qualities[i] if qualities else base.quality * (0.6 + 0.4 * w)
        key, k = jax.random.split(key)
        v = Variant(name=cfg.name, cfg=cfg, quality=q, cache_len=cache_len)
        v.build(k)
        out.append(v)
    return out
