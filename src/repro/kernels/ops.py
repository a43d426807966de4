"""Jit'd public wrappers for the Pallas kernels.

On TPU the kernels run compiled; everywhere else (CPU CI, tests) they run
in interpret mode, which executes the same kernel bodies through the JAX
interpreter — bit-identical control flow, validated against ``ref.py``.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import rglru_scan as _rg
from repro.kernels import ssd_scan as _ssd


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128):
    """q: (B,H,Sq,hd); k,v: (B,KV,Sk,hd)."""
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=not _on_tpu())


@functools.partial(jax.jit, static_argnames=("window", "block_k"))
def decode_attention(q, k, v, pos, *, window: int = 0, block_k: int = 128):
    """q: (B,KV,G,hd); k,v: (B,KV,S,hd); pos: (B,)."""
    return _dec.decode_attention(q, k, v, pos, window=window, block_k=block_k,
                                 interpret=not _on_tpu())


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dt, A, B_, C_, *, chunk: int = 128):
    """x: (B,H,S,hd); dt post-softplus (B,H,S); A: (H,); B_,C_: (B,G,S,N)."""
    return _ssd.ssd_scan(x, dt, A, B_, C_, chunk=chunk,
                         interpret=not _on_tpu())


@functools.partial(jax.jit, static_argnames=("block_s",))
def rglru_scan(a, b, *, block_s: int = 256):
    """Linear recurrence over (B,S,W)."""
    return _rg.rglru_scan(a, b, block_s=block_s, interpret=not _on_tpu())

