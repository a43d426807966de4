"""Shared layer primitives: norms, RoPE, MLPs, parameter templates."""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard


# ----------------------------------------------------------------------
# Parameter templates: shape + logical axes + init rule.  Templates let the
# dry-run build ShapeDtypeStructs and shardings without allocating, and let
# checkpoints be mesh-agnostic.
# ----------------------------------------------------------------------
@dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"  # normal | zeros | ones | rglru_lambda
    scale_dim: int = -1  # fan-in dim index for normal init scaling
    dtype: Optional[str] = None  # override the tree-wide dtype (e.g. int8 KV)


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def spec_map(fn, tree, *rest):
    return jax.tree.map(fn, tree, *rest, is_leaf=is_spec)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _init_leaf(spec: ParamSpec, k, dtype):
    """One leaf, drawn under jit: the float32 draw, its scale and the cast
    to ``dtype`` fuse, so only the ``dtype`` leaf is ever allocated on the
    device (a published-width stacked FFN leaf in float32 would not fit
    beside the rest of a pool)."""
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    if spec.init == "rglru_lambda":
        # Λ init so that a = sigmoid(Λ)^c spreads over (0.9, 0.999)
        u = jax.random.uniform(k, spec.shape, jnp.float32, 0.9, 0.999)
        lam = jnp.log(u ** (-2.0) - 1.0) * 0.5  # inverse of the a(Λ) map
        return lam.astype(dtype)
    fan_in = spec.shape[spec.scale_dim] if spec.shape else 1
    if spec.init == "normal_out":  # residual-out projection: extra-scaled
        std = 0.02 / jnp.sqrt(2.0)
    else:
        std = 1.0 / jnp.sqrt(max(1, fan_in))
    return (jax.random.normal(k, spec.shape, jnp.float32) * std).astype(dtype)


def materialize(template, key, dtype):
    """Initialize real parameters from a template tree."""
    leaves, treedef = jax.tree.flatten(template, is_leaf=is_spec)
    keys = jax.random.split(key, len(leaves))
    return treedef.unflatten([_init_leaf(s, k, jnp.dtype(dtype))
                              for s, k in zip(leaves, keys)])


def abstract(template, dtype):
    return spec_map(
        lambda s: jax.ShapeDtypeStruct(s.shape,
                                       jnp.dtype(s.dtype) if s.dtype else dtype),
        template)


def axes_tree(template):
    return spec_map(lambda s: s.axes, template)


def stack_specs(template, n: int, axis_name: Optional[str] = "layers"):
    """Add a leading stacked-layers dim to every spec (for scan)."""
    return spec_map(
        lambda s: ParamSpec((n,) + s.shape, (axis_name,) + s.axes, s.init,
                            s.scale_dim, s.dtype),
        template,
    )


# ----------------------------------------------------------------------
# Norms
# ----------------------------------------------------------------------
def rmsnorm(x, scale, eps):
    h = x.astype(jnp.float32)
    var = jnp.mean(h * h, axis=-1, keepdims=True)
    return ((h * jax.lax.rsqrt(var + eps)) * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def layernorm(x, scale, eps):
    h = x.astype(jnp.float32)
    mu = jnp.mean(h, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(h - mu), axis=-1, keepdims=True)
    return (((h - mu) * jax.lax.rsqrt(var + eps)) * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def apply_norm(kind, x, scale, eps):
    return rmsnorm(x, scale, eps) if kind == "rms" else layernorm(x, scale, eps)


def norm_template(d: int) -> dict:
    return {"scale": ParamSpec((d,), (None,), "zeros")}


# ----------------------------------------------------------------------
# RoPE
# ----------------------------------------------------------------------
def rope(x, positions, theta):
    """x: (..., S, H, hd); positions: (S,) or (B, S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., S, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if positions.ndim == 1:
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_pos(positions, d, max_scale=10_000.0):
    half = d // 2
    freqs = max_scale ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ----------------------------------------------------------------------
# MLPs
# ----------------------------------------------------------------------
def mlp_template(d: int, f: int, kind: str) -> dict:
    t = {"wo": ParamSpec((f, d), ("ff", "embed_fsdp"), "normal_out", 0)}
    if kind in ("swiglu", "geglu"):
        t["wi"] = ParamSpec((d, f), ("embed_fsdp", "ff"))
        t["wg"] = ParamSpec((d, f), ("embed_fsdp", "ff"))
    else:  # gelu
        t["wi"] = ParamSpec((d, f), ("embed_fsdp", "ff"))
    return t


def mlp_apply(params, x, kind: str):
    h = jnp.einsum("bsd,df->bsf", x, params["wi"])
    if kind in ("swiglu", "geglu"):
        g = jnp.einsum("bsd,df->bsf", x, params["wg"])
        act = jax.nn.silu(h) if kind == "swiglu" else jax.nn.gelu(h)
        h = act * g
    else:
        h = jax.nn.gelu(h)
    h = shard(h, "batch", None, "ff")
    return jnp.einsum("bsf,fd->bsd", h, params["wo"])
