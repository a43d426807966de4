"""shard_map wrappers: the Pallas kernels composed with the production
mesh.

GSPMD cannot partition an opaque `pallas_call`, so on TPU the kernels run
under `shard_map` with manual specs: batch over the data axes, heads over
'model' (when divisible — otherwise heads replicate and batch carries the
parallelism), KV broadcast for GQA.  The same wrappers run in interpret
mode on CPU fake-device meshes, which is how the tests validate the
sharded path against the unsharded oracle.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels import ops


def _data_axes(mesh: Mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def _head_axis(mesh: Mesh, n_heads: int, n_kv: int) -> Optional[str]:
    tp = "model" if "model" in mesh.shape else None
    if tp and n_heads % mesh.shape[tp] == 0 and n_kv % mesh.shape[tp] == 0:
        return tp
    return None


def sharded_flash_attention(q, k, v, mesh: Mesh, *, causal: bool = True,
                            window: int = 0):
    """q: (B, H, S, hd); k, v: (B, KV, S, hd) — batch over data axes,
    heads over 'model' when both H and KV divide it."""
    dp = _data_axes(mesh)
    hax = _head_axis(mesh, q.shape[1], k.shape[1])
    spec = P(dp or None, hax, None, None)

    fn = partial(ops.flash_attention, causal=causal, window=window)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def sharded_decode_attention(q, k, v, pos, mesh: Mesh, *, window: int = 0):
    """q: (B, KV, G, hd); k, v: (B, KV, S, hd); pos: (B,)."""
    dp = _data_axes(mesh)
    hax = _head_axis(mesh, k.shape[1], k.shape[1])
    spec_q = P(dp or None, hax, None, None)
    spec_kv = P(dp or None, hax, None, None)
    spec_pos = P(dp or None)

    fn = partial(ops.decode_attention, window=window)
    return jax.shard_map(fn, mesh=mesh,
                         in_specs=(spec_q, spec_kv, spec_kv, spec_pos),
                         out_specs=spec_q, check_vma=False)(q, k, v, pos)


def sharded_ssd_scan(x, dt, A, B_, C_, mesh: Mesh, *, chunk: int = 128):
    """x: (B, H, S, hd); dt: (B, H, S); A: (H,); B_, C_: (B, G, S, N).
    Heads shard over 'model' only when the group count divides too
    (otherwise B_/C_ would need replication-aware splitting)."""
    dp = _data_axes(mesh)
    hax = _head_axis(mesh, x.shape[1], B_.shape[1])
    fn = partial(ops.ssd_scan, chunk=chunk)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(dp or None, hax, None, None),
                  P(dp or None, hax, None),
                  P(hax),
                  P(dp or None, hax, None, None),
                  P(dp or None, hax, None, None)),
        out_specs=P(dp or None, hax, None, None), check_vma=False)(
            x, dt, A, B_, C_)


def sharded_fleet_select(mu, sig, acc, rank, t_u, t_l, keys, mesh: Mesh,
                         *, gamma: float = 1.0):
    """Fleet-wide ModiPick selection with the cell axis sharded.

    Every operand carries the cell on its leading axis — mu/sig/acc/rank
    (C, npad), t_u/t_l (C, B), keys (C, 2) PRNG keys — and shards over
    the mesh's ``cell`` axis (falling back to ``data`` when the fleet
    mesh reuses the training mesh's naming).  Each device vmaps the
    same jnp body (`kernels.policy_select.fleet_select_body`) over its
    local cells, so the sharded call is bit-identical to the single
    device `select_fleet_stacked` whenever C divides the axis; when it
    does not, the divisibility-aware rule drops the mapping and the
    call replicates (still correct, just not parallel)."""
    from repro.distributed.sharding import axis_rules, logical_to_spec
    from repro.kernels.policy_select import fleet_select_body

    ax = next((a for a in ("cell", "data") if a in mesh.shape), None)
    with axis_rules({"cell": ax}, mesh):
        spec = logical_to_spec(("cell", None), shape=t_u.shape, mesh=mesh)
    body = jax.vmap(partial(fleet_select_body, gamma=gamma))
    return jax.shard_map(body, mesh=mesh, in_specs=(spec,) * 7,
                         out_specs=spec, check_vma=False)(
                             mu, sig, acc, rank, t_u, t_l, keys)


def sharded_rglru_scan(a, b, mesh: Mesh, *, block_s: int = 256):
    """a, b: (B, S, W) — batch over data, channels over 'model'."""
    dp = _data_axes(mesh)
    tp = "model" if "model" in mesh.shape and a.shape[2] % mesh.shape["model"] == 0 else None
    spec = P(dp or None, None, tp)
    fn = partial(ops.rglru_scan, block_s=block_s)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec),
                         out_specs=spec, check_vma=False)(a, b)
