"""Program spans: named host intervals on the profiler's clock.

``span(name)`` enters a ``jax.profiler.TraceAnnotation``, so while a
profiler session runs (``jax.profiler.trace``) the interval lands on the
trace's host plane beside the device's programs, and an idle gap on the
device can be named after what the host was doing in it.  With no
session the annotation costs well under a microsecond.  While ``jax`` has
not been imported, ``span`` is a shared null context: the numpy-only
modules (``repro.router``, ``repro.sim``) import no jax for it and pay
nothing.

Every span name is listed in :data:`SERVING_SPANS` or
:data:`ROUTER_SPANS`; call sites use only those names, and readers of a
trace import the tuples to tell program spans from any others.  Spans are
never opened inside traced (jitted) code, nor once per row of a batch;
the model's scopes inside jitted code are listed in :data:`MODEL_SCOPES`.
"""
from __future__ import annotations

import contextlib
import sys

# The live pool: ``PoolExecutor.execute`` and ``Variant.run``.
SERVING_SPANS = (
    "pool.exec",            # the whole execute call
    "pool.exec.route",      # the router's decision for the request
    "pool.exec.observe",    # profile update, hedge check, the result
    "pool.run",             # one member's prefill and decode steps
    "pool.run.upload",      # the prompt's tokens to the device
    "pool.run.sync",        # the wait for the last step's logits
    "pool.run.counters",    # an expert member's counters, read after the sync
)

# Named scopes inside the jitted model code (``jax.named_scope``): they
# name the device operations of a step in the trace and the HLO metadata.
MODEL_SCOPES = (
    "mla.attend",           # latent attention: projections, scores, output
    "moe.route",            # router scores, top-k choice and gates
    "moe.held",             # the held experts' part of an expert layer
    "moe.shared",           # the shared experts
)

# The router's batch path: ``Router.route_batch_arrays`` down to
# ``kernels.policy_select.charged_select``.
ROUTER_SPANS = (
    "router.route_batch",       # the whole batch call
    "router.charged_loop",      # the host's sequential charged loop
    "router.select.pack",       # ledger columns and key, one host to device
    "router.select.readback",   # the charged scan and the read of its columns
    "router.apply",             # decisions written back row by row
)

_NULL = contextlib.nullcontext()


def span(name: str):
    """A context manager marking ``name`` on the profiler's host plane
    (a null context while ``jax`` is not imported)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _NULL
    return jax.profiler.TraceAnnotation(name)
