"""Executor layer: device idle time per request sent in the traced
window during which the host was inside the executor's or a member
run's own spans (``repro.obs.SERVING_SPANS``: routing call, profile
update and result, token upload, the final sync), in ms.  Gaps the
benchmark's own spans name (``pool.route``, ``pool.execute``,
``pool.idle``) are left out.  Nothing where the program has no such
spans."""


def read(run):
    if run.trace is None or not run.attempted:
        return None
    try:
        from repro.obs import SERVING_SPANS
    except ImportError:
        return None
    ns = [n for label, _, n in run.trace.gaps if label in SERVING_SPANS]
    return sum(ns) * 1e-6 / run.attempted if ns else None
