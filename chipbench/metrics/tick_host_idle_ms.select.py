"""Router layer: device idle time per traced tick during which the host
was inside the router's own spans (``repro.obs.ROUTER_SPANS``: the
batch call, the ledger's packing, the draw, the scan's readback and the
write-back), in ms.  Gaps the benchmark's own spans name
(``router.tick``, ``router.charged_select``) are left out.  Nothing
where the program has no such spans."""


def read(run):
    if run.trace is None or not run.log:
        return None
    try:
        from repro.obs import ROUTER_SPANS
    except ImportError:
        return None
    ns = [n for label, _, n in run.trace.gaps if label in ROUTER_SPANS]
    return sum(ns) * 1e-6 / len(run.log) if ns else None
