"""Model layer, whole step: the operations of the window's prefill and
decode calls (from shapes) over the device's busy time in the traced
window (every operation that ran, whatever its program is named) times
the chip's peak bf16 FLOP/s, in %."""


def read(run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    calls = run.model_costs()
    if not calls:
        return None
    flops = sum(c.flops for _, c in calls)
    return 100.0 * flops / (run.trace.busy_s * run.peaks["bf16_flops_per_s"])
