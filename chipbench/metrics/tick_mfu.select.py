"""Selection layer, whole tick: the least operations of the traced
ticks' charged selection (from the batch, pool and replica counts) over
the device's busy time in the traced window (every operation that ran,
whatever its program is named) times the chip's peak bf16 FLOP/s, in %."""


def read(run):
    if run.trace is None or run.trace.busy_s <= 0 or not run.log:
        return None
    flops = len(run.log) * run.scan_cost().flops
    return 100.0 * flops / (run.trace.busy_s * run.peaks["bf16_flops_per_s"])
