"""Selection layer: the least time one tick's charged scan could take
(the larger of its minimum operations over peak FLOP/s and its minimum
bytes over peak bandwidth, from the batch, pool and replica counts) over
the scan program's mean device time per traced execution, in %."""


def read(run):
    if run.trace is None:
        return None
    ev = run.trace.programs.get("run", [])
    if not ev:
        return None
    device_s = sum(d for _, d in ev) * 1e-9
    return 100.0 * len(ev) * run.scan_cost().seconds_at(run.peaks) / device_s
