"""Model layer: the least time the chip could take for the window's
prefill and decode calls (the larger of operations over peak FLOP/s and
bytes over peak bandwidth, per call, from shapes) over the device time
of the ``prefill_step`` and ``decode_step`` programs, in %.

Each kind is weighed by the executions the trace holds: the mean least
time of its calls times its traced executions, so that an execution the
profiler dropped takes its call's share out of both sides.  Where the
counts agree, this is the plain sum."""


def read(run):
    if run.trace is None:
        return None
    calls = run.model_costs()
    least = device = 0.0
    for kind in ("prefill", "decode"):
        ev = run.trace.programs.get(f"{kind}_step", [])
        times = [c.seconds_at(run.peaks) for k, c in calls if k == kind]
        if ev and times:
            least += len(ev) * sum(times) / len(times)
            device += sum(d for _, d in ev) * 1e-9
    return 100.0 * least / device if device > 0 else None
