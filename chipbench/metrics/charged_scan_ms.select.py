"""Selection layer: device time of the charged ``lax.scan`` program per
traced execution, in ms.  The program is the jitted inner ``run`` of
``kernels.policy_select._charged_jit``, so its trace name is ``run``."""


def read(run):
    if run.trace is None:
        return None
    ev = run.trace.programs.get("run", [])
    if not ev:
        return None
    return sum(d for _, d in ev) * 1e-6 / len(ev)
