"""Model layer, expert member: the least time of the expert member's
decode steps (``moe_costs.decode``: weights outside the routed experts,
the latent cache to the position, and the held experts the program's
counter says ran) over their device time, in %.  Steps are matched as
in ``moe_decode_ms.moon``; nothing where they cannot be."""
from chipbench import moe_costs


def read(run):
    steps = moe_costs.member_decode_times(run)
    if not steps:
        return None
    cost = [c for k, c in run.model_costs() if k == "decode"]
    least = sum(cost[j].seconds_at(run.peaks) for j, _ in steps)
    return 100.0 * least / (sum(d for _, d in steps) * 1e-9)
