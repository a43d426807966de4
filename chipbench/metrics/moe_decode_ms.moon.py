"""Model layer, expert member: mean device time of one decode step of
the latent-attention expert member (Moonlight), in ms.  Its executions
are the trace's ``decode_step`` executions matched in order with the
driver's decode calls; nothing where the counts differ or the pool has
no such member."""
from chipbench import moe_costs


def read(run):
    steps = moe_costs.member_decode_times(run)
    if not steps:
        return None
    return sum(d for _, d in steps) * 1e-6 / len(steps)
