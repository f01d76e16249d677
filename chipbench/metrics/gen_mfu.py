"""Whole generation's share of the chip's peak while the chip works: the
solve operations of the traced calls (``counts.solve_ops``) over the
trace's busy device seconds (every op, whatever program runs it), over
peak FLOP/s. Idle time is ``idle_share.gen``'s to read."""
from chipbench import counts


def read(ctx, facts, trace):
    if trace is None or trace.busy_s <= 0.0:
        return None
    peak = counts.peaks(ctx.devices[0].device_kind)
    rows = facts["rows_computed"] // facts["calls"]
    ops = facts["calls_traced"] * counts.solve_ops(
        rows, facts["steps"], facts["trees"], facts["depth"], facts["p"])
    return 100.0 * ops / (trace.busy_s * facts["chips"]
                          * peak["flops_per_s"])
