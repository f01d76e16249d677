"""The solve's share of its roofline: the least time the chip could take
for the traced sample calls (``counts.least_time``: the larger of their
operations over peak FLOP/s and their bytes over peak bytes/s) over the
device seconds of the solve program in the trace (module name from the
workload's ``solve_module``)."""
from chipbench import counts


def read(ctx, facts, trace):
    if trace is None or trace.module_count(facts["module"]) == 0:
        return None
    peak = counts.peaks(ctx.devices[0].device_kind)
    calls = facts["calls_traced"]
    rows = facts["rows_computed"] // facts["calls"]
    ops = calls * counts.solve_ops(rows, facts["steps"], facts["trees"],
                                   facts["depth"], facts["p"])
    nbytes = calls * counts.solve_bytes(rows, facts["steps"],
                                        facts["classes"], facts["trees"],
                                        facts["depth"], facts["p"],
                                        facts["p"])
    least, bound = counts.least_time(ops, nbytes, peak)
    ctx.log(metric="gen.solve_roofline", least_s=least, bound=bound,
            module_s=trace.module_s(facts["module"]))
    return 100.0 * least / trace.module_s(facts["module"])
