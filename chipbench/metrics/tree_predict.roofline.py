"""The tree traversal's share of its roofline: the least time the chip
could take for the traced sample calls (``counts.least_time`` of
``counts.solve_ops`` and ``counts.solve_bytes``, as ``gen.solve_roofline``
counts them) over the device seconds of the ops in the ``tree_predict``
name scope (``scopes.scope_s``, each op's scope read from the live
programs' HLO), whatever implements the traversal and whatever the program
around it is called.

The count is the whole solve's: the ``2 * out`` Euler ops per row and
step are 2 % of the counted ops, and the state read and written each step
14 % of the counted bytes, at the pion cell's shapes. The share reads at
least ``gen.solve_roofline``, whose time holds the traversal's."""
from chipbench import counts, scopes


def read(ctx, facts, trace):
    if trace is None or not trace.devices:
        return None
    programs = scopes.live_programs(ctx.devices)
    scope_s = scopes.scope_s(trace, "tree_predict", programs)
    if scope_s <= 0.0:
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
    unknown = sum(path is None for ops_ in scopes.op_scopes(trace, programs)
                  for path, _, _ in ops_)
    ctx.log(metric="tree_predict.roofline", least_s=least, bound=bound,
            scope_s=scope_s, ops_without_program=unknown)
    return 100.0 * least / scope_s
