"""Whole fit's share of the chips' peak: the operations a tree needs
(``counts.fit_tree_ops``) times the trees fitted per second of the
window, over chips times peak FLOP/s."""
from chipbench import counts


def read(ctx, facts, trace):
    if trace is None or not trace.devices:
        return None
    peak = counts.peaks(ctx.devices[0].device_kind)
    ops = counts.fit_tree_ops(facts["rows_per_ensemble"], facts["p"],
                              facts["out"], facts["depth"], facts["bins"])
    rate = ops * facts["trees"] / facts["wall_s"]
    return 100.0 * rate / (facts["chips"] * peak["flops_per_s"])
