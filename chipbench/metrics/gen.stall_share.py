"""Share (%) of the window's wall time that stalled calls spent beyond the
window's median call (``chipbench.stalls``), over the whole window of the
run, traced part and untraced part alike, from the call times the
generate job hands over."""
from chipbench.stalls import excess_s


def read(ctx, facts, trace):
    call_s = facts.get("call_s")
    if not call_s or facts["wall_s"] <= 0:
        return None
    excess = excess_s(call_s)
    ctx.log(metric="gen.stall_share", calls=len(call_s),
            stalled=sum(1 for e in excess if e > 0),
            excess_s=[e for e in excess if e > 0])
    return 100.0 * sum(excess) / facts["wall_s"]
