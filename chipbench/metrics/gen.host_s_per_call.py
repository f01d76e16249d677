"""Host seconds of a sample call outside the device wait: the summed
durations of the program's ``sample.prepare``, ``sample.dispatch``,
``sample.fetch`` and ``sample.finish`` spans inside the traced window
(host events of the trace, from ``repro.obs`` scoped spans), over the
traced calls. A synchronous call runs these while the chip idles, so the
reading sits near the window's idle seconds per call."""
from chipbench.trace import clip

PHASES = ("sample.prepare", "sample.dispatch", "sample.fetch",
          "sample.finish")


def read(ctx, facts, trace):
    if trace is None:
        return None
    seconds = {}
    for name, a, b in trace.host:
        inside = clip([(a, b)], *trace.window) if name in PHASES else []
        for s, e in inside:
            seconds[name] = seconds.get(name, 0.0) + (e - s) * 1e-9
    if not seconds:
        return None
    ctx.log(metric="gen.host_s_per_call", phase_s=seconds,
            calls_traced=facts["calls_traced"])
    return sum(seconds.values()) / facts["calls_traced"]
