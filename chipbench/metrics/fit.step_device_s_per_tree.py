"""Device seconds of the fit-step program per tree it fitted: the
program's module events in the trace (name from the workload's
``fit_module``), averaged over chips, over the trees of the window."""


def read(ctx, facts, trace):
    if trace is None or trace.module_count(facts["module"]) == 0:
        return None
    return trace.module_s(facts["module"]) / facts["trees"]
