"""Share of the traced window in which no operation ran on the chips."""


def read(ctx, facts, trace):
    if trace is None or not trace.devices or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)
