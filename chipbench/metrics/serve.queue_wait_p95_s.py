"""95th percentile of the ``serve.queue`` spans (submit to batch claim)
of the requests due in the window."""
import numpy as np


def read(ctx, facts, trace):
    waits = facts.get("queue_wait_s") or []
    return float(np.percentile(waits, 95)) if waits else None
