"""95th percentile, over the window's requests, of the client's latency
less the request's ``serve.queue`` span and its batch's ``serve.device``
span (joined on the request id): the HTTP front end, JSON and the
client's own wait."""
import numpy as np


def read(ctx, facts, trace):
    rest = facts.get("front_end_s") or []
    return float(np.percentile(rest, 95)) if rest else None
