"""Mean requested rows of the coalesced batches (``rows`` of the
``serve.device`` spans) in the window."""
import numpy as np


def read(ctx, facts, trace):
    rows = facts.get("batch_rows") or []
    return float(np.mean(rows)) if rows else None
