"""Stalled calls of a closed-loop window.

A call is stalled when it takes more than ``FACTOR`` times the median call
of its window. Its excess is what it took beyond that median call: the
time the window lost to the stall. A window whose every call is slower
moves its median with it, so it holds no stall.
"""
from __future__ import annotations

import statistics
from typing import List, Sequence

FACTOR = 3.0


def excess_s(call_s: Sequence[float]) -> List[float]:
    """Per call, the seconds a stalled call spent beyond the window's
    median call, and 0.0 for every other call."""
    if not call_s:
        return []
    med = statistics.median(call_s)
    return [c - med if c > FACTOR * med else 0.0 for c in call_s]
