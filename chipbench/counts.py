"""Operations and bytes the algorithm needs, counted from shapes.

These count the work of the method, not of one implementation: a faster
kernel that does the same algorithm keeps the same count, and a peak
share computed from them cannot pass 100% unless the time leaves work out.

Fit, per tree of depth ``depth`` over ``rows`` rows, ``p`` features,
``out`` outputs and ``bins`` bins (multi-output trees, squared error), at
each level with ``nodes = 2**level``:

* histogram: one add per (row, feature) for the count and ``out`` for the
  gradient sums: ``rows * p * (out + 1)``;
* split scan, per (node, feature, bin, output): the running left sum, the
  right sum by subtraction, two squares and two accumulations into the
  gain: ``6 * nodes * p * bins * out``;
* routing: one compare per row;

then the leaves: gradient ``rows * out`` (subtract), the leaf sums
``rows * out`` and the prediction update ``rows * out``.

Solve (Euler flow ODE), per row, step and tree: ``depth`` compares and
``out`` accumulates; per row and step, ``2 * out`` for ``x - h v``. The
least bytes of a call are the forests of the steps it runs read once,
plus the state read and written once per step.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    """Peak FLOP/s and HBM bytes/s of one chip. An unknown kind is an
    error, never a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


def fit_tree_ops(rows: int, p: int, out: int, depth: int, bins: int) -> int:
    ops = 0
    for level in range(depth):
        nodes = 2 ** level
        ops += rows * p * (out + 1) + 6 * nodes * p * bins * out + rows
    return ops + 3 * rows * out


def solve_ops(rows: int, steps: int, trees: int, depth: int, out: int) -> int:
    return rows * steps * (trees * (depth + out) + 2 * out)


def solve_bytes(rows: int, steps: int, classes: int, trees: int, depth: int,
                p: int, out: int) -> int:
    """Forests of ``steps`` steps and ``classes`` classes read once (int32
    features, fp32 thresholds and leaves) plus the fp32 state in and out
    of each step."""
    heap, leaves = 2 ** depth - 1, 2 ** depth
    forest = steps * classes * trees * (heap * 8 + leaves * out * 4)
    return forest + rows * steps * 2 * p * 4


def least_time(ops: int, nbytes: int, peak: dict):
    """(seconds, bound) of the roofline: the larger of ops over peak
    FLOP/s and bytes over peak bytes/s, and which one it is."""
    t_ops = ops / peak["flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "ops") if t_ops >= t_bytes else (t_bytes, "bytes")
