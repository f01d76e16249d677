"""Pallas TPU kernel: packed-forest inference (generation hot spot, App. B.2).

Gather-free traversal: per level, the per-row (feature, threshold) pair is
selected with a one-hot matmul over the heap arrays, and the feature value is
selected with a one-hot mask over the row tile — every step is an MXU/VPU
contraction, no scalar gathers (TPU adaptation of the level-by-level compare
that XGBoost's C++ inference performs pointer-chasing for).

Grid: (row_blocks, trees); trees accumulate into the same output block.
VMEM per step: [R, p] row tile + [R, max(H, p, L)] one-hot — with R=256,
p<=640, depth 7 (H=127, L=128) comfortably under v5e VMEM.

The heap arrays travel as ``[T, 1, H]`` so one tree's block ``(1, 1, H)``
spans the full last two dims (Mosaic refuses a ``(1, H)`` block over
``[T, H]``: its second-to-last dim is neither 8-aligned nor the whole
axis). The select matmuls run at ``HIGHEST`` precision: a one-hot row times
an f32 operand must return that operand exactly (thresholds, leaf values
and feature ids above 256 are not bf16-representable).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _onehot(idx, size):
    iota = jax.lax.broadcasted_iota(jnp.int32, (idx.shape[0], size), 1)
    return (idx[:, None] == iota).astype(jnp.float32)


def _select(onehot, table, table_axis: int):
    """``onehot [R, K]`` contracted with ``table`` over its ``table_axis``
    (of size K) -> [R, C]; exact for f32 tables (one nonzero term per row)."""
    return jax.lax.dot_general(onehot, table,
                               (((1,), (table_axis,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _predict_kernel(x_ref, feat_ref, thr_ref, leaf_ref, out_ref, *,
                    depth: int):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...]                       # [R, p]
    feat = feat_ref[0].astype(jnp.float32)                # [1, H]
    # +inf sentinels ("never go right") must be finite: 0 * inf = NaN in the
    # one-hot select matmul. 1e30 exceeds any scaled feature value.
    thr = jnp.clip(thr_ref[0], -1e30, 1e30)               # [1, H]
    heap_vals = jnp.concatenate([feat, thr], axis=0)      # [2, H]
    leaf = leaf_ref[0]                   # [L, out]
    n_heap = heap_vals.shape[1]
    p = x.shape[1]
    node = jnp.zeros((x.shape[0],), jnp.int32)
    for level in range(depth):
        heap = node + (2 ** level - 1)
        sel = _onehot(heap, n_heap)                       # [R, H]
        ft = _select(sel, heap_vals, 1)                   # [R, 2]
        f = jnp.round(ft[:, 0]).astype(jnp.int32)
        xv = jnp.sum(x * _onehot(f, p), axis=1)           # [R]
        node = node * 2 + (xv > ft[:, 1]).astype(jnp.int32)
    out_ref[...] += _select(_onehot(node, leaf.shape[0]), leaf, 0)


def forest_predict_pallas(x, feat, thr_val, leaf, depth: int,
                          rows_block: int = 256, interpret: bool = False):
    """Same contract as ref.forest_predict_ref — any row count works.

    Rows are padded up to the next ``rows_block`` multiple before the call
    and the padding is sliced off the output, so serving-path batch shapes
    (odd buckets, oversize exact-size requests) never hit a grid-divisibility
    assert. Padded rows traverse with x=0 — every value is finite (the +inf
    sentinels are clipped inside the kernel), the garbage rows just get
    dropped.
    """
    n, p = x.shape
    n_trees, n_heap = feat.shape
    n_leaves, out = leaf.shape[1], leaf.shape[2]
    rows_block = min(rows_block, pl.cdiv(n, 8) * 8)   # sublane-aligned
    n_pad = pl.cdiv(n, rows_block) * rows_block
    if n_pad != n:
        x = jnp.pad(x, ((0, n_pad - n), (0, 0)))
    grid = (n_pad // rows_block, n_trees)
    kernel = functools.partial(_predict_kernel, depth=depth)
    res = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows_block, p), lambda r, t: (r, 0)),
            pl.BlockSpec((1, 1, n_heap), lambda r, t: (t, 0, 0)),
            pl.BlockSpec((1, 1, n_heap), lambda r, t: (t, 0, 0)),
            pl.BlockSpec((1, n_leaves, out), lambda r, t: (t, 0, 0)),
        ],
        out_specs=pl.BlockSpec((rows_block, out), lambda r, t: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, out), jnp.float32),
        interpret=interpret,
    )(x.astype(jnp.float32), feat.astype(jnp.int32)[:, None],
      thr_val.astype(jnp.float32)[:, None], leaf.astype(jnp.float32))
    return res if n_pad == n else res[:n]
