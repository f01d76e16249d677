"""Pallas TPU kernel: packed-forest inference (generation hot spot, App. B.2).

Gather-free traversal. XLA lowers the reference's data-dependent lookups
(``f_h[heap]``, ``take_along_axis(x, f)``, ``l_h[node]``) to slow serial
code on the TPU; here each tree is three matmuls against one-hot or
path matrices on the MXU, and no level waits on the one before:

1. every heap node's comparison at once: the row's value of each node's
   feature is ``x @ onehot(feat)`` [R, H], compared with the node's
   threshold;
2. the leaf each row reaches: ``cmp @ P`` counts, for each leaf, the
   right turns taken on its path minus the left turns taken wrongly, and
   equals the leaf's own number of right turns only where every
   comparison on its path agrees (:func:`path_matrix`);
3. the leaf's values: ``onehot(leaf) @ leaf_table``.

Every select is exact, so the output is the reference's bit for bit. A
one-hot row holds one 1.0 and zeros, so each matmul returns one product
and adds zeros to it. The rows and the leaves are f32, so they go in as
three bf16 parts whose f32 sum ``(hi + mid) + lo`` is the value again
(:func:`split3`); each part is selected by a single-pass bf16 matmul and
the parts are added back in that order. This holds for finite values of
magnitude at least 2^-110 (about 7.7e-34) and for zero: below that the
smallest part is subnormal, which the TPU flushes.

Grid: (row_blocks, tree_blocks). A grid step walks ``trees_block`` trees
in order and adds each tree's leaf to the output block, which stays in
VMEM across the tree axis, so leaves are summed in the reference's order.
:func:`plan` sizes both blocks from the shape within :data:`VMEM_LIMIT`.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI_BITS = -65536          # 0xFFFF0000: sign, exponent, top 7 mantissa bits

# VMEM the kernel asks Mosaic for (v5e and v6e hold 128 MiB a core, v5p
# 64 MiB); :func:`plan` keeps its estimate of the working set to half of
# it, the rest is room for Mosaic's own temporaries
VMEM_LIMIT = 64 * 2 ** 20
# the fastest blocks of 256-1024 rows and 1-20 trees at the pion solve's
# widths (p = out = 533, depth 7) on a TPU v5 lite: 1024-row buckets ran
# 2.66 ms a step at 512 x 10 against 3.37 ms at 256 x 1
MAX_ROWS_BLOCK = 512
MAX_TREES_BLOCK = 10


def _trunc_bf16(a):
    """``a`` with its low 16 bits cleared: exactly a bf16 value, in f32."""
    bits = jax.lax.bitcast_convert_type(a, jnp.int32)
    return jax.lax.bitcast_convert_type(bits & _HI_BITS, jnp.float32)


def split3(a):
    """f32 -> (hi, mid, lo) bf16 with ``(hi + mid) + lo == a`` in f32.

    Truncation leaves each residual exact in f32 and each part exactly
    representable in bf16, so no cast rounds, whatever its rounding mode.
    """
    hi = _trunc_bf16(a)
    r = a - hi
    mid = _trunc_bf16(r)
    lo = r - mid
    return tuple(v.astype(jnp.bfloat16) for v in (hi, mid, lo))


def _dot(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def path_matrix(depth: int):
    """(P [H, L], n_right [1, L]) f32 for a complete heap of ``depth``
    levels: ``P[h, l]`` is +1 where leaf ``l`` lies right of node ``h``,
    -1 where it lies left of it, 0 off its path; ``n_right[l]`` counts
    its right turns."""
    n_heap, n_leaves = 2 ** depth - 1, 2 ** depth
    P = np.zeros((n_heap, n_leaves), np.float32)
    for leaf in range(n_leaves):
        node = 0
        for level in range(depth):
            right = (leaf >> (depth - 1 - level)) & 1
            P[node, leaf] = 1.0 if right else -1.0
            node = 2 * node + 1 + right
    n_right = np.array([[bin(l).count("1") for l in range(n_leaves)]],
                       np.float32)
    return P, n_right


def _lanes(k: int) -> int:
    return -(-k // 128) * 128


def vmem_bytes(rows_block: int, trees_block: int, p: int, out: int,
               depth: int) -> int:
    """Estimated VMEM working set of one grid step: the double-buffered
    blocks (split rows, output, leaves, path matrix) and the largest
    temporaries of one tree (the three selected leaf parts and their sum,
    the [R, H] and [R, L] intermediates, the feature one-hot)."""
    n_heap, n_leaves = 2 ** depth - 1, 2 ** depth
    blocks = 2 * (3 * rows_block * _lanes(p) * 2
                  + rows_block * _lanes(out) * 4
                  + trees_block * n_leaves * _lanes(out) * 4
                  + 2 * trees_block * _lanes(n_heap) * 4
                  + n_heap * _lanes(n_leaves) * 4)
    temps = (5 * rows_block * _lanes(out) * 4
             + 3 * n_leaves * _lanes(out) * 2
             + p * _lanes(n_heap) * 2
             + 6 * rows_block * _lanes(max(n_heap, n_leaves)) * 4)
    return blocks + temps


def plan(n: int, n_trees: int, p: int, out: int,
         depth: int) -> Optional[Tuple[int, int]]:
    """(rows_block, trees_block) for ``n`` rows and ``n_trees`` trees, or
    None where not even 16 rows and one tree fit :data:`VMEM_LIMIT`.

    Rows: the bucket rounded up to the bf16 tile (16), at most
    :data:`MAX_ROWS_BLOCK`; trees: the largest divisor of ``n_trees`` up
    to :data:`MAX_TREES_BLOCK`. Both shrink, trees first, until the
    working set fits.
    """
    rows = min(MAX_ROWS_BLOCK, -(-n // 16) * 16)
    trees = max(t for t in range(1, min(n_trees, MAX_TREES_BLOCK) + 1)
                if n_trees % t == 0)
    while vmem_bytes(rows, trees, p, out, depth) > VMEM_LIMIT // 2:
        if trees > 1:
            trees = max(t for t in range(1, trees) if n_trees % t == 0)
        elif rows > 16:
            rows = max(16, rows // 2 // 16 * 16)
        else:
            return None
    return rows, trees


def _predict_kernel(x_ref, feat_ref, thr_ref, leaf_ref, path_ref, nr_ref,
                    out_ref, *, trees_block: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x_parts = [x_ref[i] for i in range(3)]               # 3 x [R, p] bf16
    p = x_parts[0].shape[1]
    paths = path_ref[...].astype(jnp.bfloat16)            # [H, L]
    n_right = nr_ref[...]                                 # [1, L]
    acc = out_ref[...]
    for j in range(trees_block):
        feat, thr = feat_ref[j], thr_ref[j]               # [1, H]
        iota = jax.lax.broadcasted_iota(jnp.int32, (p, feat.shape[1]), 0)
        onehot_f = (iota == feat).astype(jnp.bfloat16)    # [p, H]
        hi, mid, lo = (_dot(xp, onehot_f) for xp in x_parts)
        cmp = (((hi + mid) + lo) > thr).astype(jnp.bfloat16)   # [R, H]
        reached = _dot(cmp, paths) == n_right             # [R, L]
        onehot_l = reached.astype(jnp.bfloat16)
        hi, mid, lo = (_dot(onehot_l, part) for part in split3(leaf_ref[j]))
        acc = acc + ((hi + mid) + lo)
    out_ref[...] = acc


def forest_predict_pallas(x, feat, thr_val, leaf, depth: int,
                          rows_block: Optional[int] = None,
                          interpret: bool = False):
    """Same contract as ref.forest_predict_ref — any row count works.

    Blocks come from :func:`plan` (``rows_block`` overrides its rows).
    Rows are padded up to the next ``rows_block`` multiple before the call
    and the padding is sliced off the output, so serving-path batch shapes
    (odd buckets, oversize exact-size requests) never hit a
    grid-divisibility assert. Padded rows traverse with x=0 and are
    dropped.
    """
    n, p = x.shape
    n_trees, n_heap = feat.shape
    n_leaves, out = leaf.shape[1], leaf.shape[2]
    auto_rows, tb = plan(n, n_trees, p, out, depth) or (16, 1)
    rows_block = min(rows_block or auto_rows, -(-n // 16) * 16)
    n_pad = pl.cdiv(n, rows_block) * rows_block
    x = x.astype(jnp.float32)
    if n_pad != n:
        x = jnp.pad(x, ((0, n_pad - n), (0, 0)))
    P, n_right = path_matrix(depth)
    # heap arrays travel as [T, 1, H]: Mosaic takes a (tb, H) block over
    # [T, H] only where tb is 8-aligned or all of T
    res = pl.pallas_call(
        functools.partial(_predict_kernel, trees_block=tb),
        grid=(n_pad // rows_block, n_trees // tb),
        in_specs=[
            pl.BlockSpec((3, rows_block, p), lambda r, t: (0, r, 0)),
            pl.BlockSpec((tb, 1, n_heap), lambda r, t: (t, 0, 0)),
            pl.BlockSpec((tb, 1, n_heap), lambda r, t: (t, 0, 0)),
            pl.BlockSpec((tb, n_leaves, out), lambda r, t: (t, 0, 0)),
            pl.BlockSpec(P.shape, lambda r, t: (0, 0)),
            pl.BlockSpec(n_right.shape, lambda r, t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((rows_block, out), lambda r, t: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, out), jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(jnp.stack(split3(x)), feat.astype(jnp.int32)[:, None],
      thr_val.astype(jnp.float32)[:, None], leaf.astype(jnp.float32),
      jnp.asarray(P), jnp.asarray(n_right))
    return res if n_pad == n else res[:n]
