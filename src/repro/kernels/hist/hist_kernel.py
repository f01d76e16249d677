"""Pallas TPU kernel: GBDT histogram build as a one-hot MXU matmul.

Design (DESIGN.md §2 "trees on a systolic-array machine"): the scatter-add
XGBoost performs per (node, feature, bin) is re-expressed as

    hist[j] = [g * w ; w]^T  @  onehot(node_id * n_bins + codes[:, j])

so the accumulation runs on the MXU instead of a serial scatter unit. The
weight column rides along as one extra gradient column, so the count
histogram falls out of the same matmul. The grid is (features, output
blocks, row blocks); row blocks accumulate into the same output block.

Layouts are chosen so every block is legal for Mosaic and no HBM array has
a trailing size-1 lane axis: codes travel as ``[p, 1, n]`` (a block's last
two dims are the whole unit axis and a 128-multiple of rows), node ids as
``[1, n]``, the gradient columns transposed to ``[out + 1, n]``, and the
result as ``[p, out + 1, n_nodes * n_bins]``. Gradient columns are tiled in
blocks of 128 so a multi-output fit (out = p = 368 for CaloChallenge
photons) keeps its ``[128, n_nodes * n_bins]`` accumulator in VMEM. The
wrapper transposes the result back to the reference's
``[n_nodes, p, n_bins, out]`` contract.

VMEM per step: the ``[n_nodes * n_bins, rows_block]`` one-hot plus the
``[128, n_nodes * n_bins]`` output block; ``rows_block`` shrinks as the
node count grows so the one-hot stays near 4 MiB.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_LANES = 128
_ONEHOT_ELEMS = 1 << 20     # one-hot budget: 4 MiB of f32 per grid step


def _hist_kernel(codes_ref, nid_ref, gw_ref, out_ref, *, n_bins: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    key = nid_ref[...] * n_bins + codes_ref[0]            # [1, R]
    nb = out_ref.shape[2]
    iota = jax.lax.broadcasted_iota(jnp.int32, (nb, key.shape[1]), 0)
    onehot = (iota == key).astype(jnp.float32)            # [NB, R]
    out_ref[0] += jax.lax.dot_general(
        gw_ref[...], onehot, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)               # [ob, NB]


def histogram_pallas(codes, node_id, g, w, n_nodes: int, n_bins: int,
                     rows_block: Optional[int] = None,
                     interpret: bool = False):
    """Same contract as ref.histogram_ref; any row count works.

    Rows are padded with zero weight up to a ``rows_block`` multiple
    (rounded up to 128 lanes; by default sized from the node count), so a
    padded row adds nothing to either histogram.
    """
    n, p = codes.shape
    out = g.shape[1]
    nb = n_nodes * n_bins
    if rows_block is None:
        rows_block = max(_LANES, min(1024, _ONEHOT_ELEMS // nb))
    rows_block = pl.cdiv(min(rows_block, n), _LANES) * _LANES
    n_pad = pl.cdiv(n, rows_block) * rows_block
    cols = out + 1                                        # + the weight column
    ob = cols if cols <= _LANES else _LANES
    cols_pad = pl.cdiv(cols, ob) * ob

    w = w.astype(jnp.float32)
    gw = jnp.concatenate([g.astype(jnp.float32) * w[:, None], w[:, None]],
                         axis=1)
    gw_t = jnp.pad(gw.T, ((0, cols_pad - cols), (0, n_pad - n)))
    codes_t = jnp.pad(codes.astype(jnp.int32).T,
                      ((0, 0), (0, n_pad - n)))[:, None, :]
    nid = jnp.pad(node_id.astype(jnp.int32), (0, n_pad - n))[None, :]

    res = pl.pallas_call(
        functools.partial(_hist_kernel, n_bins=n_bins),
        grid=(p, cols_pad // ob, n_pad // rows_block),
        in_specs=[
            pl.BlockSpec((1, 1, rows_block), lambda j, o, r: (j, 0, r)),
            pl.BlockSpec((1, rows_block), lambda j, o, r: (0, r)),
            pl.BlockSpec((ob, rows_block), lambda j, o, r: (o, r)),
        ],
        out_specs=pl.BlockSpec((1, ob, nb), lambda j, o, r: (j, o, 0)),
        out_shape=jax.ShapeDtypeStruct((p, cols_pad, nb), jnp.float32),
        interpret=interpret,
    )(codes_t, nid, gw_t)
    res = res[:, :cols].reshape(p, cols, n_nodes, n_bins)
    sum_g = jnp.transpose(res[:, :out], (2, 0, 3, 1))     # [nodes, p, bins, out]
    cnt = jnp.transpose(res[:, out], (1, 0, 2))           # [nodes, p, bins]
    return sum_g, cnt
