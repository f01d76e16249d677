"""Packed forests: stacked tree arrays + vectorised inference.

The packed layout (feat/thr/leaf arrays with leading [n_sub, T] dims) is what
the Pallas ``tree_predict`` kernel consumes; ``predict_forest`` here routes
every traversal through :func:`repro.kernels.tree_predict.ops.forest_predict`
— one dispatch point, switchable between the XLA reference scan and the
Pallas kernel per call (``impl=`` | ``ForestConfig.predict_impl`` |
``REPRO_TREE_PREDICT_IMPL`` | the platform's default) — so samplers,
imputation, and serving all inherit the kernel without their own
plumbing. One packed forest represents one (timestep, class) ensemble;
the generator stacks them further to [n_t, ...] for the ODE/SDE solve.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.kernels.tree_predict.ops import forest_predict


class PackedForest(NamedTuple):
    feat: jnp.ndarray      # [n_sub, T, H] int32
    thr_val: jnp.ndarray   # [n_sub, T, H] fp32
    leaf: jnp.ndarray      # [n_sub, T, L, out_sub] fp32
    multi_output: bool     # static


def from_boost_result(res, multi_output: bool) -> PackedForest:
    return PackedForest(res.feat, res.thr_val, res.leaf, multi_output)


def predict_forest(x, forest: PackedForest, depth: int,
                   impl: Optional[str] = None):
    """x: [n, p] raw feature values. Returns [n, p_out].

    ``impl`` selects the traversal backend (resolved per call; the Pallas
    kernel is vmapped over the ``n_sub`` sub-ensembles exactly like the
    reference scan, so both paths see identical shapes).
    """

    def sub_predict(feat, thr, leaf):
        return forest_predict(x, feat, thr, leaf, depth, impl=impl)

    out = jax.vmap(sub_predict)(forest.feat, forest.thr_val, forest.leaf)
    if forest.multi_output:
        return out[0]                      # [n, p_out]
    return jnp.transpose(out[:, :, 0])     # SO: [p_out, n, 1] -> [n, p_out]
