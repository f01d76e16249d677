"""Gradient/count histogram accumulation — the XGBoost ``hist`` hot spot.

``build_histogram`` picks, by ``impl``, between the pure-jnp reference
(segment-sum per feature, ``repro/kernels/hist/ref.py``) and the Pallas
kernel in ``repro/kernels/hist``, which implements the same contract as a
one-hot MXU matmul.

``axis_names`` turns this into the *distributed* histogram: rows are sharded
across the named mesh axes and partial histograms are psum'd — exactly
XGBoost's Rabit allreduce-of-histograms, expressed as a JAX collective.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import resolve_impl
from repro.kernels.hist.hist_kernel import histogram_pallas
from repro.kernels.hist.ref import histogram_ref


def build_histogram(codes, node_id, g, w, n_nodes: int, n_bins: int,
                    axis_names: Sequence[str] = (),
                    impl: Optional[str] = None
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Accumulate per-(node, feature, bin) gradient sums and weights.

    codes: [n, p] int; node_id: [n] int32; g: [n, out] fp32; w: [n] fp32.
    Returns (sum_g [n_nodes, p, n_bins, out], count [n_nodes, p, n_bins]).

    ``impl`` ('xla' | 'pallas' | 'pallas_interpret'; TPU runs set
    REPRO_HIST_IMPL=pallas) is resolved per call — setting the env var after
    import works, unlike the old module-level snapshot. Inside an
    already-compiled trainer the choice is baked in at trace time.
    """
    impl = resolve_impl(impl, env_var="REPRO_HIST_IMPL")
    if impl == "xla":
        sums, cnt = histogram_ref(codes, node_id, g, w, n_nodes, n_bins)
    else:
        sums, cnt = histogram_pallas(codes, node_id, g, w, n_nodes, n_bins,
                                     interpret=(impl == "pallas_interpret"))
    for ax in axis_names:
        sums = jax.lax.psum(sums, ax)
        cnt = jax.lax.psum(cnt, ax)
    return sums, cnt
