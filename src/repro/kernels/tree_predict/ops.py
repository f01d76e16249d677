"""Jit'd dispatch wrapper for packed-forest inference.

``forest_predict`` is the one entry point every traversal goes through
(:func:`repro.forest.packed.predict_forest` routes here, so samplers,
imputation, and serving inherit whichever impl is selected). The impl is
resolved per call — explicit argument first, then the
``REPRO_TREE_PREDICT_IMPL`` environment variable, then
:func:`default_impl` — and passed to the jitted core as a static
argument, so each impl compiles its own program and switching at runtime
just selects a different cache entry.

The traversal runs under ``jax.named_scope("tree_predict")`` inside the
jitted core, so the device ops of either impl carry ``tree_predict`` in
their op name, alone or inlined in a larger program (a scope opened
around a call of a jitted function does not reach a top-level call).
A profiler trace finds the traversal by that name whatever implements it.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels.dispatch import resolve_impl
from repro.kernels.tree_predict.ref import forest_predict_ref
from repro.kernels.tree_predict.tree_kernel import (forest_predict_pallas,
                                                    plan)

ENV_VAR = "REPRO_TREE_PREDICT_IMPL"


def default_impl(platform: str, p: int, out: int, mesh,
                 depth: int) -> str:
    """The traversal impl when nobody asked for one.

    ``pallas`` on a TPU with no mesh, where the kernel's VMEM working set
    at these widths fits (:func:`~repro.kernels.tree_predict.tree_kernel.plan`);
    ``xla`` elsewhere. XLA's TPU backend lowers the reference's gathers to
    slow serial code, which the kernel avoids; other backends gather well
    and run the kernel only in interpret mode. GSPMD cannot partition a
    Mosaic call, so under a mesh the choice is ``xla``.
    """
    if platform != "tpu" or mesh is not None:
        return "xla"
    return "pallas" if plan(1, 1, p, out, depth) else "xla"


@functools.partial(jax.jit, static_argnames=("depth", "impl"))
def _forest_predict(x, feat, thr_val, leaf, depth: int, impl: str):
    with jax.named_scope("tree_predict"):
        if impl == "xla":
            return forest_predict_ref(x, feat, thr_val, leaf, depth)
        return forest_predict_pallas(x, feat, thr_val, leaf, depth,
                                     interpret=(impl == "pallas_interpret"))


def forest_predict(x, feat, thr_val, leaf, depth: int,
                   impl: Optional[str] = None):
    """impl: 'xla' | 'pallas' | 'pallas_interpret' (None -> env ->
    :func:`default_impl` on the default backend)."""
    impl = resolve_impl(impl, env_var=ENV_VAR, default=default_impl(
        jax.default_backend(), x.shape[1], leaf.shape[-1], None, depth))
    return _forest_predict(x, feat, thr_val, leaf, depth, impl=impl)
