"""Imputation: REPAINT-style clamping of observed features along the
reverse solve (the companion capability of Jolicoeur-Martineau et al.).

Observed features are clamped to a fixed-noise bridge at every solver step;
the whole solve is then repeated ``refine_rounds`` times from annealed
restart times (re-noising the previous imputation) so the conditioning —
which only becomes informative at small t — propagates back through the
trajectory (RePaint-style refinement for a deterministic solver).

Forests come from the cached :class:`ForestArtifacts` device arrays
(``class_forest`` is a device slice), and ``predict_forest`` is imported
once at module scope — the seed code re-imported it and re-uploaded the
forests inside the per-class loop.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import interpolants as itp
from repro.forest.packed import PackedForest, predict_forest
from repro.kernels.dispatch import resolve_impl
from repro.kernels.tree_predict.ops import ENV_VAR as _PREDICT_ENV
from repro.tabgen.artifacts import ForestArtifacts, rescale, unscale
from repro.tabgen.sampling import predict_default


def impute(artifacts: ForestArtifacts, X_missing, y=None, *, seed: int = 0,
           refine_rounds: int = 3, impl: Optional[str] = None) -> np.ndarray:
    """Fill NaNs in ``X_missing``; observed cells are returned untouched.

    ``impl`` selects the tree-predict backend for every solver step of the
    clamped solve (argument > ``ForestConfig.predict_impl`` > env > the
    platform's default) — the imputation loop inherits the kernel exactly
    like the samplers do.
    """
    fcfg = artifacts.config
    impl = resolve_impl(impl, fcfg.predict_impl, env_var=_PREDICT_ENV,
                        default=predict_default(artifacts))
    X_missing = np.asarray(X_missing, np.float32)
    n, p = X_missing.shape
    if y is None:
        assert artifacts.n_y == 1, "labels required for conditional models"
        y_idx = np.zeros((n,), int)
    else:
        lut = {c: i for i, c in enumerate(np.asarray(artifacts.classes))}
        y_idx = np.asarray([lut[v] for v in np.asarray(y)])
    mins = np.asarray(artifacts.mins)
    maxs = np.asarray(artifacts.maxs)
    out = X_missing.copy()
    key = jax.random.PRNGKey(seed + 31)
    ts = np.asarray(itp.timesteps(fcfg.method, fcfg.n_t, fcfg.eps_diff,
                                  fcfg.t_schedule))
    for yi in range(artifacts.n_y):
        sel = np.where(y_idx == yi)[0]
        if len(sel) == 0:
            continue
        rows = X_missing[sel]
        mask = ~np.isnan(rows)                      # observed
        obs = rescale(np.nan_to_num(rows), mins[yi], maxs[yi])
        key, k_fix = jax.random.split(key)
        m = jnp.asarray(mask)
        obs_d = jnp.asarray(obs)
        # one fixed noise draw -> observed coords follow a single
        # consistent bridge path across all solver steps
        eps_fix = jax.random.normal(k_fix, (len(sel), p), jnp.float32)
        stacked = artifacts.class_forest(yi)

        x0_est = jnp.zeros((len(sel), p), jnp.float32)
        for r in range(max(1, refine_rounds)):
            # annealed restart: round 0 from pure noise at t=1; later
            # rounds re-noise the previous estimate from smaller t
            frac = 1.0 if r == 0 else float(ts[-1]) * (0.6 ** r)
            i_start = int(np.argmin(np.abs(ts - frac)))
            i_start = max(i_start, 1)
            key, kr = jax.random.split(key)
            eps_r = jax.random.normal(kr, (len(sel), p), jnp.float32)
            t0 = float(ts[i_start])
            if fcfg.method == "flow":
                x = t0 * eps_r + (1 - t0) * x0_est
            else:
                a0, s0 = itp.vp_alpha_sigma(jnp.float32(t0))
                x = a0 * x0_est + s0 * eps_r
            for i in range(i_start, 0, -1):
                t = float(ts[i])
                h_i = float(ts[i] - ts[i - 1])
                f = PackedForest(stacked.feat[i], stacked.thr_val[i],
                                 stacked.leaf[i], fcfg.multi_output)
                if fcfg.method == "flow":
                    bridge = t * eps_fix + (1 - t) * obs_d
                    x = jnp.where(m, bridge, x)
                    x = x - h_i * predict_forest(x, f, fcfg.max_depth,
                                                 impl=impl)
                else:
                    a, s_ = itp.vp_alpha_sigma(jnp.float32(t))
                    x = jnp.where(m, a * obs_d + s_ * eps_fix, x)
                    score = predict_forest(x, f, fcfg.max_depth,
                                           impl=impl)
                    t_next = float(ts[i - 1])
                    a2, s2 = itp.vp_alpha_sigma(jnp.float32(t_next))
                    eps_hat = -s_ * score
                    x0_hat = jnp.clip((x - s_ * eps_hat) / a, -1.5, 1.5)
                    eps_hat = (x - a * x0_hat) / s_
                    x = a2 * x0_hat + s2 * eps_hat
            x0_est = jnp.where(m, obs_d, x)
        vals = unscale(np.asarray(x0_est), mins[yi], maxs[yi])
        filled = np.where(mask, rows, vals)
        out[sel] = filled
    return out
