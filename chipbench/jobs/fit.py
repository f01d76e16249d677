"""Fit job: whole ``fit_artifacts`` calls on the single-device route.

Set-up builds the showers from the seed, compiles the fit step at the
cell's shapes and runs one ensemble through it (never a whole grid call).
The window makes whole calls: at least one, and another only while the
mean call time still ends inside ``--seconds``. ``fit_s_per_tree`` is the
calls' wall time over the trees they fitted.

The check draws ``check_ensembles`` of the window's ensembles from the
seed and holds each against the configuration's plain reference fit. The
control (``ctx.control``) is the program's own bf16 histogram path
(``hist_bf16``). On the chip that control does not separate from sound
runs (``held_out.json``), so the cell is not in BENCHMARK.json.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import data


def run(ctx) -> dict:
    from repro.core import interpolants as itp
    from repro.tabgen import fit_artifacts
    from repro.tabgen.fitting import prepare_classes, single_fit_program

    cfg, traffic = ctx.config, ctx.traffic
    s_data, s_fit, s_check = data.sub_seeds(ctx.seed, 3)
    X, y = data.showers(cfg["dataset"], cfg["showers_per_class"], s_data)
    fcfg = data.forest_config(cfg, hist_bf16=bool(ctx.control))
    per_batch = traffic["ensembles_per_batch"]

    # warm-up: the fit step at the cell's shapes, one ensemble through it,
    # with its arguments made as fit_artifacts makes them
    Xc, Wc = prepare_classes(X, y)[:2]
    step = single_fit_program(fcfg, warm=False)   # as fit_artifacts asks
    ts = np.asarray(itp.timesteps(fcfg.method, fcfg.n_t, fcfg.eps_diff,
                                  fcfg.t_schedule))
    jax.block_until_ready(step(
        jnp.asarray(Xc), jnp.asarray(Wc), jax.random.PRNGKey(s_fit),
        jnp.asarray([ts[0]] * per_batch, jnp.float32),
        jnp.asarray([0] * per_batch, jnp.int32),
        jnp.asarray([0] * per_batch, jnp.int32)))
    del Xc, Wc
    ctx.setup_done()

    calls = []                      # (seed, wall_s, feat, thr_val, leaf)
    with ctx.window():
        t0 = time.perf_counter()
        while True:
            seed = s_fit + len(calls)
            tc = time.perf_counter()
            art = fit_artifacts(X, y, fcfg, seed=seed,
                                ensembles_per_batch=per_batch)
            jax.block_until_ready(art.leaf)
            wall = time.perf_counter() - tc
            calls.append((seed, wall, np.asarray(art.feat),
                          np.asarray(art.thr_val), np.asarray(art.leaf)))
            elapsed = time.perf_counter() - t0
            if elapsed + elapsed / len(calls) > ctx.seconds:
                break
    peak = ctx.memory_peak_bytes()
    del art

    n_t, n_y = calls[0][2].shape[:2]
    trees = len(calls) * n_t * n_y * fcfg.n_trees
    wall = sum(c[1] for c in calls)
    ctx.log(phase="window", calls=len(calls), wall_s=[c[1] for c in calls],
            trees=trees)

    # the check: ensembles drawn from the seed among all the window fitted
    rng = np.random.default_rng(s_check)
    cells = [(c, ti, yi) for c in range(len(calls)) for ti in range(n_t)
             for yi in range(n_y)]
    picks = rng.choice(len(cells), size=min(traffic["check_ensembles"],
                                            len(cells)), replace=False)
    ts = np.linspace(0.0, 1.0, n_t, dtype=np.float32)
    worst = {"split_regret": 0.0, "leaf_gap": 0.0}
    t_ref = time.perf_counter()
    for i in picks:
        c, ti, yi = cells[i]
        seed, _, feat, thr, leaf = calls[c]
        got = ctx.reference.check_fit(
            X[y == yi], seed=seed, eid=ti * n_y + yi, t=float(ts[ti]),
            forest=cfg, trees={"feat": feat[ti, yi, 0],
                               "thr_val": thr[ti, yi, 0],
                               "leaf": leaf[ti, yi, 0]})
        ctx.log(phase="check", call=c, timestep=ti, cls=yi, **got)
        worst = {k: max(worst[k], got[k]) for k in worst}
    ctx.log(phase="reference", seconds=time.perf_counter() - t_ref)

    return {
        "e2e": {"fit_s_per_tree": wall / trees},
        "attempted": len(calls) * n_t * n_y, "failed": 0,
        "memory_peak_bytes": peak,
        "checks": worst,
        "facts": {"trees": trees, "wall_s": wall, "chips": len(ctx.devices),
                  "rows_per_ensemble": cfg["showers_per_class"]
                  * cfg["duplicate_k"],
                  "p": cfg["p"], "out": cfg["p"],
                  "depth": cfg["max_depth"], "bins": cfg["n_bins"],
                  "module": traffic["fit_module"]},
    }
