"""Generate job: a closed loop of whole ``sample`` calls (bulk generation).

Set-up draws the random Table-9 forest grid on the device from the seed,
wraps it in ``ForestArtifacts`` as a fitted model would be, and runs one
call at the window's size (the compile, from the cache after the first
run). The window makes calls of ``rows_per_call`` rows back to back, with
a fresh seed each, while the mean call still ends inside ``--seconds``.
``gen_rows_per_s`` is rows over the calls' wall time. A traced run
records the calls that end in the first ``trace_seconds``.

The check draws ``check_rows`` of the rows the window's calls returned
from the seed and solves them again with the configuration's plain reference, through
every step and tree. The control puts the reference, in bfloat16, in the
program's place.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from chipbench import data


def build_artifacts(cfg: dict, seed: int):
    """The program's model object over a random forest grid of the
    configuration's size; ``counts`` equal over the classes held."""
    from repro.tabgen import ForestArtifacts
    n_t, n_y, T = cfg["n_t"], cfg["classes_held"], cfg["n_trees"]
    feat, thr, leaf, mins, maxs = data.random_forest(
        seed, n_t=n_t, n_y=n_y, n_trees=T, depth=cfg["max_depth"],
        p=cfg["p"])
    zeros = np.zeros((n_t, n_y, 1), np.int32)
    art = ForestArtifacts(
        feat=feat, thr_val=thr, leaf=leaf, best_round=zeros + T - 1,
        rounds_run=zeros + T, val_curve=np.zeros((n_t, n_y, 1, T), np.float32),
        mins=mins, maxs=maxs, classes=np.arange(n_y),
        counts=np.full((n_y,), cfg["showers_per_class"]),
        config=data.forest_config(cfg))
    return art


def compare(ctx, art, got_rows, labels, seeds, cls, idx):
    """Rows the program returned against the reference solve.

    Row ``j`` of ``got_rows`` (data space) and ``labels`` came from a
    ``sample(seed=seeds[j])`` call, where it is class ``cls[j]``'s row
    ``idx[j]`` (from the reference's ``row_origin``). Returns the checks:
    the share (%) of rows whose widest gap in model space exceeds
    ``row_tol``, or whose label is not their class's.
    """
    ref = ctx.reference
    mins, maxs = np.asarray(art.mins), np.asarray(art.maxs)
    want = ref.solve_rows(art.feat, art.thr_val, art.leaf, seeds=seeds,
                          cls=cls, idx=idx, depth=art.config.max_depth)
    if ctx.control:   # the control: the reference in bf16 in place
        got = ref.solve_rows(art.feat, art.thr_val, art.leaf, seeds=seeds,
                             cls=cls, idx=idx, depth=art.config.max_depth,
                             dtype=jax.numpy.bfloat16)
        labels_ok = np.ones(len(cls), bool)
    elif np.shape(got_rows) != (len(cls), art.p):
        got = np.full((len(cls), art.p), np.inf)
        labels_ok = np.zeros(len(cls), bool)
    else:
        got = ref.rescale(np.asarray(got_rows, np.float64), mins[cls],
                          maxs[cls])
        labels_ok = np.asarray(labels) == np.asarray(art.classes)[cls]
    gap = np.max(np.abs(got - want), axis=1)
    gap = np.where(np.isfinite(gap), gap, np.inf)
    off = int(np.sum((gap > ctx.traffic["row_tol"]) | ~labels_ok))
    ctx.log(phase="check", rows=len(cls), rows_off=off,
            widest_gap=float(np.max(gap, initial=0.0)),
            smallest_gap=float(np.min(gap, initial=np.inf)),
            median_gap=float(np.median(gap)) if len(gap) else None)
    return {"rows_off_pct": 100.0 * off / max(len(cls), 1)}


def run(ctx) -> dict:
    from repro.tabgen import sample

    cfg, traffic = ctx.config, ctx.traffic
    s_forest, s_calls, s_check = data.sub_seeds(ctx.seed, 3)
    art = build_artifacts(cfg, s_forest)
    n = traffic["rows_per_call"]
    jax.block_until_ready(sample(art, n, seed=s_calls)[0])     # warm-up
    ctx.setup_done()

    calls, call_s, traced = [], [], None
    trace_s = traffic.get("trace_seconds", ctx.seconds)
    with ctx.window():
        t0 = time.perf_counter()
        while True:
            seed = s_calls + 1 + len(calls)
            X, y = sample(art, n, seed=seed)
            calls.append((seed, n, X, y))
            call_s.append(time.perf_counter() - t0 - sum(call_s))
            elapsed = time.perf_counter() - t0
            if traced is None and elapsed >= trace_s:
                ctx.end_trace()      # the trace holds whole calls only
                traced = len(calls)
            if elapsed + elapsed / len(calls) > ctx.seconds:
                break
        wall = time.perf_counter() - t0
    peak = ctx.memory_peak_bytes()
    ctx.log(phase="window", calls=len(calls), wall_s=wall, call_s=call_s,
            memory=ctx.devices[0].memory_stats())

    rng = np.random.default_rng(s_check)
    picks = rng.choice(n * len(calls), replace=False,
                       size=min(traffic["check_rows"], n * len(calls)))
    t_ref = time.perf_counter()
    origin = [ctx.reference.row_origin(np.asarray(art.counts), n, c[0])
              for c in calls]
    c_of, r_of = picks // n, picks % n
    checks = compare(
        ctx, art, np.stack([calls[c][2][r] for c, r in zip(c_of, r_of)]),
        np.asarray([calls[c][3][r] for c, r in zip(c_of, r_of)]),
        np.asarray([calls[c][0] for c in c_of]),
        np.asarray([origin[c][0][r] for c, r in zip(c_of, r_of)]),
        np.asarray([origin[c][1][r] for c, r in zip(c_of, r_of)]))
    ctx.log(phase="reference", seconds=time.perf_counter() - t_ref)

    rows_done = n * len(calls)
    return {
        "e2e": {"gen_rows_per_s": rows_done / wall},
        "attempted": len(calls), "failed": 0,
        "memory_peak_bytes": peak,
        "checks": checks,
        "facts": {"calls": len(calls), "rows_computed": rows_done,
                  "calls_traced": traced or len(calls),
                  "wall_s": wall, "call_s": call_s,
                  "chips": len(ctx.devices),
                  "steps": cfg["n_t"] - 1, "trees": cfg["n_trees"],
                  "depth": cfg["max_depth"], "p": cfg["p"],
                  "classes": cfg["classes_held"],
                  "module": traffic["solve_module"]},
    }
