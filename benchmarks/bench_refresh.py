"""Refresh-loop benchmark: warm-start extension vs full refit.

The freshness loop's economics in one number: when new rows arrive and a
served model needs ``K`` more boosting rounds, is
``extend_artifacts(base, X, extra_trees=K)`` (replay R base rounds, train
K) actually cheaper than refitting all ``R + K`` rounds from scratch?

Both arms produce an ``R + K``-tree model on the identical dataset through
the identical sharded trainer (a 1x1 mesh, so the lru-cached shard_map
program is reused across reps — compile excluded), ABBA-interleaved with
min-of-reps walls, the house methodology on noisy boxes. On the same data
the two results are bit-identical (asserted once per run, the tentpole
acceptance riding along in the bench), so the comparison is pure wall.

Gated metric: ``warm_extend_rows_per_sec``. The refit arm exists to be
beaten — ``full_refit_*`` is exempt in scripts/check_bench.py (reference
arm), and ``warm_vs_refit_speedup`` is recorded for the trajectory. The
replay cost grows with R (one tree-predict pass per base round), so the
speedup is below the ideal ``(R + K) / K``; the gap is the replay tax.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from benchmarks.common import emit
from repro.config import ForestConfig
from repro.data.tabular import synthetic_resource_dataset
from repro.launch.mesh import make_mesh
from repro.tabgen import extend_artifacts, fit_artifacts

#: static workload identity — check_bench matches records on ``config``
QUICK = dict(n=1024, p=8, n_y=2, n_t=2, dup_k=5, base_trees=12,
             extra_trees=3, reps=2)
FULL = dict(n=16384, p=16, n_y=2, n_t=8, dup_k=10, base_trees=40,
            extra_trees=10, reps=5)

_FIELDS = ("feat", "thr_val", "leaf", "best_round", "val_curve")


def main(quick: bool = True, json_path: str = None) -> None:
    cfg = QUICK if quick else FULL
    X, y = synthetic_resource_dataset(cfg["n"], cfg["p"], cfg["n_y"], seed=0)
    mk = lambda r: ForestConfig(n_t=cfg["n_t"], duplicate_k=cfg["dup_k"],  # noqa: E731
                                n_trees=r, max_depth=4, n_bins=32,
                                reg_lambda=1.0)
    total = cfg["base_trees"] + cfg["extra_trees"]
    mesh = make_mesh((1, 1))
    base = fit_artifacts(X, y, mk(cfg["base_trees"]), seed=0, mesh=mesh)

    def warm():
        return extend_artifacts(base, X, y,
                                extra_trees=cfg["extra_trees"], seed=0,
                                mesh=mesh)

    def refit():
        return fit_artifacts(X, y, mk(total), seed=0, mesh=mesh)

    # acceptance riding along: on the same data the arms are bit-identical
    ext, cold = warm(), refit()                 # also compiles both programs
    for f in _FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(ext, f)),
                                      np.asarray(getattr(cold, f)),
                                      err_msg=f)

    walls = {"warm": [], "refit": []}
    arms = {"warm": warm, "refit": refit}
    for _ in range(cfg["reps"]):                # ABBA
        for arm in ("warm", "refit", "refit", "warm"):
            t0 = time.perf_counter()
            arms[arm]()
            walls[arm].append(time.perf_counter() - t0)
    w_wall, r_wall = min(walls["warm"]), min(walls["refit"])
    n_ens = cfg["n_t"] * cfg["n_y"]
    record = {
        "config": {"section": "refresh", **cfg},
        "devices": 1,
        "mesh": {"data": 1, "model": 1},
        "refresh": {
            "includes_compile": False,
            "reps_per_arm": 2 * cfg["reps"],
            "bit_identical_to_refit": True,
            "warm_extend_wall_s": w_wall,
            "warm_extend_rows_per_sec": cfg["n"] * n_ens / w_wall,
            # reference arm (check_bench-exempt): exists to be beaten
            "full_refit_wall_s": r_wall,
            "full_refit_rows_per_sec": cfg["n"] * n_ens / r_wall,
            "warm_vs_refit_speedup": r_wall / w_wall,
            "ideal_speedup": total / cfg["extra_trees"],
        },
    }
    emit("refresh/warm_extend", f"{w_wall * 1e6:.0f}",
         f"rows_per_sec={record['refresh']['warm_extend_rows_per_sec']:.0f}|"
         f"speedup_vs_refit={r_wall / w_wall:.2f}x|"
         f"ideal={total / cfg['extra_trees']:.1f}x")
    emit("refresh/full_refit_reference", f"{r_wall * 1e6:.0f}",
         f"rows_per_sec={record['refresh']['full_refit_rows_per_sec']:.0f}")

    if json_path:
        d = os.path.dirname(json_path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(json_path, "w") as f:
            json.dump({"bench": "refresh", "records": [record]}, f, indent=1)
