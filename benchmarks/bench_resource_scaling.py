"""Paper Figure 1 / Figure 2 / Figure 4 (top+middle): training time and peak
memory, Original-style implementation vs ours (SO, MO, +ES), scaling in n.

Each configuration runs in a fresh subprocess so peak RSS is per-config.
CSV: name,us_per_call,derived  (derived = peak RSS in MiB).

:func:`main_store` (the ``store_scaling`` section) is the paper §3.3
out-of-core record: peak host RSS + fit throughput vs dataset size for the
in-memory trainer vs a :class:`repro.data.store.DatasetStore`-backed fit,
emitted as ``BENCH_resource_scaling.json`` and gated by
``scripts/check_bench.py``. The throughput side is ABBA-ordered min-of-reps
(both arms run the same 1x1-mesh shard_map program warm, so the ratio
isolates the data path); the ``in_memory_padded`` reference arm is the
single-device padded-block route (per-call jit, cold) and is exempt from
the gate like the other reference arms. All arms use multi-output trees
(the paper's recommended mode; SO would train p per-feature sub-forests
per ensemble and blow the CI budget at these row counts).
"""
from __future__ import annotations

import json
import os

from benchmarks.common import emit, run_measured

_FIT_SNIPPET = """
import numpy as np
from repro.config import ForestConfig
from repro.data.tabular import synthetic_resource_dataset
{import_line}
X, y = synthetic_resource_dataset({n}, {p}, {n_y}, seed=0)
fcfg = ForestConfig(n_t={n_t}, duplicate_k={K}, n_trees={T}, max_depth=4,
                    n_bins=32, reg_lambda=1.0, multi_output={mo},
                    early_stop_rounds={es})
m = {ctor}(fcfg).fit(X, y, seed=0)
result = {{}}
"""


def variants():
    ours = ("from repro.tabgen import TabularGenerator",
            "TabularGenerator")
    naive = ("from repro.core.naive import NaiveForestGenerativeModel",
             "NaiveForestGenerativeModel")
    return [
        ("original", naive, False, 0),
        ("ours-SO", ours, False, 0),
        ("ours-MO", ours, True, 0),
        ("ours-SO-ES", ours, False, 5),
        ("ours-MO-ES", ours, True, 5),
    ]


def main(sizes=(200, 500, 1000), p=8, n_y=2, n_t=3, K=10, T=10) -> None:
    for n in sizes:
        for name, (imp, ctor), mo, es in variants():
            if name == "original" and n > 500:
                # the pathological baseline becomes impractical quickly —
                # the paper's red-cross regime; don't burn the CI budget
                emit(f"resource_scaling/{name}/n={n}", "skipped(x)",
                     "skipped(x)")
                continue
            snippet = _FIT_SNIPPET.format(import_line=imp, ctor=ctor, n=n,
                                          p=p, n_y=n_y, n_t=n_t, K=K, T=T,
                                          mo=mo, es=es)
            res = run_measured(snippet, timeout=1200)
            us = res["wall_s"] * 1e6
            mib = res["peak_rss_bytes"] / 2 ** 20
            emit(f"resource_scaling/{name}/n={n}", f"{us:.0f}", f"{mib:.1f}")


# ---------------------------------------------------------------------------
# out-of-core store vs in-memory fit (ISSUE 5 / paper §3.3 scaling record)
# ---------------------------------------------------------------------------

_OOC_SNIPPET = """
import os, tempfile, time
import numpy as np
import jax
from repro.config import ForestConfig
from repro.data.tabular import synthetic_resource_batches
from repro.launch.mesh import make_mesh
from repro.tabgen import fit_artifacts

n, p, n_y, arm = {n}, {p}, {n_y}, {arm!r}
fcfg = ForestConfig(n_t={n_t}, duplicate_k={K}, n_trees={T}, max_depth=3,
                    n_bins=32, reg_lambda=1.0, multi_output=True)
result = {{}}
if arm == "store":
    from repro.data.store import ingest
    t0 = time.perf_counter()
    data = ingest(synthetic_resource_batches(n, p, n_y,
                                             batch_rows={batch_rows},
                                             seed=0),
                  os.path.join(tempfile.mkdtemp(), "store"),
                  shard_rows={shard_rows})
    result["ingest_wall_s"] = round(time.perf_counter() - t0, 3)
    labels, mesh = None, None          # auto-routes to the 1x1 sharded fit
else:
    parts = list(synthetic_resource_batches(n, p, n_y,
                                            batch_rows={batch_rows},
                                            seed=0))
    data = np.concatenate([x for x, _ in parts])
    labels = np.concatenate([y for _, y in parts])
    del parts
    # same 1x1 shard_map program as the store arm (so min-of-reps isolates
    # the data path), except the padded reference arm: the default
    # single-device route with dense [n_y, n_max, p] class blocks
    mesh = (None if arm == "in_memory_padded"
            else make_mesh((1, 1)))
walls = []
for _ in range({reps}):
    t0 = time.perf_counter()
    art = fit_artifacts(data, labels, fcfg, seed=0, mesh=mesh)
    jax.block_until_ready(art.leaf)
    walls.append(time.perf_counter() - t0)
result["fit_wall_s"] = min(walls)
result["reps"] = len(walls)
result["n_ens"] = fcfg.n_t * art.n_y
"""


def _ooc_run(arm: str, n: int, p: int, n_y: int, fit_cfg: dict,
             reps: int) -> dict:
    snippet = _OOC_SNIPPET.format(arm=arm, n=n, p=p, n_y=n_y, reps=reps,
                                  **fit_cfg)
    return run_measured(snippet, timeout=2400)


def main_store(quick: bool = True, json_path: str | None = None,
               n_y: int = 2, sizes=None) -> None:
    """Store-backed vs in-memory fit: throughput (ABBA min-of-reps) + peak
    host RSS per dataset size. The largest size is >= 10x any in-memory
    bench config (training bench tops out at n=2048), demonstrating the
    out-of-core route on a fixed-RAM box."""
    p = 32
    fit_cfg = dict(n_t=2, K=2, T=3, mo=True, batch_rows=8192,
                   shard_rows=16384)
    # full sizes are bounded by hosted-runner RAM: the quick trajectory
    # measures ~12 KiB RSS/row for the gated arms (XLA temps scale with n),
    # so 524288 rows ~ 6 GiB — comfortably inside a 16 GB nightly runner,
    # while anything million-row would OOM its child and fail the run
    sizes = sizes or ((16384, 131072) if quick else (262144, 524288))
    records = []
    for n in sizes:
        runs: dict = {"in_memory": [], "store": []}
        for arm in ("in_memory", "store", "store", "in_memory"):   # ABBA
            runs[arm].append(_ooc_run(arm, n, p, n_y, fit_cfg, reps=2))
        for arm, res_list in runs.items():
            wall = min(r["fit_wall_s"] for r in res_list)
            n_ens = res_list[0]["n_ens"]
            rss = max(r["peak_rss_bytes"] for r in res_list)
            rec = {
                "config": {"workload": "store_scaling", "arm": arm,
                           "n": n, "p": p, "n_y": n_y, **{
                               k: fit_cfg[k]
                               for k in ("n_t", "K", "T", "mo")}},
                "devices": 1,
                "trainer": "sharded_1x1",
                "fit_wall_s": wall,
                "includes_compile": False,   # min over 2 reps x 2 runs
                "rows_per_sec": n * n_ens / wall,
                "peak_rss_bytes": rss,
                "dataset_bytes": n * p * 4,
                "abba_runs": len(res_list),
                "reps_per_run": 2,
            }
            if arm == "store":
                rec["ingest_wall_s"] = min(r["ingest_wall_s"]
                                           for r in res_list)
            records.append(rec)
            emit(f"store_scaling/{arm}/n={n}",
                 f"{wall * 1e6:.0f}", f"{rss / 2**20:.1f}")
    # reference arm: the default single-device padded route (per-call jit
    # -> cold timing; exempt from the gate). Its padded blocks + full sorts
    # cost ~2x the sharded arms' RSS, so in the full lane it runs at the
    # *smaller* size to stay inside the runner — the RSS contrast is the
    # point, not the absolute n
    n_ref = sizes[-1] if quick else sizes[0]
    res = _ooc_run("in_memory_padded", n_ref, p, n_y, fit_cfg, reps=1)
    records.append({
        "config": {"workload": "store_scaling", "arm": "in_memory_padded",
                   "n": n_ref, "p": p, "n_y": n_y,
                   **{k: fit_cfg[k] for k in ("n_t", "K", "T", "mo")}},
        "devices": 1,
        "trainer": "single_padded",
        "fit_wall_s": res["fit_wall_s"],
        "includes_compile": True,
        "padded_coldstart_rows_per_sec": n_ref * res["n_ens"]
        / res["fit_wall_s"],
        "peak_rss_bytes": res["peak_rss_bytes"],
        "dataset_bytes": n_ref * p * 4,
    })
    emit(f"store_scaling/in_memory_padded/n={n_ref}",
         f"{res['fit_wall_s'] * 1e6:.0f}",
         f"{res['peak_rss_bytes'] / 2**20:.1f}")
    if json_path:
        payload = {
            "bench": "resource_scaling",
            "note": ("store arm: ingest + DatasetStore-backed fit (rows "
                     "gathered from disk shards; class stats/sketch from "
                     "the manifest). Host RSS includes the device-resident "
                     "row shards on this CPU-only box; on TPU those live "
                     "in HBM and host staging is O(shard + batch)."),
            "records": records,
        }
        os.makedirs(os.path.dirname(json_path) or ".", exist_ok=True)
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=1)


if __name__ == "__main__":
    main()
    main_store()
