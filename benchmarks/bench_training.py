"""Training throughput vs device count — the paper §3.3 scaling claim,
measured instead of asserted — plus the PR-3 pipelined-vs-serial trainer
comparison.

Each device count runs in its own subprocess (``XLA_FLAGS=
--xla_force_host_platform_device_count=D`` must precede jax init) and:

* fits the same dataset through ``fit_artifacts`` cold (the single-device
  trainer at D=1, the shard_map trainer on the ``auto_forest_mesh``
  otherwise) — the trajectory record, methodology unchanged since PR 2
  (``includes_compile: true``);
* then runs the pipelined-vs-serial comparison on a grid-heavy demo
  workload (many ensemble batches streaming checkpoints — the paper's
  n_t=50 regime scaled to CI): warm program, explicit mesh, ABBA-interleaved
  reps with min-of-reps walls (the box the CI runs on drifts by 2x, so
  paired mins are the only stable statistic), reporting serial and
  pipelined rows/sec, the speedup, and the pipeline's overlap accounting
  (``writer_busy_s`` = host-side gather+checkpoint work moved off the
  dispatch thread, ``overlap_efficiency`` = the fraction of it actually
  hidden from wall-clock).

Reports rows/sec, ensemble-rows/sec (rows x duplicate_k x ensembles /
wall), the compiled per-device memory estimate of the sharded fit program
("peak HBM" on a real accelerator; host bytes on the virtual mesh), and
subprocess peak RSS.

CSV: name,us_per_call,derived. With ``json_path`` set, also writes
``BENCH_training.json`` with one record per device count.

Caveat: on the CPU host the virtual devices share the same cores, so
rows/sec is NOT expected to scale with D here — and on a 2-core container
the pipeline's overlap gain is bounded by spare-core capacity (wall-clock
tracks total CPU work), so the speedup recorded here is a floor; real
scaling and overlap numbers come from running the same section on a TPU
slice or a multi-core host.
"""
from __future__ import annotations

import json
import os

from benchmarks.common import emit, run_measured

_SNIPPET = r"""
import time, json, shutil, tempfile
import jax
import numpy as np

from repro.config import ForestConfig
from repro.data.tabular import synthetic_resource_dataset
from repro.tabgen import PipelineConfig, fit_artifacts
from repro.tabgen import fitting
from repro.launch.mesh import auto_forest_mesh, make_mesh

n, p, n_y = {n}, {p}, {n_y}
X, y = synthetic_resource_dataset(n, p, n_y, seed=0)
fcfg = ForestConfig(n_t={n_t}, duplicate_k={dup_k}, n_trees={n_trees},
                    max_depth=4, n_bins=32, reg_lambda=1.0)
mesh = auto_forest_mesh()
t0 = time.time()
art = fit_artifacts(X, y, fcfg, seed=0, mesh=mesh)
wall = time.time() - t0
n_ens = art.n_t * art.n_y

hbm = None
if mesh is not None:
    # per-device memory of the compiled shard_map fit program: the
    # fits-in-HBM number for this (rows, grid) slice
    from repro.forest.distributed import input_specs_forest, make_distributed_fit
    d_data = dict(zip(mesh.axis_names, mesh.devices.shape))["data"]
    n_pad = -(-n // d_data) * d_data
    bs = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    compiled = make_distributed_fit(mesh, fcfg).lower(
        *input_specs_forest(fcfg, n_pad, p, max(bs, min(n_ens, 8)))).compile()
    mem = compiled.memory_analysis()
    hbm = getattr(mem, "temp_size_in_bytes", None)

# ---- pipelined vs serial (PR 3): grid-heavy demo workload, warm program,
# checkpoint streaming on, ABBA interleaving with min-of-reps walls
pn, pp, pn_y = {pipe_n}, {pipe_p}, 2
pX, py = synthetic_resource_dataset(pn, pp, pn_y, seed=0)
pcfg = ForestConfig(n_t={pipe_n_t}, duplicate_k={pipe_dup_k},
                    n_trees={pipe_n_trees}, max_depth=3, n_bins=16,
                    reg_lambda=1.0)
pipe_mesh = mesh if mesh is not None else make_mesh((1, 1))
bpb = dict(zip(pipe_mesh.axis_names, pipe_mesh.devices.shape))["model"]
PIPE = PipelineConfig(prefetch_depth={prefetch_depth})

def timed_fit(pipeline):
    ck = tempfile.mkdtemp()
    t0 = time.perf_counter()
    fit_artifacts(pX, py, pcfg, seed=0, mesh=pipe_mesh, checkpoint_dir=ck,
                  ensembles_per_batch=bpb, pipeline=pipeline)
    w = time.perf_counter() - t0
    shutil.rmtree(ck)
    return w

timed_fit(None)          # warm the program + 9p caches once for both arms
serial_walls, pipe_walls, pipe_stats = [], [], []
def timed_pipe():
    pipe_walls.append(timed_fit(PIPE))
    pipe_stats.append(dict(fitting.LAST_PIPELINE_STATS))
for _ in range({reps}):  # ABBA: serial,pipe,pipe,serial
    serial_walls.append(timed_fit(None))
    timed_pipe()
    timed_pipe()
    serial_walls.append(timed_fit(None))
s_wall, p_wall = min(serial_walls), min(pipe_walls)
# busy times must come from the same fit as the min pipelined wall, or the
# hidden/busy ratio mixes statistics from different reps
stats = pipe_stats[pipe_walls.index(p_wall)]
pipe_ens = pcfg.n_t * pn_y
# NB both arms share the same input build (the precomputed key table), so
# the speedup isolates stage overlap + loop structure; overlap_efficiency
# is still an approximation (min walls vs one rep's busy), clamped [0, 1]
hidden = max(0.0, s_wall - p_wall)
busy = stats.get("writer_busy_s", 0.0) + stats.get("prefetch_busy_s", 0.0)

result = {{
    "devices": len(jax.devices()),
    "mesh": (dict(zip(mesh.axis_names, mesh.devices.shape))
             if mesh is not None else None),
    "fit_wall_s": wall,
    "includes_compile": True,
    "rows_per_sec": n * n_ens / wall,
    "ensemble_rows_per_sec": n * fcfg.duplicate_k * n_ens / wall,
    "per_device_temp_bytes": hbm,
    "pipeline_comparison": {{
        "workload": {{"n": pn, "p": pp, "n_y": pn_y, "n_t": pcfg.n_t,
                      "duplicate_k": pcfg.duplicate_k,
                      "n_trees": pcfg.n_trees,
                      "ensembles_per_batch": bpb,
                      "n_batches": stats.get("n_batches"),
                      "checkpoint": True}},
        "includes_compile": False,
        "reps_per_arm": len(serial_walls),
        "serial_wall_s": s_wall,
        "pipelined_wall_s": p_wall,
        "serial_rows_per_sec": pn * pipe_ens / s_wall,
        "pipelined_rows_per_sec": pn * pipe_ens / p_wall,
        "pipelined_speedup": s_wall / p_wall,
        "writer_busy_s": stats.get("writer_busy_s"),
        "prefetch_busy_s": stats.get("prefetch_busy_s"),
        "prefetch_depth": stats.get("prefetch_depth"),
        "overlap_efficiency": min(1.0, hidden / busy) if busy > 0 else None,
    }},
}}
"""


def main(quick: bool = True, json_path: str = None) -> None:
    n, p, n_y = (2048, 8, 2) if quick else (65536, 32, 4)
    n_t, dup_k, n_trees = (4, 10, 10) if quick else (10, 20, 40)
    # pipeline comparison: a grid-heavy (paper n_t=50-style) slice kept
    # CI-sized — many small ensemble batches so the per-batch host work
    # (input build, gather, checkpoint write) is a visible fraction
    pipe = (dict(pipe_n=256, pipe_p=8, pipe_n_t=16, pipe_dup_k=3,
                 pipe_n_trees=3, prefetch_depth=2, reps=2) if quick else
            dict(pipe_n=2048, pipe_p=16, pipe_n_t=50, pipe_dup_k=10,
                 pipe_n_trees=10, prefetch_depth=2, reps=3))
    device_counts = (1, 8) if quick else (1, 2, 4, 8)
    records = []
    for d in device_counts:
        snippet = _SNIPPET.format(n=n, p=p, n_y=n_y, n_t=n_t,
                                  dup_k=dup_k, n_trees=n_trees, **pipe)
        # XLA_FLAGS must be in the env before the subprocess inits jax
        r = run_measured(snippet, timeout=1800, env_extra={
            "XLA_FLAGS": f"--xla_force_host_platform_device_count={d}"})
        r.setdefault("config", {"n": n, "p": p, "n_y": n_y, "n_t": n_t,
                                "duplicate_k": dup_k, "n_trees": n_trees})
        pc = r.get("pipeline_comparison", {})
        emit(f"training/devices={d}",
             f"{r['fit_wall_s'] * 1e6:.0f}",
             f"rows_per_sec={r['rows_per_sec']:.0f}|"
             f"ensemble_rows_per_sec={r['ensemble_rows_per_sec']:.0f}|"
             f"peak_rss_mb={r['peak_rss_bytes'] / 1e6:.0f}")
        if pc:
            emit(f"training/pipeline/devices={d}",
                 f"{pc['pipelined_wall_s'] * 1e6:.0f}",
                 f"serial_wall_s={pc['serial_wall_s']:.3f}|"
                 f"pipelined_speedup={pc['pipelined_speedup']:.3f}|"
                 f"overlap_efficiency={pc['overlap_efficiency']}")
        records.append(r)
    if json_path:
        d = os.path.dirname(json_path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(json_path, "w") as f:
            json.dump({"bench": "training", "records": records}, f, indent=1)
        emit("training/json", "-", json_path)


if __name__ == "__main__":
    main(json_path="BENCH_training.json")
