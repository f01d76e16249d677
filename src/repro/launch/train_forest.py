"""Forest training driver: the CLI mirror of ``serve_forest``.

Fits the (timestep, class) ensemble grid — on one device or across a mesh
(`--mesh`), with streaming checkpoints (`--checkpoint-dir` / `--resume`) —
and saves portable :class:`ForestArtifacts` that ``serve_forest`` can load.

CPU demo on a virtual 8-device mesh (rows sharded 4-way, grid 2-way):

  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python -m repro.launch.train_forest --demo --mesh 4x2 --out model

Training real data (X [n, p] float, optional y [n] labels, in an .npz):

  PYTHONPATH=src python -m repro.launch.train_forest \
      --data table.npz --mesh auto --checkpoint-dir ckpt --resume --out model

Out-of-core training from an ingested DatasetStore (``repro.launch.ingest``)
— row shards stream from disk, class stats/scalers come precomputed from
the store manifest, and no host copy of the dataset is ever materialised:

  PYTHONPATH=src python -m repro.launch.train_forest \
      --data-dir data/synth1m --mesh auto --checkpoint-dir ckpt --out model

Environment knobs: ``REPRO_HIST_IMPL=pallas`` selects the Pallas histogram
kernel on TPU (default ``xla``); ``--int8-codes`` stores bin codes at int8
(4x HBM reduction at n_bins ≤ 127).

The distributed fit loop runs double-buffered by default (prefetch thread
for input build, writer thread for gather + checkpoint streaming — see
``repro.tabgen.PipelineConfig``): tune with ``--prefetch-depth``, force
synchronous writes with ``--sync-checkpoint``, or fall back to the serial
PR-2 loop with ``--serial`` (bit-identical artifacts either way).
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.launch.compile_cache import enable_compile_cache


def parse_mesh(spec: str):
    """``auto`` | ``none`` | ``DxM`` (e.g. ``4x2`` — data x model)."""
    from repro.launch.mesh import auto_forest_mesh, make_mesh

    if spec == "none":
        return None
    if spec == "auto":
        return auto_forest_mesh()
    dims = tuple(int(d) for d in spec.split("x"))
    if len(dims) != 2:
        raise ValueError(f"--mesh {spec!r}: expected 'auto', 'none' or DxM")
    return make_mesh(dims)


def _demo_data(n: int, p: int, n_y: int, seed: int):
    from repro.data.tabular import synthetic_resource_dataset
    return synthetic_resource_dataset(n, p, n_y, seed=seed)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--data", default=None,
                    help=".npz with X [n, p] (and optionally y [n])")
    ap.add_argument("--data-dir", default=None,
                    help="DatasetStore directory from repro.launch.ingest — "
                         "out-of-core fit: row shards stream from disk, "
                         "stats come precomputed from the store manifest "
                         "(overrides --data/--demo)")
    ap.add_argument("--demo", action="store_true",
                    help="train on a synthetic dataset instead of --data")
    ap.add_argument("--demo-rows", type=int, default=2048)
    ap.add_argument("--demo-cols", type=int, default=8)
    ap.add_argument("--demo-classes", type=int, default=2)
    ap.add_argument("--mesh", default="auto",
                    help="'auto' (all devices), 'none' (single device), or "
                         "DxM e.g. 4x2")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--ensembles-per-batch", type=int, default=0)
    # pipeline knobs (distributed trainer only; see tabgen.PipelineConfig)
    ap.add_argument("--serial", action="store_true",
                    help="disable the double-buffered pipeline: serial "
                         "per-batch build -> dispatch -> gather -> write")
    ap.add_argument("--prefetch-depth", type=int, default=2,
                    help="bounded-queue depth between the input-build, "
                         "dispatch, and writer stages (1 = classic double "
                         "buffering)")
    ap.add_argument("--sync-checkpoint", action="store_true",
                    help="gather + write batch_*.npz on the dispatch "
                         "thread instead of the async writer thread")
    ap.add_argument("--out", default=None,
                    help="base path for the saved .npz/.json artifact pair")
    ap.add_argument("--metrics-dump", default=None, metavar="PATH",
                    help="after the fit, write the process metrics "
                         "registry as Prometheus text ('-' for stdout); "
                         "see docs/observability.md")
    ap.add_argument("--seed", type=int, default=0)
    # ForestConfig knobs (paper Table 9 names)
    ap.add_argument("--method", default="flow",
                    choices=("flow", "diffusion"))
    ap.add_argument("--n-t", type=int, default=10)
    ap.add_argument("--duplicate-k", type=int, default=20)
    ap.add_argument("--n-trees", type=int, default=40)
    ap.add_argument("--max-depth", type=int, default=5)
    ap.add_argument("--n-bins", type=int, default=64)
    ap.add_argument("--learning-rate", type=float, default=0.3)
    ap.add_argument("--reg-lambda", type=float, default=1.0)
    ap.add_argument("--sigma", type=float, default=0.0)
    ap.add_argument("--multi-output", action="store_true")
    ap.add_argument("--early-stop-rounds", type=int, default=0)
    ap.add_argument("--int8-codes", action="store_true")
    args = ap.parse_args(argv)
    enable_compile_cache()

    import jax

    from repro.config import ForestConfig
    from repro.tabgen import PipelineConfig, fit_artifacts

    if args.data_dir:
        from repro.data.store import DatasetStore
        X, y = DatasetStore(args.data_dir), None
        print(f"store {args.data_dir}: {X.n_rows} rows x {X.p} cols in "
              f"{X.n_shards} shards ({X.nbytes / 2**20:.1f} MiB on disk, "
              "streamed — not resident)")
    elif args.demo or args.data is None:
        X, y = _demo_data(args.demo_rows, args.demo_cols, args.demo_classes,
                          args.seed)
        print(f"demo dataset: X {X.shape}, {args.demo_classes} classes")
    else:
        with np.load(args.data) as d:
            X = d["X"]
            y = d["y"] if "y" in d.files else None
        print(f"loaded {args.data}: X {X.shape}"
              + (f", y {y.shape}" if y is not None else ", unlabeled"))

    fcfg = ForestConfig(
        method=args.method, n_t=args.n_t, duplicate_k=args.duplicate_k,
        n_trees=args.n_trees, max_depth=args.max_depth, n_bins=args.n_bins,
        learning_rate=args.learning_rate, reg_lambda=args.reg_lambda,
        sigma=args.sigma, multi_output=args.multi_output,
        early_stop_rounds=args.early_stop_rounds, int8_codes=args.int8_codes)

    mesh = parse_mesh(args.mesh)
    pipeline = (None if args.serial else PipelineConfig(
        prefetch_depth=args.prefetch_depth,
        async_checkpoint=not args.sync_checkpoint))
    if mesh is None and args.data_dir:
        print("trainer: out-of-core store fit on a 1x1 mesh "
              f"({jax.devices()[0].platform}; sharded trainer, rows "
              "streamed from disk)")
    elif mesh is None:
        print(f"trainer: single-device ({jax.devices()[0].platform})")
    else:
        shape = dict(zip(mesh.axis_names, mesh.devices.shape))
        mode = ("serial" if pipeline is None else
                f"pipelined (prefetch_depth={pipeline.prefetch_depth}, "
                f"async_checkpoint={pipeline.async_checkpoint})")
        print(f"trainer: shard_map over {mesh.devices.size} devices "
              f"{shape}, {mode}")

    t0 = time.time()
    art = fit_artifacts(X, y, fcfg, seed=args.seed,
                        checkpoint_dir=args.checkpoint_dir,
                        resume=args.resume,
                        ensembles_per_batch=args.ensembles_per_batch,
                        mesh=mesh, pipeline=pipeline)
    wall = time.time() - t0
    n_ens = art.n_t * art.n_y
    # throughput over the work actually done: every ensemble trains on all
    # n rows duplicated K-fold
    rows = X.shape[0] * fcfg.duplicate_k * n_ens
    print(f"trained {n_ens} ensembles ({art.n_t} timesteps x {art.n_y} "
          f"classes) in {wall:.2f}s -> "
          f"{rows / wall:,.0f} ensemble-rows/sec")
    print(json.dumps({"wall_s": round(wall, 3),
                      "ensemble_rows_per_sec": round(rows / wall),
                      "rows_per_sec": round(X.shape[0] * n_ens / wall)}))

    if args.out:
        base = art.save(args.out)
        print(f"artifacts saved to {base}.npz / {base}.json "
              f"(serve: python -m repro.launch.serve_forest "
              f"--artifacts {base})")

    if args.metrics_dump:
        from repro.launch.metrics import dump
        dump(args.metrics_dump)


if __name__ == "__main__":
    main()
