"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Sections:
  resource_scaling  - Fig. 1 / 2 / 4 (top+middle): time+memory vs n
  quality           - Table 2 / 7: W1 + coverage + mean rank, 7 methods
  calo              - Table 3/4/5: chi^2 separation + classifier AUC
  generation        - Fig. 4 (bottom): SO vs MO generation time
  training          - §3.3 scaling: fit throughput + memory vs device count
  store_scaling     - §3.3 out-of-core: in-memory vs DatasetStore-backed fit
                      (peak RSS + ABBA min-of-reps throughput vs dataset size)
  serving           - open-loop mixed-tenant load: in-flight scheduler vs
                      drain-then-serve reference + latency percentiles
  refresh           - freshness loop: warm-start extension vs full refit
  ablation          - Fig. 3 / 10 / 11: early stopping + K/n_tree sweeps
  roofline          - dry-run roofline table (scale deliverable)

Full-size variants are driven by the flags below; defaults are sized for the
CPU CI budget.

Every section runs in a child process of its own (``--section``); this
parent never imports JAX. An accelerator belongs to one process at a time,
so a parent holding it would leave every child to fail or hang. A section
whose child fails fails the run.
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys

SECTIONS = ("resource_scaling", "quality", "calo", "generation", "training",
            "store_scaling", "serving", "refresh", "ablation", "roofline")


def run_section(name: str, quick: bool, json_dir: str) -> None:
    """Run one section in this process (the ``--section`` child)."""
    from benchmarks import (bench_ablations, bench_calo, bench_generation,
                            bench_quality, bench_refresh,
                            bench_resource_scaling, bench_roofline,
                            bench_serving, bench_training)

    def json_path(stem):
        return os.path.join(json_dir, f"BENCH_{stem}.json")

    sections = {
        "resource_scaling": lambda: bench_resource_scaling.main(
            sizes=(200, 500, 1000) if quick else (1000, 3000, 10000)),
        "quality": lambda: bench_quality.main(quick=quick),
        "calo": lambda: bench_calo.main(quick=quick,
                                        n=1500 if quick else 120000),
        "generation": lambda: bench_generation.main(
            quick=quick, json_path=json_path("generation")),
        "training": lambda: bench_training.main(
            quick=quick, json_path=json_path("training")),
        "store_scaling": lambda: bench_resource_scaling.main_store(
            quick=quick, json_path=json_path("resource_scaling")),
        "serving": lambda: bench_serving.main(
            quick=quick, json_path=json_path("serving")),
        "refresh": lambda: bench_refresh.main(
            quick=quick, json_path=json_path("refresh")),
        "ablation": lambda: bench_ablations.main(quick=quick),
        "roofline": lambda: bench_roofline.main(),
    }
    sections[name]()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of sections")
    ap.add_argument("--section", default=None, choices=SECTIONS,
                    help="run one section in this process (what the "
                         "parent starts each child with)")
    ap.add_argument("--full", action="store_true",
                    help="paper-sized settings (hours on CPU)")
    ap.add_argument("--json-dir", default=".",
                    help="where BENCH_*.json artifacts are written (CI can "
                         "point this at a scratch dir to keep the committed "
                         "trajectory files untouched)")
    args = ap.parse_args()
    if args.section:
        run_section(args.section, not args.full, args.json_dir)
        return

    chosen = args.only.split(",") if args.only else list(SECTIONS)
    unknown = sorted(set(chosen) - set(SECTIONS))
    if unknown:
        ap.error(f"unknown sections: {unknown}")
    print("name,us_per_call,derived", flush=True)
    failed = []
    for name in chosen:
        cmd = [sys.executable, "-m", "benchmarks.run", "--section", name,
               "--json-dir", args.json_dir] + (["--full"] if args.full
                                               else [])
        if subprocess.run(cmd).returncode != 0:
            failed.append(name)
            print(f"{name},fail,see stderr", flush=True)
    if failed:  # after all sections ran, make CI see the failure
        raise SystemExit(f"benchmark sections failed: {','.join(failed)}")


if __name__ == "__main__":
    main()
