"""Readings of a cell's control, on the chip, at the cell's own size.

    python chipbench/control.py --workload calo_photons.fit \
        --seeds 101,102,103 --seconds 5

Runs the cell once per seed in one process with its control in the
program's place (the fit cell: the program's own bf16 histogram path;
the generation and serving cells: the plain reference in bfloat16) and
prints each run's compared numbers. The benchmark's own runs never run
it; the limits in ``workloads/<cell>.json`` sit below what it reads.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import run as harness
    for seed in (int(s) for s in args.seeds.split(",")):
        res = harness.run(ROOT, args.workload, seed, args.seconds, False,
                          control=True)
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
