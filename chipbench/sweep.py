"""Knee sweep of a serving cell: the highest rate with no growing backlog.

    python chipbench/sweep.py --workload calo_photons.serve --seed 7 \
        --rates 4,8,16,32 --seconds 20

One process, one set-up, then one open-loop window per rate with the
cell's traffic at that rate. A rate holds when the backlog (requests due
and not yet answered) at the window's end is no longer than after its
first 5 seconds. Prints one JSON line per rate; the knee is recorded in
PERF.md and the cell's ``rate`` set to about 0.8 of it.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import numpy as np
    from chipbench import data
    from chipbench import run as harness
    from chipbench.jobs import serve

    _, entry, workload, cfg = harness.cell_spec(ROOT, args.workload)
    harness.devices_for(entry["chips"], "tpu")
    harness.enable_compile_cache(ROOT)
    traffic = workload["traffic"]
    s_forest, s_traffic, _ = data.sub_seeds(args.seed, 3)
    _, app, httpd, thread, url = serve.start_server(cfg, traffic, s_forest)
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            due, sizes = serve.schedule(rate, args.seconds, traffic["n_min"],
                                        traffic["n_max"], s_traffic)
            recs, pool = serve.drive(url, due, sizes,
                                     workers=traffic["workers"])
            pool.shutdown(wait=True)
            lat = np.array([r["latency"] for r in recs])
            late = np.array([r["sent"] - r["due"] for r in recs])
            print(json.dumps({
                "rate": rate, "requests": len(recs),
                "failed": int(sum(not r["ok"] for r in recs)),
                "backlog_at_5s": serve.backlog(recs, 5.0),
                "backlog_at_end": serve.backlog(recs, float(due[-1])),
                "p50_s": float(np.median(lat)),
                "p95_s": float(np.percentile(lat, 95)),
                "lateness_max_s": float(late.max())}), flush=True)
    finally:
        serve.stop_server(app, httpd, thread)
    return 0


if __name__ == "__main__":
    sys.exit(main())
