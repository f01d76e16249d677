"""Serve job: open-loop generate requests over HTTP, in process.

Set-up draws the random Table-9 forest grid, registers it in a
``ModelRegistry`` (default buckets unless the workload names others),
warms every bucket with the registry's own warm-up, and starts the
``ServingApp`` behind ``serve_in_thread`` with a span ring large enough
for the window. One request through HTTP warms the JSON path.

Traffic: ``rate`` requests per second for ``--seconds``, all
``POST /v1/generate`` at interactive priority. Every seed gets the same
multiset of sizes (log-uniform over ``[n_min, n_max]`` rows, by quantile)
and of gaps (exponential, by quantile), in its own order, so seeds change
the order of the work and not its amount. A dispatcher thread sends each
request at its due time through a pool of client threads; a request's
latency runs from its due time to the last byte of its response, and a
request that fails or has not finished a minute after the window counts
with the time it was given. How late the dispatcher ran is logged.

The check reads the serving spans: each ``serve.device`` span links the
request ids of one coalesced batch in order, and the batch's seed is the
scheduler's ``BATCH_SEED_BASE + batch_id``. From those, the reference
solves the exact rows each sampled request should have received.
"""
from __future__ import annotations

import concurrent.futures as cf
import json
import time
import urllib.request

import numpy as np

from chipbench import data
from chipbench.jobs.generate import build_artifacts, compare

MODEL = "calo"
# InflightScheduler's seed convention for coalesced batches
BATCH_SEED_BASE = 1 << 20
DRAIN_S = 60.0


def schedule(rate: float, seconds: float, n_min: int, n_max: int,
             seed: int):
    """(due times [N] from the window's start, rows [N])."""
    count = max(1, int(round(rate * seconds)))
    q = (np.arange(count) + 0.5) / count
    rng = np.random.default_rng(seed)
    sizes = np.rint(n_min * (n_max / n_min) ** q).astype(int)
    gaps = -np.log1p(-q)
    gaps = rng.permutation(gaps) * (seconds / gaps.sum())
    return np.cumsum(gaps) - gaps[0], rng.permutation(sizes)


def post(url: str, body: dict, timeout: float):
    req = urllib.request.Request(url, data=json.dumps(body).encode(),
                                 headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, resp.headers.get("X-Repro-Request-Id"), resp.read()


def drive(url: str, due, sizes, *, workers: int, on_tick=None):
    """Send the schedule open loop; returns per-request records."""
    recs = [dict(n=int(n), due=float(d)) for d, n in zip(due, sizes)]
    t0 = time.perf_counter()

    def send(rec):
        rec["sent"] = time.perf_counter() - t0
        try:
            rec["status"], rec["rid"], rec["body"] = post(
                url + "/v1/generate", {"model": MODEL, "n": rec["n"],
                                       "priority": "interactive"},
                timeout=rec["due"] + 600.0)
        except Exception as exc:  # noqa: BLE001 — a failed request, counted
            rec["status"], rec["error"] = None, repr(exc)
        rec["done"] = time.perf_counter() - t0

    pool = cf.ThreadPoolExecutor(workers)
    futures = []
    for rec in recs:
        wait = rec["due"] - (time.perf_counter() - t0)
        if wait > 0:
            time.sleep(wait)
        futures.append(pool.submit(send, rec))
        if on_tick is not None:
            on_tick(time.perf_counter() - t0, recs)
    end = recs[-1]["due"]
    cf.wait(futures, timeout=max(0.0, end + DRAIN_S
                                 - (time.perf_counter() - t0)))
    cut = time.perf_counter() - t0
    pool.shutdown(wait=False, cancel_futures=True)
    for rec in recs:
        ok = rec.get("status") == 200 and "done" in rec
        rec["ok"] = ok
        rec["latency"] = (rec["done"] if ok else cut) - rec["due"]
    return recs, pool


def backlog(recs, at: float) -> int:
    """Requests due by ``at`` and not yet answered at ``at``."""
    return sum(1 for r in recs if r["due"] <= at
               and r.get("done", np.inf) > at)


def start_server(cfg: dict, traffic: dict, seed: int):
    from repro.launch.serve_http import ServingApp, serve_in_thread
    from repro.obs import Tracer
    from repro.serving import ModelRegistry

    registry = ModelRegistry(**({"buckets": tuple(traffic["buckets"])}
                                if "buckets" in traffic else {}))
    registry.register(MODEL, build_artifacts(cfg, seed))
    registry.warmup()
    app = ServingApp(registry, tracer=Tracer(capacity=1 << 17))
    httpd, thread = serve_in_thread(app)
    url = "http://%s:%d" % httpd.server_address[:2]
    status, _, _ = post(url + "/v1/generate", {"model": MODEL, "n": 8}, 600)
    if status != 200:
        raise RuntimeError(f"warm-up request failed with {status}")
    return registry, app, httpd, thread, url


def stop_server(app, httpd, thread) -> None:
    httpd.shutdown()
    httpd.server_close()
    app.stop()
    thread.join(timeout=30)


def batches_of(app) -> dict:
    """request id -> [batch seed, batch rows, device span seconds], from
    the ``serve.device`` spans that resolved."""
    out = {}
    for sp in app.tracer.spans("serve.device"):
        if sp.attrs.get("outcome") != "ok":
            continue
        out.update({rid: [BATCH_SEED_BASE + sp.attrs["batch_id"],
                          sp.attrs["rows"], sp.duration_s]
                    for rid in sp.links})
    return out


def check(ctx, art, recs, links, offsets, rng):
    """Served rows of sampled requests, the longest among them, against
    the reference."""
    done = [r for r in recs if r["ok"] and r["rid"] in links]
    picks = set(rng.choice(len(done), replace=False, size=min(
        ctx.traffic["check_requests"], len(done))).tolist())
    if done:
        picks.add(max(range(len(done)), key=lambda i: done[i]["n"]))
    rows, labels, seeds, cls, idx = [], [], [], [], []
    for i in sorted(picks):
        rec = done[i]
        seed, rows_b, _ = links[rec["rid"]]
        start = offsets[rec["rid"]]
        c, k = ctx.reference.row_origin(np.asarray(art.counts), rows_b, seed)
        body = json.loads(rec["body"])
        got = np.asarray(body["rows"], np.float64)
        if got.shape != (rec["n"], art.p):
            got = np.full((rec["n"], art.p), np.inf)
        rows.append(got)
        labels.append(np.asarray(body["labels"]).reshape(-1)[:rec["n"]])
        seeds.append(np.full(rec["n"], seed))
        cls.append(c[start:start + rec["n"]])
        idx.append(k[start:start + rec["n"]])
    ctx.log(phase="check_requests", requests=len(picks))
    return compare(ctx, art, *(np.concatenate(a) for a in
                               (rows, labels, seeds, cls, idx)))


def run(ctx) -> dict:
    cfg, traffic = ctx.config, ctx.traffic
    s_forest, s_traffic, s_check = data.sub_seeds(ctx.seed, 3)
    registry, app, httpd, thread, url = start_server(cfg, traffic, s_forest)
    due, sizes = schedule(traffic["rate"], ctx.seconds, traffic["n_min"],
                          traffic["n_max"], s_traffic)
    ctx.setup_done()

    trace_s = traffic.get("trace_seconds", ctx.seconds)

    def tick(now, recs):
        if now >= trace_s:
            ctx.end_trace()

    with ctx.window():
        recs, pool = drive(url, due, sizes, workers=traffic["workers"],
                           on_tick=tick)
    peak = ctx.memory_peak_bytes()
    stop_server(app, httpd, thread)
    pool.shutdown(wait=True)
    late = np.array([r["sent"] - r["due"] for r in recs if "sent" in r])
    lat = np.array([r["latency"] for r in recs])
    ctx.log(phase="window", requests=len(recs),
            failed=int(sum(not r["ok"] for r in recs)),
            lateness_p50_s=float(np.median(late)),
            lateness_max_s=float(late.max()),
            backlog_at_5s=backlog(recs, 5.0),
            backlog_at_end=backlog(recs, float(due[-1])),
            latency_p50_s=float(np.median(lat)))

    links = batches_of(app)
    queue_s = {sp.trace_id: sp.duration_s
               for sp in app.tracer.spans("serve.queue")}
    rows_of = {r["rid"]: r["n"] for r in recs if r.get("rid")}
    order = {}
    for sp in app.tracer.spans("serve.device"):
        pos = 0
        for rid in sp.links:
            order[rid] = pos
            pos += rows_of.get(rid, 0)
    batch_rows = [sp.attrs["rows"] for sp in app.tracer.spans("serve.device")]
    front = [r["done"] - r["sent"] - queue_s[r["rid"]] - links[r["rid"]][2]
             for r in recs if r["ok"] and r["rid"] in links
             and r["rid"] in queue_s]
    del registry, app

    art = build_artifacts(cfg, s_forest)
    t_ref = time.perf_counter()
    checks = check(ctx, art, recs, links, order,
                   np.random.default_rng(s_check))
    ctx.log(phase="reference", seconds=time.perf_counter() - t_ref)

    return {
        "e2e": {"serve_p95_s": float(np.percentile(lat, 95))},
        "attempted": len(recs),
        "failed": int(sum(not r["ok"] for r in recs)),
        "memory_peak_bytes": peak,
        "checks": checks,
        "facts": {"queue_wait_s": list(queue_s.values()),
                  "batch_rows": batch_rows, "front_end_s": front},
    }
