"""Forest serving driver: warm, pre-jitted tabular generation + imputation.

Since PR 6 this is a thin single-model front end over the
:mod:`repro.serving` control plane: a one-entry
:class:`~repro.serving.ModelRegistry`, an
:class:`~repro.serving.AdmissionController` (permissive by default — no
rate limits, generous queue bounds), and the
:class:`~repro.serving.InflightScheduler`. The multi-model, multi-tenant
HTTP tier lives in :mod:`repro.launch.serve_http`; both share every
control-plane behavior by construction.

Serving properties (carried over from PR 4, upgraded in PR 6):

* ``warmup()`` pre-compiles one program per (sampler, bucket) through the
  same :class:`TabularGenerator` facade that serves requests — warmed
  programs can't diverge from served ones;
* ``submit()`` queues a request and returns a future; the scheduler
  coalesces concurrent same-sampler requests into one bucketed device
  dispatch **and keeps admitting the next batch while the current one is
  in flight** (a waiter thread resolves futures — queue wait no longer
  stacks on device time);
* ``generate()`` stays synchronous and exactly per-(n, seed) deterministic;
* unknown sampler names raise ``ValueError`` at ``submit()``/``generate()``
  time, to the caller — not inside the dispatcher after a wasted dispatch;
* ``stats`` carries per-sampler splits and a queue-wait vs device-time
  breakdown next to the PR-4 aggregate counters — since PR 8 it is a view
  over one shared :class:`~repro.obs.MetricsRegistry` (``server.metrics``)
  fed by ``serve.queue``/``serve.device``/``serve.sync`` spans on
  ``server.tracer``; ``--metrics-dump`` writes the same numbers as
  Prometheus text and ``--trace-jsonl`` dumps the span ring (see
  docs/observability.md).

CPU demo (fits a small model, saves, loads, serves):

  PYTHONPATH=src python -m repro.launch.serve_forest --demo --requests 16

Serving a trained model across 8 virtual devices:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python -m repro.launch.serve_forest --artifacts model --mesh 4x2
"""
from __future__ import annotations

import argparse
import os
import tempfile
from concurrent.futures import Future
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.obs import MetricsRegistry, Tracer
from repro.serving import (AdmissionController, InflightScheduler,
                           ModelRegistry)
from repro.serving.registry import DEFAULT_BUCKETS  # noqa: F401 — re-export
from repro.serving.scheduler import Request as _Request  # noqa: F401
from repro.tabgen import ForestArtifacts, TabularGenerator


class ForestServer:
    """Single-host, single-model tabular-generation server.

    A convenience wrapper: one registered model named ``"default"``, the
    in-flight scheduler underneath. Reach into ``server.registry`` /
    ``server.scheduler`` for the multi-model and admission knobs (e.g.
    ``server.registry.swap("default", new_artifacts)`` for a zero-downtime
    artifact hot-swap).
    """

    MODEL = "default"

    def __init__(self, artifacts: ForestArtifacts, *,
                 samplers: Sequence[str] = (),
                 buckets: Sequence[int] = DEFAULT_BUCKETS,
                 schema=None, mesh=None, impl: Optional[str] = None,
                 max_coalesce_rows: Optional[int] = None,
                 coalesce_window_s: float = 0.002,
                 inflight_depth: int = 2,
                 sync_resolve: bool = False,
                 admission: Optional[AdmissionController] = None,
                 metrics: Optional[MetricsRegistry] = None,
                 tracer: Optional[Tracer] = None,
                 slo=None, slo_error_budget: float = 0.01, slow_log=None):
        # one registry + tracer shared by every component of this server:
        # scheduler, admission, and model registry export one family set
        self.metrics = metrics or MetricsRegistry()
        self.tracer = tracer or Tracer()
        self.registry = ModelRegistry(mesh=mesh, impl=impl, buckets=buckets,
                                      metrics=self.metrics)
        self.registry.register(self.MODEL, artifacts, schema=schema,
                               samplers=samplers)
        self.scheduler = InflightScheduler(
            self.registry,
            admission or AdmissionController(metrics=self.metrics),
            max_coalesce_rows=max_coalesce_rows,
            coalesce_window_s=coalesce_window_s,
            inflight_depth=inflight_depth, sync_resolve=sync_resolve,
            metrics=self.metrics, tracer=self.tracer,
            slo=slo, slo_error_budget=slo_error_budget, slow_log=slow_log)
        self.mesh = self.registry.mesh
        self.impl = impl
        self.schema = schema

    @classmethod
    def from_path(cls, path: str, **kw) -> "ForestServer":
        gen = TabularGenerator.load(path)
        return cls(gen.artifacts, schema=gen.schema, **kw)

    # -- model-facing views --------------------------------------------------

    @property
    def _handle(self):
        return self.registry.peek(self.MODEL)

    @property
    def artifacts(self) -> ForestArtifacts:
        return self._handle.artifacts

    @property
    def samplers(self) -> Tuple[str, ...]:
        return self._handle.samplers

    @property
    def buckets(self) -> Tuple[int, ...]:
        return self._handle.buckets

    @property
    def max_coalesce_rows(self) -> int:
        return self.scheduler.max_coalesce_rows

    @property
    def stats(self) -> Dict[str, float]:
        return self.scheduler.stats

    # -- request path -------------------------------------------------------

    def _validate_sampler(self, sampler: Optional[str]) -> str:
        name = sampler or self.samplers[0]
        if name not in self.samplers:
            raise ValueError(
                f"server does not serve sampler {name!r}; "
                f"served: {list(self.samplers)}")
        return name

    def warmup(self) -> float:
        """Compile every (sampler, bucket) program; returns wall seconds."""
        dt = self.registry.warmup(self.MODEL)
        self.scheduler.record_warm(dt)
        return dt

    def generate(self, n: int, *, sampler: Optional[str] = None,
                 seed: int = 0) -> Tuple[np.ndarray, np.ndarray]:
        """Synchronous path: exact per-(n, seed) deterministic output."""
        name = self._validate_sampler(sampler)
        handle = self.registry.acquire(self.MODEL)
        with self.tracer.span("serve.sync", model=self.MODEL, sampler=name,
                              rows=int(n)) as sp:
            X, y = handle.generate(n, name, seed=seed)
        self.scheduler.record_sync(n=n, sampler=name, tenant="default",
                                   wall_s=sp.duration_s)
        return X, y

    def submit(self, n: int, *, sampler: Optional[str] = None,
               tenant: str = "default", priority: str = "interactive",
               deadline_s: Optional[float] = None) -> Future:
        """Queue a generation request; resolves to ``(X, y)``.

        Concurrent submissions coalesce into shared device dispatches, and
        the next batch is admitted while the current one is in flight.
        Unknown samplers raise ``ValueError`` here; admission rejections
        (when the server was built with rate limits / tight queue bounds)
        raise ``RateLimited`` / ``QueueFull`` here too.
        """
        return self.scheduler.submit(
            int(n), model=self.MODEL,
            sampler=self._validate_sampler(sampler),
            tenant=tenant, priority=priority, deadline_s=deadline_s)

    def start(self) -> None:
        """Start the scheduler threads (idempotent; ``submit`` auto-starts)."""
        self.scheduler.start()

    def stop(self, timeout: float = 10.0) -> None:
        """Drain the queue and stop the scheduler threads."""
        self.scheduler.stop(timeout)

    def _serve_batch(self, batch) -> None:
        """Dispatch + resolve one pre-formed batch synchronously (test seam
        kept from PR 4; production traffic goes through ``submit``)."""
        self.scheduler.serve_batch_sync(batch)

    # -- misc ---------------------------------------------------------------

    def impute(self, X_missing, y=None, *, seed: int = 0,
               refine_rounds: int = 3) -> np.ndarray:
        return self.registry.acquire(self.MODEL).impute(
            X_missing, y, seed=seed, refine_rounds=refine_rounds)

    def rows_per_sec(self) -> float:
        return self.scheduler.rows_per_sec()


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _demo_artifacts(path: str) -> str:
    """Fit a small two-moons model and save it — the zero-setup demo."""
    from repro.config import ForestConfig
    from repro.data.tabular import two_moons
    X, y = two_moons(600, seed=0)
    fcfg = ForestConfig(method="flow", n_t=8, duplicate_k=10, n_trees=20,
                        max_depth=4, n_bins=32, reg_lambda=1.0)
    gen = TabularGenerator(fcfg).fit(X, y, seed=0)
    return gen.save(path)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--artifacts", default=None,
                    help="base path of a saved model (.npz/.json pair)")
    ap.add_argument("--demo", action="store_true",
                    help="fit+save a small two-moons model first")
    ap.add_argument("--sampler", default=None)
    ap.add_argument("--buckets", default="64,256,1024")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default="none",
                    help="'auto' | 'none' | DxM — shard the solve "
                         "(classes on model, rows on data)")
    ap.add_argument("--impl", default=None,
                    help="tree-predict backend: xla | pallas | "
                         "pallas_interpret (default: config/env)")
    ap.add_argument("--sync", action="store_true",
                    help="serve via the synchronous generate() path instead "
                         "of the micro-batching queue")
    ap.add_argument("--drain", action="store_true",
                    help="disable in-flight batching (PR-4 drain-then-serve "
                         "reference behavior)")
    ap.add_argument("--coalesce-window-ms", type=float, default=2.0)
    ap.add_argument("--metrics-dump", default=None, metavar="PATH",
                    help="after serving, write the metrics registry as "
                         "Prometheus text ('-' for stdout)")
    ap.add_argument("--trace-jsonl", default=None, metavar="PATH",
                    help="after serving, dump the span ring as JSON lines")
    args = ap.parse_args()
    enable_compile_cache()

    path = args.artifacts
    if args.demo or path is None:
        path = _demo_artifacts(os.path.join(tempfile.mkdtemp(), "demo"))
        print(f"demo artifacts saved to {path}")

    from repro.launch.train_forest import parse_mesh
    samplers = (args.sampler,) if args.sampler else ()
    buckets = tuple(int(b) for b in args.buckets.split(","))
    server = ForestServer.from_path(
        path, samplers=samplers, buckets=buckets,
        mesh=parse_mesh(args.mesh), impl=args.impl,
        coalesce_window_s=args.coalesce_window_ms / 1e3,
        sync_resolve=args.drain)
    warm = server.warmup()
    print(f"warmed {len(server.samplers)} sampler(s) x {len(buckets)} "
          f"bucket(s) in {warm:.2f}s"
          + (f" on mesh {dict(zip(server.mesh.axis_names, server.mesh.devices.shape))}"
             if server.mesh is not None else ""))

    rng = np.random.default_rng(args.seed)
    sizes = rng.integers(1, max(buckets) + 1, size=args.requests)
    if args.sync:
        for i, n in enumerate(sizes):
            server.generate(int(n), seed=args.seed + i)
    else:
        futs = [server.submit(int(n)) for n in sizes]
        for f, n in zip(futs, sizes):
            X, y = f.result(timeout=300)
            assert len(X) == n
        server.stop()
    s = server.stats
    print(f"served {int(s['requests'])} requests / {int(s['rows'])} rows "
          f"in {int(s['batches'])} dispatch(es) "
          f"({int(s['coalesced_requests'])} coalesced) "
          f"in {s['gen_s']:.3f}s -> {server.rows_per_sec():.0f} rows/sec; "
          f"queue-wait {s['queue_wait_s']:.3f}s vs device {s['device_s']:.3f}s")
    if args.metrics_dump:
        from repro.launch.metrics import dump
        dump(args.metrics_dump, registries=[server.metrics])
    if args.trace_jsonl:
        n_spans = server.tracer.export_jsonl(args.trace_jsonl)
        print(f"wrote {n_spans} spans to {args.trace_jsonl}")


if __name__ == "__main__":
    main()
