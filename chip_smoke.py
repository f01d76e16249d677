#!/usr/bin/env python3
"""Bring-up smoke: fit -> save/load -> kernel parity -> HTTP serve on a TPU.

Drives the system's main path once, through the entry points a user calls,
at the full widths of the CaloChallenge photon deployment: p = 368
features, 15 energy classes, depth-7 multi-output trees over 64 bins. Only
scale is cut (showers, duplication K, timesteps, trees); each cut is
printed on an early line. Data and weights come from ``--seed``.

    python chip_smoke.py             # one chip: every phase below
    python chip_smoke.py --chips 4   # four chips: sharded fit, solve, serve

One chip, one process, each phase fatal:

1. fit      ``fit_artifacts`` trains every (timestep, class) ensemble,
            ``ENSEMBLES_PER_BATCH`` at a time (sized from the compiled fit
            step's memory, printed with its bytes)
2. io       ``save`` -> ``load``; sampling the loaded artifacts equals
            sampling the fitted ones for a fixed seed
3. kernels  ``sample(impl="pallas")`` vs ``impl="xla"`` and the histogram
            kernel vs ``build_histogram(impl="xla")`` on the deepest level
            of one real ensemble, both within 1e-5 and compiled
4. serve    ``ServingApp`` over a ``ModelRegistry`` behind ``serve_in_thread``:
            mixed-size generate requests at both priorities, one impute,
            one ``GET /metrics``

``--chips 4`` runs only what exists across chips: a fit under
``mesh="auto"`` (2x2), ``sample(mesh=...)`` and ``ForestArtifacts.shard``
against the single-device solve of the same artifacts, and a mesh-backed
``ModelRegistry`` serving a few requests.

With no TPU the script exits non-zero before any phase runs. The last line
of stdout is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import concurrent.futures as cf
import functools
import json
import sys
import tempfile
import time
import urllib.request
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.config import ForestConfig  # noqa: E402
from repro.core import interpolants as itp  # noqa: E402
from repro.data import calorimeter  # noqa: E402
from repro.forest.hist import build_histogram  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import auto_forest_mesh  # noqa: E402
from repro.launch.serve_http import ServingApp, serve_in_thread  # noqa: E402
from repro.serving import ModelRegistry  # noqa: E402
from repro.tabgen import ForestArtifacts, fit_artifacts, sample  # noqa: E402
from repro.tabgen.artifacts import rescale  # noqa: E402
from repro.tabgen.fitting import (ensemble_inputs, prepare_classes,  # noqa: E402
                                  single_fit_program)
from repro.tabgen.sampling import sample_async  # noqa: E402

DATASET = "photons"          # p = 368, 15 energy classes
TOL = 1e-5                   # Pallas vs XLA, sharded vs single device

# Scale cuts (widths stay the deployment's).
SHOWERS_PER_CLASS = 1024     # CaloChallenge photons: ~8k per class
DUPLICATE_K = 10             # paper: K = 100
N_T = 2                      # paper: n_t = 50
N_TREES = 3                  # paper: up to 100 boosting rounds
# The single-device fit step at these shapes, compiled for a v5e, needs
# 12.51 GB of temp at 1 ensemble per batch; at 2 the compiler asks for
# 33.20 GB of the chip's 15.75 GB.
ENSEMBLES_PER_BATCH = 1
# The XLA reference histogram of the deepest level (10 860 rows, 64 nodes,
# 64 bins, 368 outputs), compiled for a v5e, needs 16.46 GB of HBM over
# all 368 features and 3.0 GB of temp over 92; the parity check runs it
# 92 features at a time against one full-width kernel call.
HIST_REF_FEATURES = 92
# --chips 4: every class ensemble of the sharded trainer sees all rows,
# K-duplicated, split over the 2-wide data axis.
SHARDED_SHOWERS_PER_CLASS = 96
SHARDED_DUPLICATE_K = 8

GENERATE_REQUESTS = ((40, "interactive"), (700, "bulk"),
                     (150, "interactive"), (2500, "bulk"))
BUCKETS = (64, 256)


def log(**kv) -> None:
    print(json.dumps(kv, default=str), flush=True)


def require_tpu(count: int):
    """The devices to run on; exits (non-zero) without ``count`` TPUs."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{devs[0].platform!r}); nothing was run")
    if len(devs) < count:
        raise SystemExit(f"chip_smoke: needs {count} TPU chips, JAX sees "
                         f"{len(devs)}")
    return devs[:count]


def smoke_config(n_t: int = N_T, n_trees: int = N_TREES,
                 duplicate_k: int = DUPLICATE_K, max_depth: int = 7,
                 n_bins: int = 64) -> ForestConfig:
    return ForestConfig(method="flow", n_t=n_t, duplicate_k=duplicate_k,
                        n_trees=n_trees, max_depth=max_depth, n_bins=n_bins,
                        multi_output=True)


def make_data(showers_per_class: int, seed: int, dataset: str = DATASET):
    n = showers_per_class * calorimeter.N_CLASSES
    return calorimeter.generate(dataset, n, seed=seed)


def model_space(art: ForestArtifacts, X, y):
    """Samples back in the per-class [-1, 1] space the forests route in,
    where one tolerance fits every feature (raw energies span 1e-2..1e6)."""
    idx = np.searchsorted(np.asarray(art.classes), y)
    return rescale(np.asarray(X, np.float64), np.asarray(art.mins)[idx],
                   np.asarray(art.maxs)[idx])


@functools.partial(jax.jit, static_argnames="tol")
def _diff_stats(got, want, tol: float):
    """(max |got - want|, max of |got - want| - tol |want|, all finite),
    fused on the device: a multi-output histogram is 2 GB, too big for
    host copies or unfused temporaries."""
    err = jnp.abs(got - want)
    return (jnp.max(err), jnp.max(err - tol * jnp.abs(want)),
            jnp.all(jnp.isfinite(got)))


def check_close(name: str, got, want, tol: float = TOL) -> float:
    """``|got - want| <= tol + tol * |want|`` everywhere (numpy's
    ``allclose`` with ``rtol = atol = tol``) and all finite; returns the
    largest absolute difference."""
    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    if got.shape != want.shape:
        raise AssertionError(f"{name}: shape {got.shape} vs {want.shape}")
    max_err, excess, finite = (float(v) for v in
                               _diff_stats(got, want, tol=tol))
    if not finite or excess > tol:
        raise AssertionError(f"{name}: max |diff| {max_err} exceeds "
                             f"{tol} (1 + |ref|), finite={bool(finite)}")
    return max_err


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_fit_memory(X, y, fcfg: ForestConfig, ensembles_per_batch: int):
    """Compile the fit step at the run's shapes; its bytes must fit HBM."""
    Xc, Wc = prepare_classes(X, y)[:2]
    args = (jax.ShapeDtypeStruct(Xc.shape, jnp.float32),
            jax.ShapeDtypeStruct(Wc.shape, jnp.float32),
            jax.ShapeDtypeStruct((2,), jnp.uint32),
            jax.ShapeDtypeStruct((ensembles_per_batch,), jnp.float32),
            jax.ShapeDtypeStruct((ensembles_per_batch,), jnp.int32),
            jax.ShapeDtypeStruct((ensembles_per_batch,), jnp.int32))
    t0 = time.perf_counter()
    compiled = single_fit_program(fcfg).lower(*args).compile()
    mem = compiled.memory_analysis()
    need = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes)
    stats = jax.devices()[0].memory_stats() or {}
    limit = stats.get("bytes_limit")
    log(phase="fit_memory", ensembles_per_batch=ensembles_per_batch,
        rows_per_ensemble=int(Xc.shape[1]) * fcfg.duplicate_k,
        temp_bytes=mem.temp_size_in_bytes, total_bytes=need,
        device_bytes_limit=limit,
        compile_s=time.perf_counter() - t0)
    if limit is not None and need > limit:
        raise AssertionError(f"fit step needs {need} bytes at "
                             f"ensembles_per_batch={ensembles_per_batch}; "
                             f"the device holds {limit}")
    return need


def phase_fit(X, y, fcfg: ForestConfig, *, seed: int,
              ensembles_per_batch: int, mesh=None) -> ForestArtifacts:
    t0 = time.perf_counter()
    art = fit_artifacts(X, y, fcfg, seed=seed, mesh=mesh,
                        ensembles_per_batch=ensembles_per_batch)
    jax.block_until_ready(art.leaf)
    wall = time.perf_counter() - t0
    leaf = np.asarray(art.leaf)
    if not np.all(np.isfinite(leaf)) or not np.any(leaf):
        raise AssertionError("fit produced non-finite or all-zero leaves")
    log(phase="fit", mesh=None if mesh is None else dict(mesh.shape),
        ensembles=art.n_t * art.n_y, feat_shape=list(art.feat.shape),
        leaf_shape=list(art.leaf.shape), wall_s=wall,
        s_per_ensemble=wall / (art.n_t * art.n_y))
    return art


def phase_round_trip(art: ForestArtifacts, workdir: str, *, n: int,
                     seed: int, impl: str) -> None:
    t0 = time.perf_counter()
    base = art.save(str(Path(workdir) / "photons"))
    loaded = ForestArtifacts.load(base)
    save_load_s = time.perf_counter() - t0
    X1, y1 = sample(art, n, seed=seed, impl=impl)
    X2, y2 = sample(loaded, n, seed=seed, impl=impl)
    if not (np.array_equal(y1, y2) and np.array_equal(X1, X2)):
        raise AssertionError("sample on loaded artifacts differs from the "
                             "fitted ones")
    if X1.shape != (n, art.p) or not np.all(np.isfinite(X1)):
        raise AssertionError(f"sample: shape {X1.shape}, expected "
                             f"({n}, {art.p}), or non-finite values")
    log(phase="round_trip", save_load_s=save_load_s, rows=n, equal=True)


def phase_kernel_parity(art: ForestArtifacts, X, y, *, n: int, seed: int,
                        impl: str) -> None:
    """Tree kernel through full ``sample`` calls; histogram kernel on the
    deepest level of the last-timestep ensemble of class 0, rebuilt from
    the same inputs the fit drew."""
    times = {}
    for name in ("xla", impl):
        t0 = time.perf_counter()
        sample(art, n, seed=seed, impl=name)            # compile + run
        t1 = time.perf_counter()
        Xs, ys = sample(art, n, seed=seed, impl=name)
        times[name] = (t1 - t0, time.perf_counter() - t1, Xs, ys)
    Xr, yr = times["xla"][2:]
    Xk, yk = times[impl][2:]
    if not np.array_equal(yr, yk):
        raise AssertionError("labels differ between tree-predict impls")
    d_sample = check_close("sample pallas vs xla", model_space(art, Xk, yk),
                           model_space(art, Xr, yr))

    fcfg = art.config
    Xc, Wc = prepare_classes(X, y)[:2]
    ti, yi = fcfg.n_t - 1, 0
    ts = np.asarray(itp.timesteps(fcfg.method, fcfg.n_t, fcfg.eps_diff,
                                  fcfg.t_schedule), np.float32)
    codes, tgt, w, _, _, _, xt, _ = ensemble_inputs(
        fcfg, jnp.asarray(Xc), jnp.asarray(Wc), jax.random.PRNGKey(seed),
        jnp.float32(ts[ti]), yi, ti * art.n_y + yi)
    depth = fcfg.max_depth
    feat = np.asarray(art.feat[ti, yi, 0, 0])
    thr = np.asarray(art.thr_val[ti, yi, 0, 0])
    xt_h = np.asarray(xt)
    node = np.zeros(xt_h.shape[0], np.int32)
    for level in range(depth - 1):      # route to the deepest split level
        heap = node + 2 ** level - 1
        node = node * 2 + (xt_h[np.arange(len(node)), feat[heap]]
                           > thr[heap])
    n_nodes = 2 ** (depth - 1)
    node_d = jnp.asarray(node, jnp.int32)

    def hist_fn(name):
        return jax.jit(lambda c, i, g_, w_: build_histogram(
            c, i, g_, w_, n_nodes, fcfg.n_bins, impl=name))

    kernel = hist_fn(impl)
    t0 = time.perf_counter()
    got = jax.block_until_ready(kernel(codes, node_d, -tgt, w))
    t1 = time.perf_counter()
    jax.block_until_ready(kernel(codes, node_d, -tgt, w))
    t2 = time.perf_counter()
    # the reference runs over slices of the features (each feature's
    # histogram is independent): see HIST_REF_FEATURES
    ref, d_sum, d_cnt = hist_fn("xla"), 0.0, 0.0
    for j in range(0, codes.shape[1], HIST_REF_FEATURES):
        cols = slice(j, j + HIST_REF_FEATURES)
        want = ref(codes[:, cols], node_d, -tgt, w)
        d_sum = max(d_sum, check_close("histogram sums pallas vs xla",
                                       got[0][:, cols], want[0]))
        d_cnt = max(d_cnt, check_close("histogram counts pallas vs xla",
                                       got[1][:, cols], want[1]))
    log(phase="kernel_parity", impl=impl, sample_rows=n,
        sample_max_abs_diff=d_sample,
        sample_first_call_s={k: v[0] for k, v in times.items()},
        sample_s={k: v[1] for k, v in times.items()},
        hist_shape=[int(codes.shape[0]), int(codes.shape[1]), n_nodes,
                    fcfg.n_bins, int(tgt.shape[1])],
        hist_max_abs_diff=[d_sum, d_cnt], hist_first_call_s=t1 - t0,
        hist_s=t2 - t1, hist_ref_s=time.perf_counter() - t2)


def _http(url: str, body=None):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=600) as resp:
        return resp.status, resp.read()


def phase_serve(art: ForestArtifacts, X, y, *, impl: str, mesh=None,
                requests=GENERATE_REQUESTS, buckets=BUCKETS) -> None:
    registry = ModelRegistry(impl=impl, mesh=mesh, buckets=buckets)
    registry.register("photons", art)
    warm_s = registry.warmup()
    app = ServingApp(registry)
    httpd, thread = serve_in_thread(app)
    url = "http://%s:%d" % httpd.server_address[:2]
    try:
        def generate(nr):
            n, priority = nr
            t0 = time.perf_counter()
            status, raw = _http(url + "/v1/generate",
                                {"model": "photons", "n": n,
                                 "priority": priority})
            body = json.loads(raw)
            rows = np.asarray(body["rows"], np.float64)
            if (status != 200 or rows.shape != (n, art.p)
                    or len(body["labels"]) != n
                    or not np.all(np.isfinite(rows))):
                raise AssertionError(f"generate n={n}: status {status}, "
                                     f"rows {rows.shape}")
            return time.perf_counter() - t0

        with cf.ThreadPoolExecutor(len(requests)) as pool:
            latencies = list(pool.map(generate, requests))

        rows = X[:4].astype(np.float64)
        missing = np.zeros(rows.shape, bool)
        missing[:, ::7] = True
        cells = [[None if m else float(v) for v, m in zip(r, mr)]
                 for r, mr in zip(rows, missing)]
        status, raw = _http(url + "/v1/impute",
                            {"model": "photons", "rows": cells,
                             "labels": [int(v) for v in y[:4]]})
        filled = np.asarray(json.loads(raw)["rows"], np.float64)
        if (status != 200 or filled.shape != rows.shape
                or not np.all(np.isfinite(filled))
                or not np.allclose(filled[~missing], rows[~missing])):
            raise AssertionError(f"impute: status {status}, rows "
                                 f"{filled.shape}")

        status, raw = _http(url + "/metrics")
        if status != 200 or b"serving_requests_total" not in raw:
            raise AssertionError(f"/metrics: status {status}")
    finally:
        httpd.shutdown()
        httpd.server_close()
        app.stop()
        thread.join(timeout=10)
    log(phase="serve", mesh=None if mesh is None else dict(mesh.shape),
        impl=impl, warm_s=warm_s,
        requests=[{"n": n, "priority": pr, "latency_s": s}
                  for (n, pr), s in zip(requests, latencies)],
        impute_rows=len(rows), metrics_bytes=len(raw))


def phase_sharded(art: ForestArtifacts, mesh, *, n: int, seed: int,
                  impl: str) -> None:
    """``sample(mesh=...)`` and pre-sharded artifacts vs the single-device
    solve of the same artifacts; the results must live on every device."""
    n_dev = mesh.devices.size
    X1, y1 = sample(art, n, seed=seed, impl=impl)
    placed = art.shard(mesh)
    diffs = {}
    for name, a in (("mesh", art), ("shard", placed)):
        handle = sample_async(a, n, seed=seed, mesh=mesh, impl=impl)
        if len(handle.sharding.device_set) != n_dev:
            raise AssertionError(f"{name}: sharded solve ran on "
                                 f"{len(handle.sharding.device_set)} of "
                                 f"{n_dev} devices")
        Xm, ym = handle.result()
        if not np.array_equal(y1, ym):
            raise AssertionError(f"{name}: labels differ from single device")
        diffs[name] = check_close(f"sharded ({name}) vs single device",
                                  model_space(art, Xm, ym),
                                  model_space(art, X1, y1))
    for f in ("feat", "leaf", "mins"):
        if len(getattr(placed, f).sharding.device_set) != n_dev:
            raise AssertionError(f"shard(mesh).{f} is not on all devices")
    log(phase="sharded_solve", mesh=dict(mesh.shape), rows=n,
        max_abs_diff=diffs, devices=n_dev)


# ---------------------------------------------------------------------------
# runs
# ---------------------------------------------------------------------------

def run_one_chip(seed: int) -> None:
    fcfg = smoke_config()
    log(cuts={"dataset": DATASET, "p": calorimeter.P_TARGET[DATASET],
              "classes": calorimeter.N_CLASSES, "max_depth": fcfg.max_depth,
              "n_bins": fcfg.n_bins, "multi_output": fcfg.multi_output,
              "showers_per_class": SHOWERS_PER_CLASS,
              "duplicate_k": fcfg.duplicate_k, "n_t": fcfg.n_t,
              "n_trees": fcfg.n_trees,
              "ensembles_per_batch": ENSEMBLES_PER_BATCH})
    X, y = make_data(SHOWERS_PER_CLASS, seed)
    phase_fit_memory(X, y, fcfg, ENSEMBLES_PER_BATCH)
    art = phase_fit(X, y, fcfg, seed=seed,
                    ensembles_per_batch=ENSEMBLES_PER_BATCH)
    with tempfile.TemporaryDirectory() as d:
        phase_round_trip(art, d, n=1500, seed=seed, impl="pallas")
    phase_kernel_parity(art, X, y, n=1500, seed=seed, impl="pallas")
    phase_serve(art, X, y, impl="pallas")


def run_four_chips(seed: int) -> None:
    fcfg = smoke_config(duplicate_k=SHARDED_DUPLICATE_K)
    mesh = auto_forest_mesh()
    log(cuts={"dataset": DATASET, "p": calorimeter.P_TARGET[DATASET],
              "classes": calorimeter.N_CLASSES, "max_depth": fcfg.max_depth,
              "n_bins": fcfg.n_bins, "multi_output": fcfg.multi_output,
              "showers_per_class": SHARDED_SHOWERS_PER_CLASS,
              "duplicate_k": fcfg.duplicate_k, "n_t": fcfg.n_t,
              "n_trees": fcfg.n_trees, "mesh": dict(mesh.shape)})
    X, y = make_data(SHARDED_SHOWERS_PER_CLASS, seed)
    art = phase_fit(X, y, fcfg, seed=seed, ensembles_per_batch=0, mesh=mesh)
    # the Pallas kernel has no mesh route (GSPMD cannot partition it), so
    # the sharded solve runs the XLA traversal
    phase_sharded(art, mesh, n=1500, seed=seed, impl="xla")
    phase_serve(art, X, y, impl="xla", mesh=mesh,
                requests=GENERATE_REQUESTS[:3])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the sharded fit/solve/serve path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    devs = require_tpu(args.chips)
    cache_dir = enable_compile_cache()
    log(device=devs[0].device_kind, count=len(devs), jax=jax.__version__,
        compile_cache=cache_dir)
    if args.chips == 4:
        run_four_chips(args.seed)
    else:
        run_one_chip(args.seed)
    log(total_s=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
