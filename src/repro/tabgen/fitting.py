"""Training: data prep + the batched ensemble fit, producing ForestArtifacts.

Memory discipline (paper §3.3, re-expressed for accelerators):

* Issue 1 — the [n_t, nK, p] array of noised inputs is never built. Each
  ensemble batch constructs its own x_t inside the jitted fit.
* Issue 2 — exactly one copy of X0 lives in memory; noise X1 is *never stored
  at all*: it is regenerated on device from a counter-based PRNG key (a
  strictly stronger version of the shared-memmap fix).
* Issue 3 — trained ensembles are streamed to disk per batch
  (``checkpoint_dir``) and training resumes from the manifest after failure.
  The manifest carries a config fingerprint so a resume can never silently
  mix batches trained under a different configuration.
* Issues 5-7 — classes are sorted/padded into dense [n_y, n_max, p] blocks
  (static-shape slices, no boolean-mask copies), one quantised code matrix is
  shared by all p outputs of an ensemble (DMatrix reuse), and everything is
  fp32.

Algorithmic additions from §3.4: multi-output trees, early stopping on a
fresh-noise validation set, per-class min-max scalers, empirical label
sampling.

Scaling (paper §3.3's 370x-larger-datasets claim): ``fit_artifacts`` also
routes through the shard_map trainer (:mod:`repro.forest.distributed`) when
given a ``mesh`` — rows sharded over the data axes with weight-masked class
conditioning (no padded per-class blocks), the (timestep, class) ensemble
grid sharded over the model axis, and host→device streaming of row chunks so
X never has to fit on a single device. ``mesh="auto"`` builds one from
``jax.devices()``; ``mesh=None`` keeps the single-device path.

Memory model (PR 5): two data routes with different peak-residency classes.

* In-memory single-device route (``mesh=None``, host arrays): peak host
  memory is O(dataset + padded class blocks) — ``prepare_classes`` streams
  the class stats in row chunks and gathers rows straight into the padded
  ``[n_y, n_max, p]`` blocks, so the old full class-sorted intermediate
  copy is gone, but the padded blocks themselves remain. Use it for data
  that comfortably fits in RAM.
* Out-of-core route (``X`` is a :class:`repro.data.store.DatasetStore`):
  always runs the sharded trainer (a 1x1 mesh is built if none is given).
  Class stats and quantile summaries come precomputed from the store
  manifest (no fit-time stats pass at all), and ``build_row_shards``
  gathers each device's row slice directly from the on-disk shards — peak
  *host* memory is O(shard + batch) staging on top of the device-resident
  row shards (which on TPU live in HBM, and in aggregate hold the dataset
  exactly once). No dataset-sized host copy, padded block, or full-column
  sort exists anywhere on this route.

Pipelining (PR 3): the distributed fit loop is a staged producer/consumer
pipeline — a prefetch thread builds batch ``b+1``'s host-side inputs (the
sharded row arrays on first use, per-batch timesteps/classes/PRNG keys)
while batch ``b`` runs on the devices, the main thread only dispatches, and
a writer thread does the deferred ``jax.block_until_ready`` bookkeeping:
gathering each ``BoostResult`` and streaming ``batch_*.npz`` checkpoints +
manifest updates off the critical path. :class:`PipelineConfig` carries the
knobs (prefetch depth, async checkpointing); ``pipeline=None`` falls back to
the PR-2 serial loop, and both paths build bit-identical batches.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import queue
import threading
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ForestConfig
from repro.core import interpolants as itp
from repro.data.store import DatasetStore
from repro.forest.binning import edges_with_sentinel, pack_codes, transform
from repro.forest.boosting import fit_ensemble
from repro.obs import default_registry, default_tracer
from repro.tabgen.artifacts import (RESULT_FIELDS, ForestArtifacts,
                                    rescale)
from repro.train import checkpoint as _ckpt


def weighted_edges(x, w, n_bins: int):
    """Quantile edges over the rows with positive weight (padded rows excluded).

    x: [n, p]; w: [n]. Returns [p, n_bins - 1] fp32.
    """
    big = jnp.where(w[:, None] > 0, x, jnp.inf)
    s = jnp.sort(big, axis=0)
    n_real = jnp.sum(w > 0).astype(jnp.float32)
    qs = jnp.arange(1, n_bins, dtype=jnp.float32) / n_bins
    idx = jnp.clip((qs * (n_real - 1.0)).astype(jnp.int32), 0,
                   x.shape[0] - 1)
    return jnp.transpose(s[idx])


def prepare_classes(X: np.ndarray, y: Optional[np.ndarray],
                    row_chunk: int = 65536, stats=None):
    """Gather rows by class into dense padded [n_y, n_max, p] blocks with
    per-class min-max scalers (Issue 5: static-shape blocks, no boolean
    masks inside the device program).

    Class stats come from one chunked streaming pass
    (:func:`class_stats_streaming`) and rows are rescaled + written
    straight into the padded blocks chunk by chunk, so peak extra memory
    is the padded ``[n_y, n_max, p]`` output plus one row chunk — the
    previous implementation first materialised a full class-sorted fp32
    copy of X (argsort + fancy index), doubling the transient footprint.
    Bit-identical output: within-class row order is the original row order
    either way (the old sort was stable).

    ``stats`` (classes, counts, mins, maxs) skips the streaming stats pass
    and pins the per-class scalers — the warm-start path passes the *base
    model's* mins/maxs here so extension rows land in the exact model space
    the base trees route in (``counts`` must still describe this data).

    Returns (Xc, Wc, classes, counts, mins, maxs).
    """
    if not hasattr(X, "shape"):      # plain sequences still accepted
        X = np.asarray(X, np.float32)
    n, p = X.shape
    if y is None:
        y = np.zeros((n,), np.int64)
    y = np.asarray(y)
    if stats is None:
        classes, counts, mins, maxs = class_stats_streaming(X, y, row_chunk)
    else:
        classes, counts, mins, maxs = stats
    n_y = len(classes)
    n_max = int(counts.max())
    Xc = np.zeros((n_y, n_max, p), np.float32)
    Wc = np.zeros((n_y, n_max), np.float32)
    pos = np.zeros((n_y,), np.int64)
    for s in range(0, n, row_chunk):
        xb = np.asarray(X[s:s + row_chunk], np.float32)  # Issue 7: fp32
        cid = np.searchsorted(classes, y[s:s + row_chunk])
        for i in np.unique(cid):
            rows = rescale(xb[cid == i], mins[i], maxs[i])
            Xc[i, pos[i]:pos[i] + len(rows)] = rows
            pos[i] += len(rows)
    for i, c in enumerate(counts):
        Xc[i, c:] = Xc[i, 0] if c else 0.0   # repeat-first-row padding
        Wc[i, :c] = 1.0
    return Xc, Wc, classes, counts, mins, maxs


def class_stats_streaming(X, y, row_chunk: int = 65536):
    """Classes / counts / per-class min-max scalers in one streaming pass
    over row chunks — never materialises a class-sorted or padded copy of X
    (the sharded-trainer replacement for :func:`prepare_classes`).
    """
    n, p = X.shape
    if y is None:
        y = np.zeros((n,), np.int64)
    classes = np.unique(np.asarray(y))
    n_y = len(classes)
    counts = np.zeros((n_y,), np.int64)
    mins = np.full((n_y, p), np.inf, np.float32)
    maxs = np.full((n_y, p), -np.inf, np.float32)
    for s in range(0, n, row_chunk):
        xb = np.asarray(X[s:s + row_chunk], np.float32)
        cid = np.searchsorted(classes, np.asarray(y[s:s + row_chunk]))
        for i in np.unique(cid):
            sel = xb[cid == i]
            counts[i] += len(sel)
            mins[i] = np.minimum(mins[i], sel.min(axis=0))
            maxs[i] = np.maximum(maxs[i], sel.max(axis=0))
    return classes, counts, mins, maxs


# ---------------------------------------------------------------------------
# warm start (the incremental freshness loop)
# ---------------------------------------------------------------------------

def _check_warm_start(base: ForestArtifacts, fcfg: ForestConfig,
                      p: int) -> None:
    """Refuse an extension whose config/data can't continue ``base``.

    Every :class:`ForestConfig` field but ``n_trees`` must match (the trees
    being replayed were grown under those hyperparameters), ``n_trees`` must
    strictly grow, and the feature count must agree. Errors name every
    differing field with both values.
    """
    bc = dataclasses.asdict(base.config)
    nc = dataclasses.asdict(fcfg)
    diffs = [k for k in nc if k != "n_trees" and bc.get(k) != nc[k]]
    if diffs:
        raise ValueError(
            "warm_start config mismatch — an extension may only change "
            "n_trees; differing fields: " + "; ".join(
                f"{k}: base={bc.get(k)!r} != new={nc[k]!r}" for k in diffs))
    if fcfg.n_trees <= base.config.n_trees:
        raise ValueError(
            f"warm_start needs n_trees > the base model's "
            f"{base.config.n_trees} (got {fcfg.n_trees}); use "
            "extend_artifacts(..., extra_trees=K) to grow by K rounds")
    if base.p != p:
        raise ValueError(f"warm_start base was fit on p={base.p} features "
                         f"but this data has p={p}")


def _check_warm_classes(base: ForestArtifacts, classes) -> None:
    """The extension data's label set must be exactly the base model's —
    each (timestep, class) ensemble continues an existing one; a new class
    would need ensembles that don't exist yet (full refit territory)."""
    if not np.array_equal(np.asarray(classes), np.asarray(base.classes)):
        raise ValueError(
            f"warm_start class mismatch: base model has classes "
            f"{np.asarray(base.classes).tolist()} but this data has "
            f"{np.asarray(classes).tolist()}; extension data must cover "
            "exactly the base label set (retrain from scratch otherwise)")


def _warm_host_arrays(base: ForestArtifacts):
    """Base model buffers as host numpy, in ``fit_boosted`` warm order:
    (feat, thr_val, leaf, val_curve, best_round), each ``[n_t, n_y, n_sub,
    ...]`` — sliced per (timestep, class) cell by the batch drivers."""
    return tuple(np.asarray(getattr(base, f)) for f in
                 ("feat", "thr_val", "leaf", "val_curve", "best_round"))


def _build_lineage(X, n_rows: int, p: int, fcfg: ForestConfig,
                   base: Optional[ForestArtifacts]) -> dict:
    """Data provenance recorded on the trained artifacts (and persisted in
    the save sidecar): enough for a serving host to detect a stale
    model-vs-store pairing at swap time."""
    lin = {"rows": int(n_rows), "p": int(p), "store": None, "base": None}
    if isinstance(X, DatasetStore):
        lin["store"] = {"fingerprint": X.fingerprint,
                        "version": int(X.version),
                        "n_rows": int(X.n_rows)}
    if base is not None:
        # one level of history: the base's own lineage minus *its* base,
        # so a nightly refresh chain doesn't nest without bound
        prev = {k: v for k, v in (base.lineage or {}).items() if k != "base"}
        lin["base"] = {
            "round_range": [int(base.config.n_trees), int(fcfg.n_trees)],
            "lineage": prev or None,
        }
    return lin


def extend_artifacts(base: ForestArtifacts, X, y=None, *, extra_trees: int,
                     **kwargs) -> ForestArtifacts:
    """Grow ``base`` by ``extra_trees`` boosting rounds per ensemble.

    Boosting is additive, so an extension from round R to R + K never
    recomputes the first R rounds: the base trees seed every ensemble and
    their running predictions are replayed (see
    :mod:`repro.forest.boosting`). The base's per-class scalers are reused
    — extension rows are binned in the model space the base trees route in
    — and on the *same* data the result is bit-identical to
    :func:`fit_artifacts` run straight to R + K with the same seed.

    ``X`` may be fresh (e.g. a :class:`~repro.data.store.DatasetStore`
    grown by :meth:`~repro.data.store.DatasetStore.append`); the new rounds
    then fit the residuals of the base trees on the new data. ``kwargs``
    forward to :func:`fit_artifacts` (mesh, checkpoint_dir, seed, ...).
    """
    if extra_trees <= 0:
        raise ValueError(f"extra_trees must be positive, got {extra_trees}")
    fcfg = dataclasses.replace(
        base.config, n_trees=base.config.n_trees + int(extra_trees))
    return fit_artifacts(X, y, fcfg, warm_start=base, **kwargs)


# ---------------------------------------------------------------------------
# checkpoint manifest
# ---------------------------------------------------------------------------

def _manifest_fingerprint(fcfg: ForestConfig, *, n_t: int, n_y: int,
                          batch_size: int, n_rows: int, p: int,
                          trainer: str, warm_rounds: int = 0) -> dict:
    """Everything that determines which ensemble lands in which batch file.

    Resuming under a different ``ensembles_per_batch`` or ``ForestConfig``
    used to silently mix stale ``batch_*.npz`` files with fresh ones; now the
    manifest pins the full grid layout and the config, and a mismatch refuses
    to resume. Deliberately *not* fingerprinted: the seed (resume may finish
    another run's grid — completed batches never retrain) and the sharded
    trainer's mesh shape (batches are whole trained ensembles, so a
    checkpoint may be resumed on a different device count — elastic resume).

    A warm-start fit adds ``warm_start: <base round count>`` so its batch
    files never mix with a cold run's; cold fingerprints are unchanged
    (byte-compatible with pre-warm-start manifests).
    """
    fp = {
        "config": dataclasses.asdict(fcfg),
        "grid": [n_t, n_y],
        "ensembles_per_batch": batch_size,
        "data_shape": [int(n_rows), int(p)],
        "trainer": trainer,
    }
    if warm_rounds:
        fp["warm_start"] = int(warm_rounds)
    return fp


def _manifest_batch_size(checkpoint_dir: str) -> Optional[int]:
    """The batch size an existing checkpoint was written with, if any."""
    path = os.path.join(checkpoint_dir, "manifest.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f).get("fingerprint", {}).get("ensembles_per_batch")


def _run_grid_batches(run_batch, grid, bs: int, *,
                      checkpoint_dir: Optional[str], resume: bool,
                      fingerprint: dict, warm_base: Optional[dict] = None):
    """Drive the (timestep, class) grid in batches with checkpoint/resume.

    ``run_batch(chunk)`` trains ``chunk`` (a list of (ti, yi)) and returns
    ``{field: np.ndarray}`` with leading dim ``len(chunk)``. Shared by the
    single-device and serial sharded trainers, so both get the same Issue-3
    streaming checkpoints and the same manifest safety (the pipelined
    driver below shares the :class:`~repro.train.checkpoint.GridManifest`
    too, so the three paths are resume-compatible).

    ``warm_base`` (a warm-start fit's base-run descriptor) lets
    :meth:`GridManifest.load_done` accept — rather than refuse — a
    checkpoint dir holding the *base* model's committed batches: the
    extension retrains every batch and overwrites them in place.
    """
    manifest = (_ckpt.GridManifest(checkpoint_dir, fingerprint,
                                   warm_base=warm_base)
                if checkpoint_dir else None)
    done = manifest.load_done(resume) if manifest else set()

    results = {}
    for b0 in range(0, len(grid), bs):
        chunk = grid[b0:b0 + bs]
        key_id = (b0, len(chunk))
        if key_id in done:
            res_np = _ckpt.read_batch_npz(checkpoint_dir, b0)
        else:
            with default_tracer().span("fit.batch", batch=b0,
                                       ensembles=len(chunk)):
                res_np = run_batch(chunk)
            if manifest:   # Issue 3: stream to disk, checkpointed
                _ckpt.write_batch_npz(checkpoint_dir, b0, res_np)
                manifest.mark_done(key_id)
        for j, (ti, yi) in enumerate(chunk):
            results[(ti, yi)] = {k: v[j] for k, v in res_np.items()}
    return results


# ---------------------------------------------------------------------------
# pipelined (double-buffered) grid driver
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    """Knobs of the double-buffered distributed fit loop.

    ``prefetch_depth`` bounds both inter-stage queues: the prefetch thread
    may run at most this many batches of input-build ahead of the dispatch
    loop (1 = classic double buffering), and at most this many dispatched
    batches of device results may be in flight awaiting the writer — the
    backpressure that keeps host memory bounded.

    ``async_checkpoint`` moves the ``BoostResult`` gather and the
    ``batch_*.npz`` / manifest writes onto the writer thread. Disable it to
    get the PR-2 strictly-synchronous writes (inputs still prefetch) — e.g.
    when the checkpoint filesystem misbehaves under concurrent fsyncs or
    when debugging with deterministic thread interleavings.
    """
    prefetch_depth: int = 2
    async_checkpoint: bool = True


#: Wall/overlap accounting of the most recent pipelined fit in this process
#: (written once, after the pipeline drains — read by bench_training to
#: report overlap efficiency; not part of the stable API).
LAST_PIPELINE_STATS: dict = {}

_STOP = object()


def _run_grid_batches_pipelined(dispatch, collect, grid, bs: int, *,
                                checkpoint_dir: Optional[str], resume: bool,
                                fingerprint: dict, prefetch,
                                pcfg: PipelineConfig,
                                warm_base: Optional[dict] = None):
    """Producer/consumer version of :func:`_run_grid_batches`.

    Three stages over the same batch sequence, bit-identical results:

    * prefetch thread — ``prefetch(chunk) -> inputs`` (host-only input
      build; skipped for batches the manifest already has);
    * main thread — ``dispatch(inputs) -> device result`` (asynchronous
      under jit, so dispatching batch ``b+1`` does not wait for ``b``);
    * writer thread — ``collect(result, n) -> {field: np}`` (the deferred
      ``block_until_ready`` + device→host gather) followed by the durable
      ``batch_*.npz`` write and manifest update.

    Any stage failing sets a shared stop event, the queues drain, and the
    first error re-raises on the caller's thread. The manifest is only ever
    updated after its batch file is durably committed, so a crash between
    writer flushes resumes from the last committed batch.
    """
    manifest = (_ckpt.GridManifest(checkpoint_dir, fingerprint,
                                   warm_base=warm_base)
                if checkpoint_dir else None)
    done = manifest.load_done(resume) if manifest else set()

    batches = [(b0, grid[b0:b0 + bs]) for b0 in range(0, len(grid), bs)]
    depth = max(1, pcfg.prefetch_depth)
    in_q: queue.Queue = queue.Queue(maxsize=depth)
    out_q: queue.Queue = queue.Queue(maxsize=depth)
    stop = threading.Event()
    errors: list = []
    batch_np: dict = {}
    stats = {"writer_busy_s": 0.0, "prefetch_busy_s": 0.0,
             "n_batches": len(batches), "n_cached": 0,
             "prefetch_depth": depth,
             "async_checkpoint": pcfg.async_checkpoint}
    # stage timing comes from fit.prefetch / fit.dispatch / fit.write spans
    # (busy_s below are their summed durations); the histograms export the
    # same numbers through the process-wide registry for --metrics-dump
    tracer = default_tracer()
    _m = default_registry()
    h_prefetch = _m.histogram(
        "fit_prefetch_seconds", "Per-batch host input-build time "
        "(fit.prefetch span durations)")
    h_dispatch = _m.histogram(
        "fit_dispatch_seconds", "Per-batch async dispatch-enqueue time "
        "(fit.dispatch span durations; device time overlaps the pipeline)")
    h_write = _m.histogram(
        "fit_write_seconds", "Per-batch gather + checkpoint-commit time "
        "(fit.write span durations)")
    c_batches = _m.counter(
        "fit_batches", "Ensemble-grid batches by disposition",
        ("status",))

    def _put(q, item):
        """Bounded put that aborts when another stage failed."""
        while not stop.is_set():
            try:
                q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _get(q):
        while not stop.is_set():
            try:
                return q.get(timeout=0.05)
            except queue.Empty:
                continue
        return _STOP

    def _fail(exc):
        errors.append(exc)
        stop.set()

    def _producer():
        try:
            for b0, chunk in batches:
                if (b0, len(chunk)) in done:
                    item = (b0, chunk, None)     # cached: nothing to build
                else:
                    with tracer.span("fit.prefetch", batch=b0) as sp:
                        inputs = prefetch(chunk)
                    stats["prefetch_busy_s"] += sp.duration_s
                    h_prefetch.observe(sp.duration_s)
                    item = (b0, chunk, inputs)
                if not _put(in_q, item):
                    return
            _put(in_q, _STOP)
        except Exception as exc:  # noqa: BLE001 — re-raised on main thread
            _fail(exc)

    def _finish(b0, chunk, res_dev):
        """Writer-stage work: deferred sync + gather + durable commit."""
        with tracer.span("fit.write", batch=b0) as sp:
            res_np = collect(res_dev, len(chunk))
            if manifest:
                _ckpt.write_batch_npz(checkpoint_dir, b0, res_np)
                manifest.mark_done((b0, len(chunk)))
            batch_np[b0] = res_np
        stats["writer_busy_s"] += sp.duration_s
        h_write.observe(sp.duration_s)

    def _writer():
        try:
            while True:
                item = _get(out_q)
                if item is _STOP:
                    return
                _finish(*item)
        except Exception as exc:  # noqa: BLE001 — re-raised on main thread
            _fail(exc)

    wall0 = time.perf_counter()
    threads = [threading.Thread(target=_producer, name="tabgen-prefetch",
                                daemon=True)]
    if pcfg.async_checkpoint:
        threads.append(threading.Thread(target=_writer, name="tabgen-writer",
                                        daemon=True))
    for t in threads:
        t.start()
    completed = False
    try:
        while True:
            item = _get(in_q)
            if item is _STOP:
                break
            b0, chunk, inputs = item
            if inputs is None:    # committed by a previous (or this) run
                batch_np[b0] = _ckpt.read_batch_npz(checkpoint_dir, b0)
                stats["n_cached"] += 1
                c_batches.inc(1, status="cached")
                continue
            with tracer.span("fit.dispatch", batch=b0) as sp:
                res_dev = dispatch(inputs)   # async: returns device futures
            h_dispatch.observe(sp.duration_s)
            c_batches.inc(1, status="dispatched")
            if pcfg.async_checkpoint:
                if not _put(out_q, (b0, chunk, res_dev)):
                    break
            else:
                _finish(b0, chunk, res_dev)
        if pcfg.async_checkpoint and not stop.is_set():
            _put(out_q, _STOP)
        completed = True
    except Exception as exc:  # noqa: BLE001 — unified error path
        _fail(exc)
    finally:
        # BaseException (KeyboardInterrupt, GeneratorExit) skips the except
        # above: stop the stages here so the joins below can't hang and no
        # polling daemon thread outlives the fit pinning the row shards
        if not completed and not stop.is_set():
            stop.set()
        for t in threads:
            t.join()
    if errors:
        raise errors[0]
    stats["wall_s"] = time.perf_counter() - wall0
    LAST_PIPELINE_STATS.clear()
    LAST_PIPELINE_STATS.update(stats)

    results = {}
    for b0, chunk in batches:
        res_np = batch_np[b0]
        for j, (ti, yi) in enumerate(chunk):
            results[(ti, yi)] = {k: v[j] for k, v in res_np.items()}
    return results


# ---------------------------------------------------------------------------
# single-device trainer
# ---------------------------------------------------------------------------

def ensemble_inputs(fcfg: ForestConfig, Xc, Wc, root, t, y_idx, eid):
    """Noised inputs and codes of one (t, y) ensemble of the single-device
    trainer; transient by design (built inside the jitted fit step).

    Returns ``(codes, tgt, w, edges, codes_v, tgt_v, x_t, x_t_v)`` over the
    class's ``n_max * duplicate_k`` duplicated rows.
    """
    K = fcfg.duplicate_k
    x0d = jnp.repeat(Xc[y_idx], K, axis=0)               # [mK, p]
    wd = jnp.repeat(Wc[y_idx], K, axis=0)
    k_tr = jax.random.fold_in(root, eid * 2)
    k_va = jax.random.fold_in(root, eid * 2 + 1)
    _, xt, tgt = itp.sample_bridge(k_tr, x0d, fcfg.method, t, fcfg.sigma)
    edges = weighted_edges(xt, wd, fcfg.n_bins)
    codes = transform(xt, edges)
    _, xtv, tgtv = itp.sample_bridge(k_va, x0d, fcfg.method, t, fcfg.sigma)
    codes_v = transform(xtv, edges)
    if fcfg.int8_codes:   # QuantileDMatrix-style narrow storage
        codes = pack_codes(codes, fcfg.n_bins)
        codes_v = pack_codes(codes_v, fcfg.n_bins)
    return codes, tgt, wd, edges, codes_v, tgtv, xt, xtv


@functools.lru_cache(maxsize=16)
def single_fit_program(fcfg: ForestConfig, warm: bool = False):
    """The jitted single-device fit step over a batch of ensembles.

    ``fn(Xc [n_y, n_max, p], Wc [n_y, n_max], root key, t [b], y [b],
    eid [b]) -> BoostResult`` stacked over the ``b`` ensembles of the batch
    (``warm=True`` adds the five base-model slices ``feat [b, n_sub, R, H],
    thr_val, leaf, val_curve, best_round``). The padded class blocks are
    arguments, not closure constants, so the program compiles once per
    (config, shapes) and can be lowered from shapes alone — the memory
    check that sizes ``ensembles_per_batch`` for a device does exactly that.
    """
    def fit_one(Xc, Wc, root, t, y_idx, eid, *warm_slice):
        """Train (or, with a base-model slice, continue) one ensemble."""
        codes, tgt, wd, edges, codes_v, tgtv, xt, xtv = \
            ensemble_inputs(fcfg, Xc, Wc, root, t, y_idx, eid)
        if not warm_slice:
            return fit_ensemble(codes, tgt, wd, edges_with_sentinel(edges),
                                codes_v, tgtv, wd, fcfg)
        return fit_ensemble(codes, tgt, wd, edges_with_sentinel(edges),
                            codes_v, tgtv, wd, fcfg, warm=warm_slice,
                            x_raw=xt, val_raw=xtv)

    in_axes = (None, None, None, 0, 0, 0) + ((0,) * 5 if warm else ())
    return jax.jit(jax.vmap(fit_one, in_axes=in_axes))


def fit_artifacts(X, y=None, fcfg: ForestConfig = ForestConfig(), *,
                  seed: int = 0, checkpoint_dir: Optional[str] = None,
                  resume: bool = False, ensembles_per_batch: int = 0,
                  mesh=None, data_axes: Optional[Tuple[str, ...]] = None,
                  model_axis: str = "model", row_chunk: int = 65536,
                  pipeline="auto",
                  warm_start: Optional[ForestArtifacts] = None
                  ) -> ForestArtifacts:
    """Train all (timestep, class) ensembles; returns portable artifacts.

    One jitted+vmapped fit program trains ``ensembles_per_batch`` ensembles
    per dispatch; batches stream to ``checkpoint_dir`` (Issue 3) and
    ``resume=True`` restarts from the manifest.

    ``mesh`` selects the trainer: ``None`` (default) is the single-device
    path; a :class:`jax.sharding.Mesh` routes through the shard_map trainer
    with rows sharded over ``data_axes`` and the ensemble grid over
    ``model_axis``; the string ``"auto"`` builds a mesh from every visible
    device (``repro.launch.mesh.auto_forest_mesh``) and falls back to the
    single-device path when there is only one.

    ``pipeline`` applies to the sharded trainer: ``"auto"`` (default) runs
    the double-buffered pipeline with :class:`PipelineConfig` defaults, an
    explicit :class:`PipelineConfig` pins its knobs, and ``None`` keeps the
    serial PR-2 loop. Both produce bit-identical artifacts for a fixed seed
    and share one manifest format, so a serial checkpoint resumes under the
    pipeline (and vice versa) — the execution style, like the mesh shape,
    is deliberately not fingerprinted. The single-device trainer ignores
    ``pipeline`` (its batches have no host/device overlap to hide).

    Out-of-core data: ``X`` may be a :class:`repro.data.store.DatasetStore`
    (built by :func:`repro.data.store.ingest` / ``repro.launch.ingest``).
    Store-backed fits always run the sharded trainer — when no mesh is
    given (or ``"auto"`` resolves to a single device) a 1x1 mesh is built,
    because the padded single-device route would materialise the dataset.
    Class stats and scalers are read from the store manifest (no fit-time
    stats pass) and row shards are gathered straight from disk; ``y``
    defaults to the store's own labels. A store-backed fit is bit-identical
    to the in-memory sharded fit of the same rows on the same mesh, and
    their checkpoints interoperate.

    ``warm_start`` seeds every ensemble from an existing
    :class:`ForestArtifacts` (same config up to ``n_trees``, same feature
    count and label set) and continues boosting from its trees instead of
    round 0 — see :func:`extend_artifacts` for the usual entry point. The
    base model's per-class scalers are reused so extension rows are binned
    in the space the base trees route in; on identical data the result is
    bit-identical to a cold fit run straight to the new ``n_trees``.
    """
    if isinstance(mesh, str):
        if mesh != "auto":
            raise ValueError(f"mesh={mesh!r}: expected a Mesh, None, or "
                             "'auto'")
        from repro.launch.mesh import auto_forest_mesh
        mesh = auto_forest_mesh()
    if mesh is None and isinstance(X, DatasetStore):
        # out-of-core route: the sharded trainer streams per-device row
        # slices from the store's shards; the single-device route would
        # densify the whole dataset into padded class blocks
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((1, 1))
    # validate on every path: a malformed pipeline knob should fail loudly
    # on a single-device box too, not first on the production mesh
    if pipeline == "auto":
        pipeline = PipelineConfig()
    elif not (pipeline is None or isinstance(pipeline, PipelineConfig)):
        raise ValueError(f"pipeline={pipeline!r}: expected 'auto', "
                         "None, or a PipelineConfig")
    if mesh is not None:
        return _fit_artifacts_sharded(
            X, y, fcfg, mesh, seed=seed, checkpoint_dir=checkpoint_dir,
            resume=resume, ensembles_per_batch=ensembles_per_batch,
            data_axes=data_axes, model_axis=model_axis, row_chunk=row_chunk,
            pipeline=pipeline, warm_start=warm_start)

    stats = None
    if warm_start is not None:
        Xs = X if hasattr(X, "shape") else np.asarray(X, np.float32)
        _check_warm_start(warm_start, fcfg, int(np.shape(Xs)[1]))
        classes, counts, _, _ = class_stats_streaming(Xs, y, row_chunk)
        _check_warm_classes(warm_start, classes)
        # pin the base scalers: extension rows must land in the model space
        # the base trees were grown in (fresh counts keep label sampling
        # honest on appended data)
        stats = (classes, counts, np.asarray(warm_start.mins, np.float32),
                 np.asarray(warm_start.maxs, np.float32))
    Xc, Wc, classes, counts, mins, maxs = prepare_classes(X, y, stats=stats)
    n_y, n_max, p = Xc.shape
    Xc_d = jnp.asarray(Xc)
    Wc_d = jnp.asarray(Wc)
    ts = np.asarray(itp.timesteps(fcfg.method, fcfg.n_t, fcfg.eps_diff,
                                  fcfg.t_schedule))
    root = jax.random.PRNGKey(seed)
    fit_batch = single_fit_program(fcfg, warm=warm_start is not None)
    if warm_start is not None:
        Wfeat, Wthr, Wleaf, Wvc, Wbr = _warm_host_arrays(warm_start)

    grid = [(ti, yi) for ti in range(fcfg.n_t) for yi in range(n_y)]
    bs = ensembles_per_batch or max(1, min(len(grid), 8))

    def run_batch(chunk):
        t_arr = jnp.asarray([ts[ti] for ti, _ in chunk], jnp.float32)
        y_arr = jnp.asarray([yi for _, yi in chunk], jnp.int32)
        e_arr = jnp.asarray([ti * n_y + yi for ti, yi in chunk], jnp.int32)
        if warm_start is None:
            res = fit_batch(Xc_d, Wc_d, root, t_arr, y_arr, e_arr)
        else:
            tis = [ti for ti, _ in chunk]
            yis = [yi for _, yi in chunk]
            res = fit_batch(Xc_d, Wc_d, root, t_arr, y_arr, e_arr,
                            jnp.asarray(Wfeat[tis, yis]),
                            jnp.asarray(Wthr[tis, yis]),
                            jnp.asarray(Wleaf[tis, yis]),
                            jnp.asarray(Wvc[tis, yis]),
                            jnp.asarray(Wbr[tis, yis]))
        return {k: np.asarray(getattr(res, k)) for k in RESULT_FIELDS}

    warm_rounds = warm_start.config.n_trees if warm_start else 0
    fingerprint = _manifest_fingerprint(
        fcfg, n_t=fcfg.n_t, n_y=n_y, batch_size=bs,
        n_rows=np.shape(X)[0], p=p, trainer="single",
        warm_rounds=warm_rounds)
    warm_base = (None if warm_start is None else
                 {"config": dataclasses.asdict(warm_start.config),
                  "grid": [fcfg.n_t, n_y]})
    results = _run_grid_batches(run_batch, grid, bs,
                                checkpoint_dir=checkpoint_dir, resume=resume,
                                fingerprint=fingerprint, warm_base=warm_base)
    arts = ForestArtifacts.from_grid_results(results, fcfg.n_t, n_y, mins,
                                             maxs, classes, counts, fcfg)
    arts.lineage = _build_lineage(X, np.shape(X)[0], p, fcfg, warm_start)
    return arts


# ---------------------------------------------------------------------------
# sharded trainer (the paper's §3.3 scaling story, TPU-native)
# ---------------------------------------------------------------------------

def _fit_artifacts_sharded(X, y, fcfg: ForestConfig, mesh, *, seed: int,
                           checkpoint_dir: Optional[str], resume: bool,
                           ensembles_per_batch: int,
                           data_axes: Optional[Tuple[str, ...]],
                           model_axis: str, row_chunk: int,
                           pipeline: Optional[PipelineConfig],
                           warm_start: Optional[ForestArtifacts] = None
                           ) -> ForestArtifacts:
    """shard_map training from host data to :class:`ForestArtifacts`.

    Rows (rescaled per class, weight-masked class conditioning — no padded
    [n_y, n_max, p] blocks) are sharded over the data axes and streamed to
    the devices chunk by chunk via ``build_row_shards``: each device uploads
    only its own row slice, so X never has to fit on one device. The
    (timestep, class) grid is sharded over the model axis in batches of
    ``ensembles_per_batch`` (rounded up to the model-axis size), reusing the
    same checkpoint/resume manifest as the single-device path.

    With a :class:`PipelineConfig` the batch loop runs double-buffered: the
    input build (row-shard upload on first use + per-batch keys) happens on
    a prefetch thread while the previous batch executes, and the gather +
    checkpoint writes happen on a writer thread; ``pipeline=None`` is the
    serial loop. Batches are bit-identical either way.
    """
    from repro.forest.distributed import (build_batch_inputs,
                                          build_grid_key_table,
                                          build_row_shards,
                                          make_distributed_fit)

    # keep memmap/store inputs lazy: only per-shard chunks are ever copied
    if isinstance(X, DatasetStore):
        X_np = X                       # row gathers read straight from disk
        n, p = X.shape
        if y is None:
            y = X.labels()
            # one manifest read replaces the whole fit-time stats pass (the
            # values are exactly what class_stats_streaming would recompute)
            classes, counts, mins, maxs = X.class_stats()
        else:
            # explicit labels override the store's own: the manifest stats
            # were computed under the store's grouping, so re-stream the
            # per-class scalers in chunked reads over the shards
            y = np.asarray(y)
            classes, counts, mins, maxs = class_stats_streaming(X, y,
                                                                row_chunk)
    else:
        X_np = X if isinstance(X, np.ndarray) else np.asarray(X, np.float32)
        n, p = X_np.shape
        if y is None:
            y = np.zeros((n,), np.int64)
        classes, counts, mins, maxs = class_stats_streaming(X_np, y,
                                                            row_chunk)
    if warm_start is not None:
        _check_warm_start(warm_start, fcfg, p)
        _check_warm_classes(warm_start, classes)
        # base scalers, not this data's: the replayed trees route in the
        # base model's [-1, 1] space (fresh counts stay — label sampling)
        mins = np.asarray(warm_start.mins, np.float32)
        maxs = np.asarray(warm_start.maxs, np.float32)
    n_y = len(classes)
    cid_full = np.searchsorted(classes, np.asarray(y)).astype(np.int32)

    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if model_axis not in axis_sizes:
        raise ValueError(f"mesh has no {model_axis!r} axis: "
                         f"{mesh.axis_names}")
    if data_axes is None:
        data_axes = tuple(a for a in mesh.axis_names if a != model_axis)
    data_axes = tuple(data_axes)   # hashable for the trainer cache
    m_size = axis_sizes[model_axis]

    # Deterministic shuffle so every row shard sees every class: the sketch
    # quantiles gather the head of each shard, and a class-sorted input on a
    # small mesh would starve some ensembles' sketches entirely.
    perm = np.random.default_rng(seed).permutation(n)

    # row-shard build is deferred into the input-build stage: the pipelined
    # driver runs it on the prefetch thread (overlapping the host→device
    # upload with dispatch-side work), and an all-cached resume never pays
    # for it at all
    row_state: dict = {}

    def rows():
        if "arrs" not in row_state:
            row_state["arrs"] = build_row_shards(
                mesh, X_np, cid_full, mins, maxs, perm, data_axes)
        return row_state["arrs"]

    ts = np.asarray(itp.timesteps(fcfg.method, fcfg.n_t, fcfg.eps_diff,
                                  fcfg.t_schedule))
    root = jax.random.PRNGKey(seed)
    grid = [(ti, yi) for ti in range(fcfg.n_t) for yi in range(n_y)]
    bs = ensembles_per_batch or max(m_size, min(len(grid), 8))
    if not ensembles_per_batch and resume and checkpoint_dir:
        # elastic resume: the batch size is part of the checkpoint layout,
        # so when the caller didn't pin one, inherit the manifest's rather
        # than deriving a (possibly different) default from the new mesh
        bs = _manifest_batch_size(checkpoint_dir) or bs
    bs = -(-bs // m_size) * m_size          # model-axis divisibility
    if resume and checkpoint_dir:
        stale = _manifest_batch_size(checkpoint_dir)
        if stale and stale != bs:
            raise ValueError(
                f"checkpoint at {checkpoint_dir} was written with "
                f"ensembles_per_batch={stale} but this run resolves to "
                f"{bs} (the {m_size}-wide model axis needs a multiple of "
                f"{m_size}); resume with ensembles_per_batch={stale} on a "
                "compatible mesh, or retrain with resume=False.")

    warm_rounds = warm_start.config.n_trees if warm_start else 0
    fit = make_distributed_fit(mesh, fcfg, data_axes=data_axes,
                               model_axis=model_axis,
                               warm_rounds=warm_rounds)
    if warm_start is not None:
        Wfeat, Wthr, Wleaf, Wvc, Wbr = _warm_host_arrays(warm_start)

    def warm_slices(chunk):
        """Base-model slices of one (padded) batch: [bs, n_sub, R, ...]."""
        tis = [ti for ti, _ in chunk]
        yis = [yi for _, yi in chunk]
        return (Wfeat[tis, yis], Wthr[tis, yis], Wleaf[tis, yis],
                Wvc[tis, yis], Wbr[tis, yis])

    def pad(chunk):
        # pad the tail batch by repeating entries: one compiled program for
        # every dispatch; the duplicates are sliced off before writing
        return chunk + [chunk[-1]] * (bs - len(chunk))

    fingerprint = _manifest_fingerprint(
        fcfg, n_t=fcfg.n_t, n_y=n_y, batch_size=bs, n_rows=n, p=p,
        trainer="sharded", warm_rounds=warm_rounds)
    warm_base = (None if warm_start is None else
                 {"config": dataclasses.asdict(warm_start.config),
                  "grid": [fcfg.n_t, n_y]})

    # one vectorized dispatch for every ensemble's PRNG keys (devices are
    # idle here; values bit-identical to the per-batch fold_in pairs) —
    # both loops slice plain numpy thereafter, and the pipeline's prefetch
    # thread never contends with in-flight batches for device queues
    key_table = build_grid_key_table(root, fcfg.n_t * n_y)

    if pipeline is None:
        def run_batch(chunk):
            padded = pad(chunk)
            t_np, y_np, keys = build_batch_inputs(padded, ts, n_y, root,
                                                  key_table)
            x0_sh, w_sh, c_sh = rows()
            extra = (() if warm_start is None else
                     tuple(jnp.asarray(a) for a in warm_slices(padded)))
            res = fit(x0_sh, w_sh, c_sh, jnp.asarray(t_np),
                      jnp.asarray(y_np), jnp.asarray(keys), *extra)
            # gather per-model-axis shards back to host, drop pad entries
            return {k: np.asarray(getattr(res, k))[:len(chunk)]
                    for k in RESULT_FIELDS}

        results = _run_grid_batches(run_batch, grid, bs,
                                    checkpoint_dir=checkpoint_dir,
                                    resume=resume, fingerprint=fingerprint,
                                    warm_base=warm_base)
    else:
        def prefetch(chunk):
            # input-build stage: row shards (once) + this batch's grid
            # cells (+ the base-model slices when warm starting)
            padded = pad(chunk)
            extra = () if warm_start is None else warm_slices(padded)
            return (rows() + build_batch_inputs(padded, ts, n_y, root,
                                                key_table) + extra)

        def dispatch(inputs):
            x0_sh, w_sh, c_sh = inputs[:3]
            rest = [jnp.asarray(a) for a in inputs[3:]]
            return fit(x0_sh, w_sh, c_sh, *rest)

        def collect(res, n_real):
            # deferred bookkeeping: one explicit sync for the whole batch,
            # then per-model-axis shards gather back to host; pad entries
            # are sliced off before the batch is written
            res = jax.block_until_ready(res)
            return {k: np.asarray(getattr(res, k))[:n_real]
                    for k in RESULT_FIELDS}

        results = _run_grid_batches_pipelined(
            dispatch, collect, grid, bs, checkpoint_dir=checkpoint_dir,
            resume=resume, fingerprint=fingerprint, prefetch=prefetch,
            pcfg=pipeline, warm_base=warm_base)
    arts = ForestArtifacts.from_grid_results(results, fcfg.n_t, n_y, mins,
                                             maxs, classes, counts, fcfg)
    arts.lineage = _build_lineage(X, n, p, fcfg, warm_start)
    return arts
