"""Incremental freshness loop (Issue 9): DatasetStore.append, warm-start
forest extension, lineage metadata, and the live hot-swap path.

The tentpole acceptance lives here: extending a base model by K rounds is
bit-identical to fitting R + K rounds from scratch on the same data (in
memory and store-backed), appends version the store without disturbing
open readers, and the admin reload endpoint swaps a grown model into a
serving registry with zero dropped requests.
"""
import dataclasses
import json
import threading
import time

import numpy as np
import pytest

from repro.config import ForestConfig
from repro.launch.mesh import make_mesh
from repro.data.store import DatasetStore, ingest
from repro.tabgen import (TabularGenerator, extend_artifacts, fit_artifacts)
from repro.tabgen.fitting import class_stats_streaming
from repro.train.checkpoint import GridManifest

FIELDS = ("feat", "thr_val", "leaf", "best_round", "rounds_run", "val_curve",
          "mins", "maxs")

FCFG = ForestConfig(n_t=2, duplicate_k=3, n_trees=6, max_depth=2, n_bins=8,
                    reg_lambda=1.0)


def _assert_same(a, b):
    for f in FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)), err_msg=f)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1, 1))


@pytest.fixture(scope="module")
def small_data():
    rng = np.random.default_rng(3)
    X = rng.normal(size=(96, 3)).astype(np.float32)
    y = (rng.random(96) > 0.5).astype(np.int64)
    return X, y


def _batches(X, y, k=24):
    for s in range(0, len(X), k):
        yield X[s:s + k], y[s:s + k]


# ---------------------------------------------------------------------------
# tentpole acceptance: extend-by-K == straight fit to R + K, bit for bit
# ---------------------------------------------------------------------------

def test_extend_bit_identical_in_memory(small_data):
    X, y = small_data
    cold = fit_artifacts(X, y, FCFG, seed=5)
    base = fit_artifacts(X, y, dataclasses.replace(FCFG, n_trees=4), seed=5)
    ext = extend_artifacts(base, X, y, extra_trees=2, seed=5)
    assert ext.config.n_trees == 6
    _assert_same(cold, ext)
    # lineage records the continuation point
    assert ext.lineage["base"]["round_range"] == [4, 6]
    assert ext.lineage["rows"] == len(X) and ext.lineage["store"] is None


def test_extend_bit_identical_with_early_stopping(small_data):
    """Early stopping discards rounds past the best validation round; the
    warm start replays only the kept prefix and re-grows the rest — still
    bit-identical to the longer cold fit."""
    X, y = small_data
    fcfg = dataclasses.replace(FCFG, early_stop_rounds=2)
    cold = fit_artifacts(X, y, fcfg, seed=5)
    base = fit_artifacts(X, y, dataclasses.replace(fcfg, n_trees=4), seed=5)
    ext = extend_artifacts(base, X, y, extra_trees=2, seed=5)
    _assert_same(cold, ext)


def test_extend_bit_identical_store_backed(tmp_path, mesh, small_data):
    X, y = small_data
    store = ingest(_batches(X, y), str(tmp_path / "store"), shard_rows=32)
    cold = fit_artifacts(store, None, FCFG, seed=5, mesh=mesh)
    base = fit_artifacts(store, None, dataclasses.replace(FCFG, n_trees=4),
                         seed=5, mesh=mesh)
    ext = extend_artifacts(base, store, extra_trees=2, seed=5, mesh=mesh)
    _assert_same(cold, ext)
    assert ext.lineage["store"]["version"] == 1
    assert ext.lineage["store"]["n_rows"] == len(X)


def test_extend_on_appended_store(tmp_path, mesh, small_data):
    """The production shape: base fit on the store, append fresh rows,
    extend on the grown store — base scalers are pinned so new rounds fit
    residuals in the base model space, and lineage pins the new version."""
    X, y = small_data
    store = ingest(_batches(X[:64], y[:64]), str(tmp_path / "store"),
                   shard_rows=32)
    base = fit_artifacts(store, None, dataclasses.replace(FCFG, n_trees=4),
                         seed=5, mesh=mesh)
    grown = store.append(_batches(X[64:], y[64:]))
    assert (store.n_rows, grown.n_rows) == (64, 96)
    ext = extend_artifacts(base, grown, extra_trees=2, seed=5, mesh=mesh)
    assert ext.config.n_trees == 6
    # base trees are carried over verbatim; scalers stay the base's
    np.testing.assert_array_equal(np.asarray(ext.feat)[..., :4, :],
                                  np.asarray(base.feat))
    np.testing.assert_array_equal(np.asarray(ext.mins),
                                  np.asarray(base.mins))
    assert ext.lineage["store"]["version"] == 2
    assert ext.lineage["base"]["lineage"]["store"]["version"] == 1


# ---------------------------------------------------------------------------
# DatasetStore.append: versioning, reader isolation, crash-resume
# ---------------------------------------------------------------------------

def test_append_versions_and_merges_stats(tmp_path, small_data):
    X, y = small_data
    d = str(tmp_path / "s")
    store = ingest(_batches(X[:64], y[:64]), d, shard_rows=32)
    assert store.version == 1
    grown = store.append(_batches(X[64:], y[64:]))
    assert grown.version == 2 and grown.n_rows == 96
    # merged class stats == one streaming pass over the concatenation
    ref = class_stats_streaming(X, y)
    got = grown.class_stats()
    for a, b in zip(got, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
    # the pre-append reader keeps serving its snapshot (stats included)
    assert store.n_rows == 64 and store.version == 1
    assert len(store.class_stats()[0]) == 2
    # append streams are labelled like the ingest was
    with pytest.raises(ValueError, match="labelled"):
        store.append(iter([X[64:]]))


def test_append_refuses_inflight_and_resumes(tmp_path, small_data):
    X, y = small_data
    d = str(tmp_path / "s")
    ingest(_batches(X[:48], y[:48]), d, shard_rows=16)

    def crashing():
        yield X[48:64], y[48:64]
        raise OSError("disk gone")

    with pytest.raises(OSError):
        DatasetStore(d).append(crashing(), source="nightly")
    # marker is durable: a non-resume append refuses, with row accounting
    with pytest.raises(ValueError, match="unfinished append"):
        DatasetStore(d).append(_batches(X[48:], y[48:]), source="nightly")
    # a resume under a different source refuses too — both sources named
    with pytest.raises(ValueError, match="'nightly'.*'weekly'"):
        DatasetStore(d).append(_batches(X[48:], y[48:]), source="weekly",
                               resume=True)
    grown = DatasetStore(d).append(_batches(X[48:], y[48:], k=16),
                                   source="nightly", resume=True)
    assert grown.n_rows == 96 and grown.version == 2
    ref = class_stats_streaming(X, y)
    np.testing.assert_allclose(np.asarray(grown.class_stats()[1]),
                               np.asarray(ref[1]))
    # retry-after-success: resume with no marker is a no-op reader
    again = DatasetStore(d).append(iter(()), source="nightly", resume=True)
    assert again.n_rows == 96 and again.version == 2


def test_ingest_refusal_names_differing_keys(tmp_path, small_data):
    """Satellite: fingerprint refusals print both fingerprints plus every
    differing key, store- and checkpoint-side alike."""
    X, y = small_data
    d = str(tmp_path / "s")
    ingest(_batches(X, y), d, shard_rows=32, source={"kind": "a"})
    with pytest.raises(ValueError) as ei:
        ingest(_batches(X, y), d, shard_rows=16, resume=True,
               source={"kind": "b"})
    msg = str(ei.value)
    assert "differing keys" in msg
    assert "shard_rows" in msg and "source" in msg
    assert "store fingerprint" in msg and "requested fingerprint" in msg


# ---------------------------------------------------------------------------
# GridManifest warm-base acceptance
# ---------------------------------------------------------------------------

def test_grid_manifest_accepts_warm_base_and_refuses_strangers(tmp_path):
    d = str(tmp_path / "ckpt")
    base_fp = {"config": {"n_trees": 4, "max_depth": 2}, "grid": [2, 2],
               "ensembles_per_batch": 2, "data": [96, 3]}
    m0 = GridManifest(d, base_fp)
    m0.load_done(resume=False)
    m0.mark_done((0, 2))
    ext_fp = dict(base_fp, config={"n_trees": 6, "max_depth": 2},
                  warm_start=4)
    # warm_base match -> accepted with an EMPTY done-set (base batches
    # hold fewer-round buffers; the extension rewrites them all)
    m1 = GridManifest(d, ext_fp, warm_base={"config": base_fp["config"],
                                            "grid": base_fp["grid"]})
    assert m1.load_done(resume=True) == set()
    # no warm_base -> the PR-2 refusal, now with the full diff
    with pytest.raises(ValueError) as ei:
        GridManifest(d, ext_fp).load_done(resume=True)
    msg = str(ei.value)
    assert "differing keys" in msg and "config" in msg
    assert "checkpoint fingerprint" in msg
    # a warm_base that matches nothing on disk also refuses
    other = GridManifest(d, ext_fp, warm_base={"config": {"n_trees": 9},
                                               "grid": [2, 2]})
    with pytest.raises(ValueError, match="differing keys"):
        other.load_done(resume=True)


def test_fit_artifacts_resumes_over_base_checkpoint(tmp_path, small_data):
    """End to end: an extension pointed at the *base* run's checkpoint dir
    is accepted (warm-base fingerprint) and overwrites it in place."""
    X, y = small_data
    d = str(tmp_path / "ckpt")
    base = fit_artifacts(X, y, dataclasses.replace(FCFG, n_trees=4), seed=5,
                         checkpoint_dir=d, ensembles_per_batch=2)
    ext = extend_artifacts(base, X, y, extra_trees=2, seed=5,
                           checkpoint_dir=d, resume=True,
                           ensembles_per_batch=2)
    cold = fit_artifacts(X, y, FCFG, seed=5)
    _assert_same(cold, ext)
    man = json.loads((tmp_path / "ckpt" / "manifest.json").read_text())
    assert man["fingerprint"]["warm_start"] == 4


# ---------------------------------------------------------------------------
# extension validation
# ---------------------------------------------------------------------------

def test_extend_validation_errors(small_data):
    X, y = small_data
    base = fit_artifacts(X, y, dataclasses.replace(FCFG, n_trees=4), seed=5)
    with pytest.raises(ValueError, match="extra_trees"):
        extend_artifacts(base, X, y, extra_trees=0)
    with pytest.raises(ValueError, match="n_trees > the base"):
        fit_artifacts(X, y, dataclasses.replace(FCFG, n_trees=4),
                      warm_start=base)
    with pytest.raises(ValueError, match="max_depth: base=2 != new=3"):
        fit_artifacts(X, y, dataclasses.replace(FCFG, max_depth=3),
                      warm_start=base)
    with pytest.raises(ValueError, match=r"p=3 .*p=4"):
        extend_artifacts(base, np.zeros((96, 4), np.float32), y,
                         extra_trees=2)
    y3 = y.copy()
    y3[:5] = 2
    with pytest.raises(ValueError, match=r"\[0, 1\].*\[0, 1, 2\]"):
        extend_artifacts(base, X, y3, extra_trees=2)


# ---------------------------------------------------------------------------
# lineage persistence + the admin reload endpoint
# ---------------------------------------------------------------------------

def test_lineage_survives_save_load_and_extend_method(tmp_path, small_data):
    X, y = small_data
    base = fit_artifacts(X, y, dataclasses.replace(FCFG, n_trees=4), seed=5)
    ext = base.extend(X, y, extra_trees=2, seed=5)
    path = str(tmp_path / "m")
    ext.save(path)
    back = type(ext).load(path)
    assert back.lineage == ext.lineage
    assert back.lineage["base"]["round_range"] == [4, 6]
    # replace() (the registry's demote/promote path) keeps lineage; jit
    # round-trips drop it (it is metadata, not a pytree leaf)
    assert dataclasses.replace(ext).lineage == ext.lineage


def _post(app, name, body):
    return app.reload_model(name, body)


def test_reload_endpoint_swaps_and_surfaces_lineage(tmp_path, small_data):
    from repro.launch.serve_http import ServingApp
    from repro.serving import AdmissionController, ModelRegistry
    X, y = small_data
    base = fit_artifacts(X, y, dataclasses.replace(FCFG, n_trees=4), seed=5)
    p1, p2 = str(tmp_path / "v1"), str(tmp_path / "v2")
    base.save(p1)
    base.extend(X, y, extra_trees=2, seed=5).save(p2)

    registry = ModelRegistry(buckets=(64,))
    registry.register("m", TabularGenerator.load(p1).artifacts)
    app = ServingApp(registry, AdmissionController(),
                     model_paths={"m": p1})
    try:
        assert registry.describe()["m"]["lineage"]["base"] is None
        status, body = _post(app, "m", {"path": p2})
        assert status == 200 and body["version"] == 2
        assert body["lineage"]["base"]["round_range"] == [4, 6]
        assert registry.describe()["m"]["lineage"] == body["lineage"]
        # path-less reload reuses the registered path (refresh-in-place)
        status, body = _post(app, "m", {})
        assert status == 200 and body["path"] == p2  # remembered last path
        status, body = _post(app, "nope", {"path": p2})
        assert status == 404 and body["models"] == ["m"]
        status, body = _post(app, "m", {"path": str(tmp_path / "missing")})
        assert status == 400 and "failed" in body["error"]
        assert registry.peek("m").version == 3     # failed reload: no swap
    finally:
        app.stop()


def test_reload_under_lru_pressure_drops_no_request(tmp_path, small_data,
                                                    recompile_budget):
    """A refresh hot-swap while the registry is evicting under budget
    pressure and requests are in flight: every request completes, and a
    same-shape swap costs zero recompiles."""
    from repro.launch.serve_http import ServingApp
    from repro.serving import AdmissionController, ModelRegistry
    from repro.serving.registry import artifacts_nbytes
    X, y = small_data
    art = fit_artifacts(X, y, dataclasses.replace(FCFG, n_trees=4), seed=5)
    p1, p2 = str(tmp_path / "v1"), str(tmp_path / "v2")
    art.save(p1)
    # same shapes, shifted scalers -> same-shape swap, distinct model
    dataclasses.replace(art, mins=np.asarray(art.mins) + 1.0,
                        maxs=np.asarray(art.maxs) + 1.0).save(p2)

    budget = int(artifacts_nbytes(art) * 2.5)      # fits 2 of 3 hot
    registry = ModelRegistry(buckets=(64,), device_budget_bytes=budget)
    for name in ("a", "b", "m"):
        registry.register(name, art)
    registry.warmup()
    app = ServingApp(registry, AdmissionController(),
                     model_paths={"m": p1}, coalesce_window_s=0.0)
    stop = threading.Event()
    results, lock = [], threading.Lock()

    def hammer(name):
        while not stop.is_set():
            f = app.scheduler.submit(8, model=name)
            Xg, yg = f.result(timeout=120)
            with lock:
                results.append(Xg.shape)
            # rotate LRU pressure: touching a/b evicts/promotes around m
            time.sleep(0.002)

    threads = [threading.Thread(target=hammer, args=(n,))
               for n in ("a", "b", "m")]
    try:
        for t in threads:
            t.start()
        time.sleep(0.05)
        with recompile_budget(0):                  # same-shape swap
            status, body = app.reload_model("m", {"path": p2})
        assert status == 200 and body["version"] == 2
        time.sleep(0.05)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=60)
        app.stop()
    assert all(s == (8, 3) for s in results)       # zero dropped/mis-shaped
    assert len(results) >= 3
    assert registry.peek("m").version == 2
