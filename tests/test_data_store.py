"""Out-of-core data layer: quantile-sketch parity against the in-memory
reference edge functions, ingest round-trips, crash-resume safety, and
store-backed fit parity with the in-memory trainers.

Fits run in-process on a 1x1 mesh (one CPU device) with one shared tiny
ForestConfig so the lru_cached shard_map program compiles once per module.
"""
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ForestConfig
from repro.data.sketch import QuantileSketch, sketch_dataset
from repro.data.store import DatasetStore, ingest
from repro.data.tabular import (correlated_gaussian_batches,
                                synthetic_resource_batches,
                                synthetic_resource_dataset,
                                two_moons_batches)
from repro.forest.binning import fit_bins, fit_bins_streaming, pack_codes, \
    transform
from repro.launch.mesh import make_mesh
from repro.tabgen import fit_artifacts
from repro.tabgen.fitting import class_stats_streaming, weighted_edges

FIELDS = ("feat", "thr_val", "leaf", "best_round", "rounds_run", "val_curve",
          "mins", "maxs")

FCFG = ForestConfig(n_t=2, duplicate_k=3, n_trees=3, max_depth=2, n_bins=8,
                    reg_lambda=1.0)


def _equal(a, b):
    return all(np.array_equal(np.asarray(getattr(a, f)),
                              np.asarray(getattr(b, f))) for f in FIELDS)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1, 1))


@pytest.fixture(scope="module")
def small_data():
    rng = np.random.default_rng(3)
    X = rng.normal(size=(96, 3)).astype(np.float32)
    y = (rng.random(96) > 0.5).astype(np.int64)
    return X, y


def _batches(X, y, k=20):
    for s in range(0, len(X), k):
        yield X[s:s + k], y[s:s + k]


# ---------------------------------------------------------------------------
# sketch parity (tentpole acceptance: weighted_edges / fit_bins semantics)
# ---------------------------------------------------------------------------

def test_sketch_floor_mode_matches_weighted_edges_exactly():
    """Unpruned sketch == weighted_edges bit-for-bit, including the padded
    zero-weight rows the trainer masks out."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(257, 5)).astype(np.float32)
    w = (rng.random(257) > 0.2).astype(np.float32)    # 0/1 row mask
    ref = np.asarray(weighted_edges(jnp.asarray(x), jnp.asarray(w), 16))
    got = QuantileSketch(5, max_entries=1024).update(x, w).edges(16, "floor")
    np.testing.assert_array_equal(got, ref)
    # unweighted: every row counts
    ref_all = np.asarray(weighted_edges(jnp.asarray(x),
                                        jnp.ones(257, jnp.float32), 16))
    got_all = QuantileSketch(5, max_entries=1024).update(x).edges(16, "floor")
    np.testing.assert_array_equal(got_all, ref_all)


def test_sketch_linear_mode_matches_fit_bins():
    rng = np.random.default_rng(1)
    x = rng.normal(size=(211, 4)).astype(np.float32)
    ref = np.asarray(fit_bins(jnp.asarray(x), 16))
    got = QuantileSketch(4, max_entries=1024).update(x).edges(16, "linear")
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
    # and the streaming front door (chunked feed, no full-column sort)
    via_stream = np.asarray(fit_bins_streaming(x, 16, max_entries=1024,
                                               row_chunk=37))
    np.testing.assert_allclose(via_stream, ref, rtol=1e-5, atol=1e-6)


def test_sketch_compression_bounds_rank_error():
    """Past max_entries the sketch compresses; quantile estimates must stay
    within a small empirical-rank error of the true quantiles."""
    rng = np.random.default_rng(2)
    big = rng.normal(size=(20000, 3)).astype(np.float32)
    sk = QuantileSketch(3, max_entries=256)
    sk._ABSORB_CHUNK = 4096               # force multiple compressions
    sk.update(big)
    assert sk.vals.shape[1] <= 2 * 256    # state stayed bounded
    qs = np.linspace(0.05, 0.95, 19)
    est = sk.quantiles(qs, "linear")
    srt = np.sort(big, axis=0)
    for f in range(3):
        ranks = np.searchsorted(srt[:, f], est[f]) / len(big)
        assert np.abs(ranks - qs).max() < 0.02


def test_sketch_merge_matches_single_pass():
    rng = np.random.default_rng(4)
    big = rng.normal(size=(8000, 2)).astype(np.float32)
    a = QuantileSketch(2, 256).update(big[:4000])
    b = QuantileSketch(2, 256).update(big[4000:])
    merged = a.merge(b)
    qs = np.linspace(0.1, 0.9, 9)
    est = merged.quantiles(qs, "linear")
    srt = np.sort(big, axis=0)
    for f in range(2):
        ranks = np.searchsorted(srt[:, f], est[f]) / len(big)
        assert np.abs(ranks - qs).max() < 0.02


def test_sketch_int8_code_path():
    """Sketch edges feed transform/pack_codes like exact edges do: same
    codes (unpruned sketch), narrow dtype, codes within [0, n_bins)."""
    rng = np.random.default_rng(5)
    x = rng.normal(size=(300, 4)).astype(np.float32)
    exact = np.asarray(weighted_edges(jnp.asarray(x),
                                      jnp.ones(300, jnp.float32), 16))
    sk_edges = sketch_dataset(x, max_entries=1024).edges(16, "floor")
    codes_exact = pack_codes(transform(jnp.asarray(x), jnp.asarray(exact)),
                             16)
    codes_sketch = pack_codes(transform(jnp.asarray(x),
                                        jnp.asarray(sk_edges)), 16)
    assert codes_sketch.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(codes_exact),
                                  np.asarray(codes_sketch))
    assert int(jnp.max(codes_sketch)) < 16 and int(jnp.min(codes_sketch)) >= 0


def test_sketch_state_roundtrip():
    rng = np.random.default_rng(6)
    x = rng.normal(size=(500, 3)).astype(np.float32)
    sk = QuantileSketch(3, 128).update(x)
    back = QuantileSketch.from_state(sk.state_dict())
    np.testing.assert_array_equal(back.edges(8), sk.edges(8))
    assert back.n_points == sk.n_points


# ---------------------------------------------------------------------------
# ingest / DatasetStore
# ---------------------------------------------------------------------------

def test_ingest_roundtrip_and_precomputed_stats(tmp_path):
    n, p, n_y = 1000, 4, 3
    parts = list(synthetic_resource_batches(n, p, n_y, batch_rows=96,
                                            seed=7))
    X = np.concatenate([x for x, _ in parts])
    y = np.concatenate([yy for _, yy in parts])
    store = ingest(synthetic_resource_batches(n, p, n_y, batch_rows=96,
                                              seed=7),
                   str(tmp_path / "store"), shard_rows=256)
    assert store.shape == (n, p) and store.n_shards == 4
    # row access: full range, arbitrary gather order, slices
    np.testing.assert_array_equal(store[np.arange(n)], X)
    idx = np.array([5, 999, 3, 500, 500])
    np.testing.assert_array_equal(store[idx], X[idx])
    np.testing.assert_array_equal(store[100:300], X[100:300])
    np.testing.assert_array_equal(store.labels(), y)
    # manifest stats == the streaming pass the fit would otherwise run
    for got, ref in zip(store.class_stats(), class_stats_streaming(X, y)):
        np.testing.assert_array_equal(got, ref)
    # precomputed sketch edges == full-sort reference (exact: n < entries)
    ref_edges = np.asarray(weighted_edges(jnp.asarray(X),
                                          jnp.ones(n, jnp.float32), 8))
    np.testing.assert_array_equal(store.edges(8, "floor"), ref_edges)
    # iter_batches streams the same rows back
    out = np.concatenate([xb for xb, _ in store.iter_batches(130)])
    np.testing.assert_array_equal(out, X)


def test_ingest_unlabelled_and_generator_determinism(tmp_path):
    store = ingest(correlated_gaussian_batches(300, 3, batch_rows=64,
                                               seed=1),
                   str(tmp_path / "u"), shard_rows=128)
    assert not store.has_labels
    np.testing.assert_array_equal(store.labels(), np.zeros(300, np.int64))
    classes, counts, _, _ = store.class_stats()
    assert classes.tolist() == [0] and counts.tolist() == [300]
    # chunked generators are deterministic in their seed
    a = [x for x in correlated_gaussian_batches(300, 3, batch_rows=64,
                                                seed=1)]
    b = [x for x in correlated_gaussian_batches(300, 3, batch_rows=64,
                                                seed=1)]
    for xa, xb in zip(a, b):
        np.testing.assert_array_equal(xa, xb)
    # odd batch sizes still total exactly n (two_moons returns 2*(n//2)
    # rows, so the generator over-asks and slices)
    moons = list(two_moons_batches(101, batch_rows=40, seed=2))
    assert sum(len(x) for x, _ in moons) == 101


def test_ingest_refuses_dirty_dir_and_mismatched_fingerprint(tmp_path):
    d = str(tmp_path / "s")
    ingest(_batches(*synthetic_resource_dataset(200, 3, 2, seed=0)), d,
           shard_rows=64)
    with pytest.raises(ValueError, match="resume=True"):
        ingest(_batches(*synthetic_resource_dataset(200, 3, 2, seed=0)), d,
               shard_rows=64)
    # resume with a different config refuses before consuming anything
    with pytest.raises(ValueError, match="mismatched"):
        ingest(_batches(*synthetic_resource_dataset(200, 3, 2, seed=0)), d,
               shard_rows=32, resume=True)
    # resume of a complete store with the matching config is a no-op
    again = ingest(_batches(*synthetic_resource_dataset(200, 3, 2, seed=0)),
                   d, shard_rows=64, resume=True)
    assert again.n_rows == 200


def test_crash_resume_finishes_without_touching_committed_shards(tmp_path):
    X, y = synthetic_resource_dataset(1000, 4, 3, seed=11)

    def batches(crash_after=None):
        sent = 0
        for s in range(0, 1000, 96):
            if crash_after is not None and sent >= crash_after:
                raise RuntimeError("simulated ingest crash")
            yield X[s:s + 96], y[s:s + 96]
            sent += 1

    clean = ingest(batches(), str(tmp_path / "clean"), shard_rows=256)

    crash_dir = str(tmp_path / "crash")
    with pytest.raises(RuntimeError, match="simulated"):
        ingest(batches(crash_after=5), crash_dir, shard_rows=256)
    man = json.load(open(os.path.join(crash_dir, "manifest.json")))
    assert man["complete"] is False and man["n_rows"] == 256
    with pytest.raises(ValueError, match="unfinished ingest"):
        DatasetStore(crash_dir)        # reader refuses a partial store

    def digests():
        return {f: hashlib.sha256(
                    open(os.path.join(crash_dir, f), "rb").read()).hexdigest()
                for f in os.listdir(crash_dir) if f.startswith("shard_")}

    before = digests()
    mtimes = {f: os.stat(os.path.join(crash_dir, f)).st_mtime_ns
              for f in before}
    resumed = ingest(batches(), crash_dir, shard_rows=256, resume=True)
    # committed shard files were neither re-written nor re-derived
    assert {f: d for f, d in digests().items() if f in before} == before
    assert all(os.stat(os.path.join(crash_dir, f)).st_mtime_ns == t
               for f, t in mtimes.items())
    # the finished store is byte-equal to an uninterrupted ingest
    np.testing.assert_array_equal(resumed[np.arange(1000)],
                                  clean[np.arange(1000)])
    for got, ref in zip(resumed.class_stats(), clean.class_stats()):
        np.testing.assert_array_equal(got, ref)
    np.testing.assert_array_equal(resumed.edges(8), clean.edges(8))
    assert resumed.manifest["shards"] == clean.manifest["shards"]


def test_resume_refuses_short_stream(tmp_path):
    X, y = synthetic_resource_dataset(500, 3, 2, seed=12)
    d = str(tmp_path / "s")

    def half():
        yield X[:256], y[:256]
        raise RuntimeError("crash")

    with pytest.raises(RuntimeError):
        ingest(half(), d, shard_rows=128)
    with pytest.raises(ValueError, match="not the one"):
        ingest(iter([(X[:100], y[:100])]), d, shard_rows=128, resume=True)


# ---------------------------------------------------------------------------
# store-backed training (tentpole acceptance: parity with in-memory fits)
# ---------------------------------------------------------------------------

def test_store_backed_fit_parity_with_in_memory(tmp_path, mesh, small_data):
    X, y = small_data
    in_mem = fit_artifacts(X, y, FCFG, seed=0, mesh=mesh)
    store = ingest(_batches(X, y), str(tmp_path / "store"), shard_rows=32)
    st = fit_artifacts(store, None, FCFG, seed=0, mesh=mesh)
    assert _equal(in_mem, st)
    # mesh=None on a store auto-routes to the 1x1 sharded trainer
    st2 = fit_artifacts(store, None, FCFG, seed=0)
    assert _equal(st, st2)


def test_store_fit_with_explicit_labels_overrides_manifest(tmp_path, mesh,
                                                           small_data):
    """Regression: explicit y on a store-backed fit must re-derive the
    class stats from the given labels, not trust the manifest (whose stats
    were computed under the store's own grouping). An unlabelled store +
    3-class y used to IndexError in build_row_shards."""
    X, y = small_data
    # unlabelled store (manifest knows one class), explicit 2-class labels
    store = ingest((X[s:s + 20] for s in range(0, len(X), 20)),
                   str(tmp_path / "u"), shard_rows=32)
    assert not store.has_labels
    via_store = fit_artifacts(store, y, FCFG, seed=0, mesh=mesh)
    in_mem = fit_artifacts(X, y, FCFG, seed=0, mesh=mesh)
    assert _equal(via_store, in_mem)


def test_store_and_in_memory_checkpoints_interoperate(tmp_path, mesh,
                                                      small_data):
    """Same data, same grid -> same manifest fingerprint: an in-memory fit's
    checkpoint resumes a store-backed fit (all batches cache-served)."""
    X, y = small_data
    ck = str(tmp_path / "ck")
    in_mem = fit_artifacts(X, y, FCFG, seed=0, mesh=mesh,
                           ensembles_per_batch=2, checkpoint_dir=ck)
    store = ingest(_batches(X, y), str(tmp_path / "store"), shard_rows=32)
    resumed = fit_artifacts(store, None, FCFG, seed=0, mesh=mesh,
                            ensembles_per_batch=2, checkpoint_dir=ck,
                            resume=True)
    assert _equal(in_mem, resumed)


def test_facade_schema_refuses_store(tmp_path, small_data):
    from repro.tabgen import TabularGenerator
    X, y = small_data
    store = ingest(_batches(X, y), str(tmp_path / "store"), shard_rows=48)
    with pytest.raises(ValueError, match="schema-aware"):
        TabularGenerator(FCFG, cat_cols=[0]).fit(store)


def test_ingest_and_train_clis(tmp_path, mesh):
    """repro.launch.ingest -> train_forest --data-dir, all in-process."""
    from repro.launch import ingest as ingest_cli
    from repro.launch import train_forest
    from repro.tabgen import ForestArtifacts

    d = str(tmp_path / "store")
    ingest_cli.main(["--out", d, "--synthetic", "96x3x2", "--shard-rows",
                     "32", "--batch-rows", "20", "--seed", "3"])
    out = str(tmp_path / "model")
    train_forest.main(["--data-dir", d, "--mesh", "none", "--n-t", "2",
                       "--duplicate-k", "3", "--n-trees", "3",
                       "--max-depth", "2", "--n-bins", "8", "--out", out])
    art = ForestArtifacts.load(out)
    assert art.n_t == 2 and art.n_y == 2
    # the CLI fit is the same fit the API runs (the CLI flags above spell
    # out FCFG, so the module's one compiled program is reused)
    store = DatasetStore(d)
    api = fit_artifacts(store, None, FCFG, seed=0)
    assert _equal(art, api)
