"""Pipelined (double-buffered) distributed trainer: bit-exactness against
the serial loop, prefetch-queue backpressure, crash-resume under async
checkpointing, manifest thread-safety, and the int8 single-device plumbing.

Everything here runs in-process on a 1x1 mesh (works on one CPU device)
with one shared ForestConfig, so the lru_cached shard_map program compiles
once for the whole module.
"""
import dataclasses
import json
import threading
import time

import numpy as np
import pytest

from repro.config import ForestConfig
from repro.launch.mesh import make_mesh
from repro.tabgen import PipelineConfig, fit_artifacts
from repro.tabgen import fitting
from repro.train import checkpoint as ckpt

FIELDS = ("feat", "thr_val", "leaf", "best_round", "rounds_run", "val_curve")

FCFG = ForestConfig(n_t=4, duplicate_k=3, n_trees=3, max_depth=2, n_bins=8,
                    reg_lambda=1.0)


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(3)
    X = rng.normal(size=(96, 3)).astype(np.float32)
    y = (rng.random(96) > 0.5).astype(np.int64)
    return X, y


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1, 1))


def _equal(a, b):
    return all(np.array_equal(np.asarray(getattr(a, f)),
                              np.asarray(getattr(b, f))) for f in FIELDS)


def test_pipelined_bit_exact_vs_serial(data, mesh):
    X, y = data
    serial = fit_artifacts(X, y, FCFG, seed=0, mesh=mesh,
                           ensembles_per_batch=2, pipeline=None)
    piped = fit_artifacts(X, y, FCFG, seed=0, mesh=mesh,
                          ensembles_per_batch=2, pipeline=PipelineConfig())
    assert _equal(serial, piped)
    # sync-checkpoint mode (prefetch only, no writer thread) is also exact
    sync = fit_artifacts(X, y, FCFG, seed=0, mesh=mesh,
                         ensembles_per_batch=2,
                         pipeline=PipelineConfig(async_checkpoint=False))
    assert _equal(serial, sync)


def test_prefetch_backpressure_depths_identical(data, mesh, tmp_path):
    """depth=1 (classic double buffering) and depth=4 bound different
    amounts of in-flight work but must produce identical artifacts and
    identical checkpoint files."""
    X, y = data
    arts = {}
    for depth in (1, 4):
        d = tmp_path / f"depth{depth}"
        arts[depth] = fit_artifacts(
            X, y, FCFG, seed=0, mesh=mesh, ensembles_per_batch=2,
            checkpoint_dir=str(d),
            pipeline=PipelineConfig(prefetch_depth=depth))
        assert fitting.LAST_PIPELINE_STATS["prefetch_depth"] == depth
        assert fitting.LAST_PIPELINE_STATS["n_batches"] == 4
    assert _equal(arts[1], arts[4])
    for b0 in (0, 2, 4, 6):
        a = ckpt.read_batch_npz(str(tmp_path / "depth1"), b0)
        b = ckpt.read_batch_npz(str(tmp_path / "depth4"), b0)
        assert sorted(a) == sorted(b)
        for k in a:
            np.testing.assert_array_equal(a[k], b[k],
                                          err_msg=f"batch_{b0}.npz[{k}]")


def test_crash_between_writer_flushes_resumes(data, mesh, tmp_path,
                                              monkeypatch):
    """Kill the writer thread after its first durable flush: the manifest
    must stay consistent (only batch 0 committed) and a pipelined resume
    must finish the grid to bit-identical artifacts."""
    X, y = data
    full = fit_artifacts(X, y, FCFG, seed=0, mesh=mesh,
                         ensembles_per_batch=2,
                         checkpoint_dir=str(tmp_path / "full"),
                         pipeline=PipelineConfig())

    crash_dir = str(tmp_path / "crash")
    real = ckpt.write_batch_npz
    calls = {"n": 0}

    def flaky(directory, b0, arrays):
        if calls["n"] >= 1:
            raise OSError("injected crash between writer flushes")
        calls["n"] += 1
        return real(directory, b0, arrays)

    monkeypatch.setattr(ckpt, "write_batch_npz", flaky)
    with pytest.raises(OSError, match="injected crash"):
        fit_artifacts(X, y, FCFG, seed=0, mesh=mesh, ensembles_per_batch=2,
                      checkpoint_dir=crash_dir, pipeline=PipelineConfig())
    monkeypatch.setattr(ckpt, "write_batch_npz", real)

    # only the durably flushed batch is in the manifest
    man = ckpt.GridManifest(crash_dir, fingerprint={})
    with open(man.path) as f:
        committed = json.load(f)["batches"]
    assert committed == [[0, 2]], committed

    resumed = fit_artifacts(X, y, FCFG, seed=0, mesh=mesh,
                            ensembles_per_batch=2, checkpoint_dir=crash_dir,
                            resume=True, pipeline=PipelineConfig())
    assert _equal(full, resumed)
    assert fitting.LAST_PIPELINE_STATS["n_cached"] == 1


def test_serial_checkpoint_resumes_under_pipeline(data, mesh, tmp_path):
    """The execution style is not fingerprinted: a checkpoint written by the
    serial loop resumes under the pipeline (and is fully cache-served)."""
    X, y = data
    d = str(tmp_path / "ck")
    serial = fit_artifacts(X, y, FCFG, seed=0, mesh=mesh,
                           ensembles_per_batch=2, checkpoint_dir=d,
                           pipeline=None)
    piped = fit_artifacts(X, y, FCFG, seed=0, mesh=mesh,
                          ensembles_per_batch=2, checkpoint_dir=d,
                          resume=True, pipeline=PipelineConfig())
    assert _equal(serial, piped)
    assert fitting.LAST_PIPELINE_STATS["n_cached"] == 4


def test_pipeline_arg_validation(data, mesh):
    X, y = data
    with pytest.raises(ValueError, match="pipeline="):
        fit_artifacts(X, y, FCFG, seed=0, mesh=mesh, pipeline="bogus")
    # the knob must fail loudly on the single-device path too, not be
    # silently ignored until the code first runs on a real mesh
    with pytest.raises(ValueError, match="pipeline="):
        fit_artifacts(X, y, FCFG, seed=0, mesh=None,
                      pipeline=PipelineConfig)  # the class, not an instance


def test_base_exception_joins_pipeline_threads():
    """KeyboardInterrupt-style BaseExceptions must stop and join the stage
    threads — no busy-polling daemon may outlive the fit."""
    class Boom(BaseException):
        pass

    def dispatch(inputs):
        raise Boom("simulated Ctrl-C mid-dispatch")

    def collect(res, n):  # pragma: no cover — never reached
        return {}

    before = threading.active_count()
    with pytest.raises(Boom):
        fitting._run_grid_batches_pipelined(
            dispatch, collect, [(0, 0), (1, 0)], 1, checkpoint_dir=None,
            resume=False, fingerprint={}, prefetch=lambda chunk: ("x",),
            pcfg=PipelineConfig())
    deadline = time.time() + 5.0
    while threading.active_count() > before and time.time() < deadline:
        time.sleep(0.01)
    assert threading.active_count() == before, "pipeline thread leaked"


def test_grid_manifest_concurrent_mark_done(tmp_path):
    """mark_done from many threads (out-of-order completion) keeps the
    manifest a consistent superset-free record of exactly the marked keys."""
    man = ckpt.GridManifest(str(tmp_path), fingerprint={"v": 1})
    keys = [(b0, 2) for b0 in range(0, 40, 2)]
    threads = [threading.Thread(target=man.mark_done, args=(k,))
               for k in keys]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    fresh = ckpt.GridManifest(str(tmp_path), fingerprint={"v": 1})
    assert fresh.load_done(resume=True) == set(keys)
    # and the fingerprint refusal still works on the final file
    other = ckpt.GridManifest(str(tmp_path), fingerprint={"v": 2})
    with pytest.raises(ValueError, match="mismatched"):
        other.load_done(resume=True)


def test_int8_codes_single_device_parity(data):
    """ROADMAP item: int8_codes must engage in the single-device fit_one
    too, and quantised code storage must not change the trained forest
    (codes are exact small ints either way)."""
    X, y = data
    f32 = fit_artifacts(X, y, FCFG, seed=0)
    i8 = fit_artifacts(X, y, dataclasses.replace(FCFG, int8_codes=True),
                       seed=0)
    assert _equal(f32, i8)
