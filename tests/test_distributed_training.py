"""Training-path correctness: PRNG decorrelation, manifest safety, and
single-device vs shard_map trainer parity (subprocess: XLA_FLAGS must be set
before jax init to get the 8-virtual-device host mesh)."""
import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ForestConfig
from repro.core import interpolants as itp
from repro.tabgen import fit_artifacts


def test_cfm_jitter_decorrelated_from_noise():
    """Regression: ``fit_one``/``_fit_one_sharded`` drew x1 with ``k_tr`` and
    passed the same ``k_tr`` as the CFM-jitter key, so the "independent"
    jitter was exactly ``sigma * x1``. ``sample_bridge`` must fold in a
    distinct subkey."""
    t, sigma = 0.5, 0.7
    x0 = jnp.zeros((4096, 2), jnp.float32)
    x1, xt, tgt = itp.sample_bridge(jax.random.PRNGKey(0), x0, "flow", t,
                                    sigma)
    eps = (np.asarray(xt) - t * np.asarray(x1)) / sigma   # recovered jitter
    # under the bug eps == x1 bit-for-bit (same key, same shape)
    assert not np.allclose(eps, np.asarray(x1))
    corr = np.corrcoef(np.asarray(x1).ravel(), eps.ravel())[0, 1]
    assert abs(corr) < 0.05, corr
    # the target is unaffected: flow regresses x1 - x0
    np.testing.assert_allclose(np.asarray(tgt), np.asarray(x1), rtol=1e-6)


def _small_cfg(**kw):
    base = dict(n_t=4, duplicate_k=5, n_trees=5, max_depth=3, n_bins=16,
                reg_lambda=1.0)
    base.update(kw)
    return ForestConfig(**base)


def test_resume_refuses_mismatched_batch_size(tmp_path):
    rng = np.random.default_rng(0)
    X = rng.normal(size=(120, 3)).astype(np.float32)
    fit_artifacts(X, None, _small_cfg(), seed=0,
                  checkpoint_dir=str(tmp_path), ensembles_per_batch=2)
    with pytest.raises(ValueError, match="ensembles_per_batch"):
        fit_artifacts(X, None, _small_cfg(), seed=0,
                      checkpoint_dir=str(tmp_path), resume=True,
                      ensembles_per_batch=4)


def test_resume_refuses_mismatched_config(tmp_path):
    rng = np.random.default_rng(0)
    X = rng.normal(size=(120, 3)).astype(np.float32)
    fit_artifacts(X, None, _small_cfg(), seed=0,
                  checkpoint_dir=str(tmp_path), ensembles_per_batch=2)
    with pytest.raises(ValueError, match="config"):
        fit_artifacts(X, None, _small_cfg(n_trees=7), seed=0,
                      checkpoint_dir=str(tmp_path), resume=True,
                      ensembles_per_batch=2)
    # matching run config still resumes bit-identically
    a1 = fit_artifacts(X, None, _small_cfg(), seed=0,
                       checkpoint_dir=str(tmp_path), resume=True,
                       ensembles_per_batch=2)
    a2 = fit_artifacts(X, None, _small_cfg(), seed=0,
                       checkpoint_dir=str(tmp_path), resume=True,
                       ensembles_per_batch=2)
    np.testing.assert_array_equal(np.asarray(a1.leaf), np.asarray(a2.leaf))


_PARITY = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, shutil
import jax
import numpy as np

from repro.config import ForestConfig
from repro.eval import metrics as M
from repro.launch.mesh import make_mesh
from repro.tabgen import TabularGenerator, fit_artifacts, sample

assert len(jax.devices()) == 8
rng = np.random.default_rng(1)
n_per, p = 192, 3
mu0, mu1 = np.array([-1.5, 0.0, 1.0]), np.array([1.5, 1.0, -1.0])
X = np.concatenate([mu0 + 0.4 * rng.normal(size=(n_per, p)),
                    mu1 + 0.4 * rng.normal(size=(n_per, p))]).astype(
                        np.float32)
y = np.concatenate([np.zeros(n_per), np.ones(n_per)]).astype(np.int64)
fcfg = ForestConfig(n_t=4, duplicate_k=8, n_trees=8, max_depth=3, n_bins=16,
                    reg_lambda=1.0)

def class_err(art):
    G, yg = sample(art, 2 * n_per, seed=5)
    errs = []
    for cls, mu in ((0, mu0), (1, mu1)):
        sel = yg == cls
        errs.append(float(np.abs(G[sel].mean(0) - mu).max()))
        errs.append(float(np.abs(G[sel].std(0) - 0.4).max()))
    return G, max(errs)

art_single = fit_artifacts(X, y, fcfg, seed=0)
meshes = {"1x1": make_mesh((1, 1)),
          "4x2": make_mesh((4, 2))}
G0, err0 = class_err(art_single)
report = {"single": err0}
for name, mesh in meshes.items():
    art_m = fit_artifacts(X, y, fcfg, seed=0, mesh=mesh)
    Gm, errm = class_err(art_m)
    report[name] = errm
    report[f"{name}_w1_vs_single"] = float(M.sliced_w1(Gm, G0))

# facade + save/load round-trip through the sharded trainer
tmp = os.environ.get("TMPDIR", "/tmp") + "/parity_model"
gen = TabularGenerator(fcfg).fit(X, y, seed=0, mesh=meshes["4x2"])
base = gen.save(tmp)
G_loaded, _ = TabularGenerator.load(base).generate(2 * n_per, seed=5)
report["roundtrip_w1_vs_single"] = float(M.sliced_w1(G_loaded, G0))

# resume mid-grid: a fresh dir seeded with only the first batch of a full
# checkpointed run must finish the remaining batches to identical forests
ck_full, ck_part = "/tmp/ck_full", "/tmp/ck_part"
for d in (ck_full, ck_part):
    shutil.rmtree(d, ignore_errors=True)
art_full = fit_artifacts(X, y, fcfg, seed=0, mesh=meshes["4x2"],
                         checkpoint_dir=ck_full, ensembles_per_batch=4)
os.makedirs(ck_part)
shutil.copy(ck_full + "/batch_0.npz", ck_part)
with open(ck_full + "/manifest.json") as f:
    man = json.load(f)
man["batches"] = [b for b in man["batches"] if b[0] == 0]
with open(ck_part + "/manifest.json", "w") as f:
    json.dump(man, f)
art_res = fit_artifacts(X, y, fcfg, seed=0, mesh=meshes["4x2"],
                        checkpoint_dir=ck_part, resume=True,
                        ensembles_per_batch=4)
report["resume_equal"] = bool(
    np.array_equal(np.asarray(art_full.leaf), np.asarray(art_res.leaf))
    and np.array_equal(np.asarray(art_full.feat), np.asarray(art_res.feat)))

# elastic resume: a different mesh shape with no pinned batch size inherits
# the manifest's ensembles_per_batch instead of refusing on the fingerprint
mesh24 = make_mesh((2, 4))
art_el = fit_artifacts(X, y, fcfg, seed=0, mesh=mesh24,
                       checkpoint_dir=ck_full, resume=True)
report["elastic_equal"] = bool(
    np.array_equal(np.asarray(art_full.leaf), np.asarray(art_el.leaf)))

# pipelined vs serial on the 4x2 mesh: the double-buffered loop must be
# bit-exact against the serial PR-2 loop for the same seed and batch size
from repro.tabgen import PipelineConfig
art_ser = fit_artifacts(X, y, fcfg, seed=0, mesh=meshes["4x2"],
                        ensembles_per_batch=4, pipeline=None)
art_pipe = fit_artifacts(X, y, fcfg, seed=0, mesh=meshes["4x2"],
                         ensembles_per_batch=4,
                         pipeline=PipelineConfig(prefetch_depth=2))
report["pipe_bitexact"] = all(
    np.array_equal(np.asarray(getattr(art_ser, f)),
                   np.asarray(getattr(art_pipe, f)))
    for f in ("feat", "thr_val", "leaf", "best_round", "rounds_run",
              "val_curve"))
report["ok"] = True
print(json.dumps(report))
"""


@pytest.mark.slow
@pytest.mark.distributed
def test_sharded_trainer_parity_and_resume_8dev():
    out = subprocess.run([sys.executable, "-c", _PARITY],
                         capture_output=True, text=True, timeout=900,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                              "HOME": "/root", "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-3000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["ok"]
    # each trainer recovers the class structure...
    for k in ("single", "1x1", "4x2"):
        assert r[k] < 0.5, r
    # ...and the sharded samples match the single-device ones in
    # distribution (keys differ per shard, so compare statistically)
    for k in ("1x1_w1_vs_single", "4x2_w1_vs_single",
              "roundtrip_w1_vs_single"):
        assert r[k] < 0.35, r
    assert r["resume_equal"], r
    assert r["elastic_equal"], r
    assert r["pipe_bitexact"], r
