"""The kernel-backed generation path: per-call impl dispatch + parity.

PR 4 collapsed the duplicated forest traversal — ``predict_forest`` routes
through ``repro.kernels.tree_predict.ops.forest_predict`` with an impl
switch resolved at call time (argument > ``ForestConfig.predict_impl`` >
``REPRO_TREE_PREDICT_IMPL`` > xla). These tests pin:

* Pallas(interpret) <-> XLA parity for the dispatch itself (SO and MO
  forests, odd row counts) and end-to-end through the euler/heun/ddim
  solvers and the imputation loop;
* per-call env resolution (the old module-level snapshot ignored changes
  made after import) for both the tree-predict and the hist switch.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ForestConfig
from repro.data.tabular import two_moons
from repro.forest.hist import build_histogram
from repro.forest.packed import PackedForest, predict_forest
from repro.tabgen import fit_artifacts, impute, sample


@pytest.fixture(scope="module")
def moons():
    return two_moons(240, seed=0)


def _fit(moons, **kw):
    X, y = moons
    base = dict(n_t=5, duplicate_k=6, n_trees=8, max_depth=3,
                n_bins=16, reg_lambda=1.0)
    base.update(kw)
    return fit_artifacts(X, y, ForestConfig(**base), seed=0)


@pytest.fixture(scope="module")
def flow_so(moons):
    return _fit(moons, method="flow")


@pytest.fixture(scope="module")
def flow_mo(moons):
    return _fit(moons, method="flow", multi_output=True)


@pytest.fixture(scope="module")
def diff_so(moons):
    return _fit(moons, method="diffusion", n_t=6)


# ---------------------------------------------------------------------------
# predict_forest dispatch parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [64, 97, 130, 1])  # odd n: wrapper row padding
@pytest.mark.parametrize("art_name", ["flow_so", "flow_mo"])
def test_predict_forest_impl_parity(request, art_name, n):
    art = request.getfixturevalue(art_name)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.uniform(-1, 1, (n, art.p)).astype(np.float32))
    forest = PackedForest(art.feat[0, 0], art.thr_val[0, 0], art.leaf[0, 0],
                          art.config.multi_output)
    ref = predict_forest(x, forest, art.config.max_depth, impl="xla")
    got = predict_forest(x, forest, art.config.max_depth,
                         impl="pallas_interpret")
    assert ref.shape == (n, art.p)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# end-to-end through the solvers (acceptance: <= 1e-5 through a full sample)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sampler,art_name", [
    ("euler", "flow_so"), ("heun", "flow_so"), ("euler", "flow_mo"),
    ("ddim", "diff_so"),
])
def test_sample_impl_parity_end_to_end(request, sampler, art_name):
    art = request.getfixturevalue(art_name)
    G1, y1 = sample(art, 131, sampler=sampler, seed=3)  # odd n on purpose
    G2, y2 = sample(art, 131, sampler=sampler, seed=3,
                    impl="pallas_interpret")
    np.testing.assert_array_equal(y1, y2)
    np.testing.assert_allclose(G1, G2, rtol=1e-5, atol=1e-5)


def test_impute_impl_parity(flow_so, moons):
    X, y = moons
    Xm = X[:24].copy()
    Xm[:, 1] = np.nan
    lab = np.repeat(np.asarray(flow_so.classes), 12)[:24]
    f1 = impute(flow_so, Xm, lab, seed=2, refine_rounds=1)
    f2 = impute(flow_so, Xm, lab, seed=2, refine_rounds=1,
                impl="pallas_interpret")
    np.testing.assert_allclose(f1, f2, rtol=1e-5, atol=1e-5)


def test_config_predict_impl_drives_dispatch(flow_so, tmp_path):
    """`ForestConfig.predict_impl` selects the backend and round-trips
    through the artifacts sidecar."""
    art_k = dataclasses.replace(
        flow_so, config=dataclasses.replace(flow_so.config,
                                            predict_impl="pallas_interpret"))
    G1, _ = sample(flow_so, 80, seed=5)
    G2, _ = sample(art_k, 80, seed=5)
    np.testing.assert_allclose(G1, G2, rtol=1e-5, atol=1e-5)
    from repro.tabgen import ForestArtifacts
    base = art_k.save(str(tmp_path / "m"))
    assert ForestArtifacts.load(base).config.predict_impl == "pallas_interpret"


# ---------------------------------------------------------------------------
# per-call env resolution (regression: was frozen at import time)
# ---------------------------------------------------------------------------

def test_tree_predict_env_resolved_per_call(flow_so, monkeypatch):
    G_ref, _ = sample(flow_so, 60, seed=1)
    monkeypatch.setenv("REPRO_TREE_PREDICT_IMPL", "pallas_interpret")
    G_env, _ = sample(flow_so, 60, seed=1)
    np.testing.assert_allclose(G_ref, G_env, rtol=1e-5, atol=1e-5)
    # a typo'd env var fails loudly at the next call, not silently runs xla
    monkeypatch.setenv("REPRO_TREE_PREDICT_IMPL", "bogus")
    with pytest.raises(ValueError, match="bogus"):
        sample(flow_so, 60, seed=1)


def test_hist_env_resolved_per_call(monkeypatch):
    rng = np.random.default_rng(0)
    codes = jnp.asarray(rng.integers(0, 8, (128, 3)), jnp.int32)
    nid = jnp.asarray(rng.integers(0, 2, (128,)), jnp.int32)
    g = jnp.asarray(rng.normal(size=(128, 1)).astype(np.float32))
    w = jnp.ones((128,), jnp.float32)
    monkeypatch.delenv("REPRO_HIST_IMPL", raising=False)
    s_ref, c_ref = build_histogram(codes, nid, g, w, 2, 8)
    # env set AFTER repro.forest.hist import: must take effect (was ignored)
    monkeypatch.setenv("REPRO_HIST_IMPL", "pallas_interpret")
    s_pl, c_pl = build_histogram(codes, nid, g, w, 2, 8)
    np.testing.assert_allclose(np.asarray(s_pl), np.asarray(s_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(c_pl), np.asarray(c_ref),
                               rtol=1e-5, atol=1e-5)
    monkeypatch.setenv("REPRO_HIST_IMPL", "bogus")
    with pytest.raises(ValueError, match="bogus"):
        build_histogram(codes, nid, g, w, 2, 8)


def test_compiled_kernel_refuses_a_mesh(flow_so):
    """A Mosaic kernel cannot be partitioned by GSPMD: ``sample`` says so
    up front instead of failing inside the TPU lowering."""
    from repro.launch.mesh import make_mesh
    with pytest.raises(ValueError, match="no mesh route"):
        sample(flow_so, 16, seed=0, mesh=make_mesh((1, 1)), impl="pallas")
