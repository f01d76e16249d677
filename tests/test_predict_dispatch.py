"""The kernel-backed generation path: per-call impl dispatch + parity.

PR 4 collapsed the duplicated forest traversal — ``predict_forest`` routes
through ``repro.kernels.tree_predict.ops.forest_predict`` with an impl
switch resolved at call time (argument > ``ForestConfig.predict_impl`` >
``REPRO_TREE_PREDICT_IMPL`` > xla). These tests pin:

* Pallas(interpret) <-> XLA parity for the dispatch itself (SO and MO
  forests, odd row counts) and end-to-end through the euler/heun/ddim
  solvers and the imputation loop;
* per-call env resolution (the old module-level snapshot ignored changes
  made after import) for both the tree-predict and the hist switch;
* the default when nothing is asked for (``default_impl``): the kernel on
  a TPU without a mesh where its VMEM working set fits, XLA elsewhere, at
  every site that resolves the impl.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ForestConfig
from repro.data.tabular import two_moons
from repro.forest.hist import build_histogram
from repro.forest.packed import PackedForest, predict_forest
from repro.kernels.tree_predict import ops
from repro.kernels.tree_predict.ops import default_impl
from repro.launch.mesh import make_mesh
from repro.tabgen import fit_artifacts, impute, imputation, sample, sampling


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


# ---------------------------------------------------------------------------
# the default impl: platform, mesh and shape decide; explicit choices win
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("platform,p,out,mesh,depth,want", [
    ("tpu", 533, 533, False, 7, "pallas"),     # pion generation
    ("tpu", 368, 1, False, 7, "pallas"),       # single-output forests
    ("tpu", 2, 2, False, 3, "pallas"),         # a small table
    ("cpu", 533, 533, False, 7, "xla"),
    ("gpu", 533, 533, False, 7, "xla"),
    ("tpu", 533, 533, True, 7, "xla"),         # GSPMD cannot split Mosaic
    ("cpu", 533, 533, True, 7, "xla"),
    ("tpu", 40000, 40000, False, 7, "xla"),    # blocks over the VMEM budget
    ("tpu", 64, 64, False, 14, "xla"),         # path matrix over the budget
])
def test_default_impl_rule(platform, p, out, mesh, depth, want):
    m = make_mesh((1, 1)) if mesh else None
    assert default_impl(platform, p, out, m, depth) == want


class _Spy:
    """Stands in for the traversal (or the whole solve) and records the
    impl each call resolved to."""

    def __init__(self, result):
        self.impls, self._result = [], result

    def __call__(self, *args, **kw):
        self.impls.append(kw["impl"])
        return self._result(*args, **kw)


@pytest.fixture
def on_tpu(monkeypatch):
    """The default backend reads as a TPU; the traversal never runs."""
    monkeypatch.delenv("REPRO_TREE_PREDICT_IMPL", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _spy_solve(monkeypatch, art):
    spy = _Spy(lambda *a, **kw: jnp.zeros((art.n_y, kw["m"], art.p)))
    monkeypatch.setattr(sampling, "_solve_all_classes", spy)
    return spy


def test_sample_defaults_to_the_kernel_on_a_tpu(flow_so, monkeypatch, on_tpu):
    spy = _spy_solve(monkeypatch, flow_so)
    sample(flow_so, 40, seed=0)
    # under a mesh the default is xla, and nothing raises
    sample(flow_so, 40, seed=0, mesh=make_mesh((1, 1)))
    assert spy.impls == ["pallas", "xla"]


def test_explicit_choices_beat_the_default(flow_so, monkeypatch, on_tpu):
    spy = _spy_solve(monkeypatch, flow_so)
    sample(flow_so, 40, seed=0, impl="xla")
    art_x = dataclasses.replace(
        flow_so, config=dataclasses.replace(flow_so.config,
                                            predict_impl="xla"))
    sample(art_x, 40, seed=0)
    monkeypatch.setenv("REPRO_TREE_PREDICT_IMPL", "xla")
    sample(flow_so, 40, seed=0)
    assert spy.impls == ["xla", "xla", "xla"]
    # an explicit kernel under a mesh still has no route
    with pytest.raises(ValueError, match="no mesh route"):
        sample(flow_so, 16, seed=0, mesh=make_mesh((1, 1)), impl="pallas")


def test_sample_defaults_to_xla_on_the_cpu(flow_so, monkeypatch):
    monkeypatch.delenv("REPRO_TREE_PREDICT_IMPL", raising=False)
    spy = _spy_solve(monkeypatch, flow_so)
    sample(flow_so, 40, seed=0)
    assert spy.impls == ["xla"]


def test_impute_and_forest_predict_take_the_default(flow_so, moons,
                                                    monkeypatch, on_tpu):
    X, y = moons
    Xm = X[:12].copy()
    Xm[:, 1] = np.nan
    lab = np.repeat(np.asarray(flow_so.classes), 6)[:12]
    spy = _Spy(lambda x, *a, **kw: jnp.zeros_like(x))
    monkeypatch.setattr(imputation, "predict_forest", spy)
    impute(flow_so, Xm, lab, seed=2, refine_rounds=1)
    assert spy.impls and set(spy.impls) == {"pallas"}
    spy.impls.clear()
    impute(flow_so, Xm, lab, seed=2, refine_rounds=1, impl="xla")
    assert set(spy.impls) == {"xla"}

    core = _Spy(lambda x, *a, **kw: x)
    monkeypatch.setattr(ops, "_forest_predict", core)
    f = flow_so
    args = (jnp.zeros((8, f.p)), f.feat[0, 0, 0], f.thr_val[0, 0, 0],
            f.leaf[0, 0, 0], f.config.max_depth)
    ops.forest_predict(*args)
    monkeypatch.setenv("REPRO_TREE_PREDICT_IMPL", "xla")
    ops.forest_predict(*args)
    assert core.impls == ["pallas", "xla"]
