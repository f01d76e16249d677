"""Per-kernel sweeps: Pallas (interpret=True) vs pure-jnp oracles (the tree
kernel bit for bit, the others within a tolerance)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # optional dev dependency; see README + the shim module
    from _hypothesis_fallback import given, settings, strategies as st

from repro.kernels.flash_attention.fa_kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.hist.hist_kernel import histogram_pallas
from repro.kernels.hist.ref import histogram_ref
from repro.kernels.tree_predict.ref import forest_predict_ref
from repro.kernels.tree_predict.tree_kernel import (VMEM_LIMIT,
                                                    forest_predict_pallas,
                                                    path_matrix, plan, split3,
                                                    vmem_bytes)


# ---------------------------------------------------------------------------
# histogram kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,p,out,n_nodes,n_bins,rows_block", [
    (256, 3, 1, 1, 8, 128),
    (512, 7, 2, 4, 16, 256),
    (1024, 5, 4, 8, 32, 512),
    (384, 2, 3, 2, 64, 128),
])
def test_hist_kernel_matches_ref(n, p, out, n_nodes, n_bins, rows_block):
    rng = np.random.default_rng(0)
    codes = jnp.asarray(rng.integers(0, n_bins, (n, p)), jnp.int32)
    nid = jnp.asarray(rng.integers(0, n_nodes, (n,)), jnp.int32)
    g = jnp.asarray(rng.normal(size=(n, out)).astype(np.float32))
    w = jnp.asarray(rng.uniform(0.0, 1.0, n).astype(np.float32))
    s_ref, c_ref = histogram_ref(codes, nid, g, w, n_nodes, n_bins)
    s_pl, c_pl = histogram_pallas(codes, nid, g, w, n_nodes, n_bins,
                                  rows_block=rows_block, interpret=True)
    np.testing.assert_allclose(np.asarray(s_pl), np.asarray(s_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(c_pl), np.asarray(c_ref),
                               rtol=1e-5, atol=1e-5)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 4), st.integers(1, 3), st.integers(0, 10 ** 6))
def test_hist_kernel_property(n_nodes_pow, out, seed):
    """Property: kernel == oracle for random node/bin assignments."""
    rng = np.random.default_rng(seed)
    n, p, n_bins = 128, 3, 8
    n_nodes = 2 ** n_nodes_pow
    codes = jnp.asarray(rng.integers(0, n_bins, (n, p)), jnp.int32)
    nid = jnp.asarray(rng.integers(0, n_nodes, (n,)), jnp.int32)
    g = jnp.asarray(rng.normal(size=(n, out)).astype(np.float32))
    w = jnp.asarray(rng.uniform(0.0, 1.0, n).astype(np.float32))
    s_ref, c_ref = histogram_ref(codes, nid, g, w, n_nodes, n_bins)
    s_pl, c_pl = histogram_pallas(codes, nid, g, w, n_nodes, n_bins,
                                  rows_block=64, interpret=True)
    np.testing.assert_allclose(np.asarray(s_pl), np.asarray(s_ref),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# tree predict kernel
# ---------------------------------------------------------------------------

def _forest(rng, n, p, depth, n_trees, out, lead=()):
    """Rows, heap arrays and leaves; the leaves hold values near +-1e30 and
    +-1e-30 and exact zeros besides ordinary ones, and the last heap slots
    the +inf "never go right" sentinel."""
    h, l = 2 ** depth - 1, 2 ** depth
    x = rng.normal(size=lead + (n, p)).astype(np.float32)
    feat = rng.integers(0, p, lead + (n_trees, h)).astype(np.int32)
    thr = rng.normal(size=lead + (n_trees, h)).astype(np.float32)
    thr[..., -2:] = np.inf
    leaf = rng.normal(size=lead + (n_trees, l, out)).astype(np.float32)
    flat = leaf.reshape(-1)
    sign = rng.choice([-1.0, 1.0], flat.size).astype(np.float32)
    scale = rng.uniform(0.5, 2.0, flat.size).astype(np.float32)
    flat[0::5] = (sign * scale * 1e30)[0::5]
    flat[1::5] = (sign * scale * 1e-30)[1::5]
    flat[2::13] = 0.0
    return [jnp.asarray(a) for a in (x, feat, thr, leaf)]


@pytest.mark.parametrize("n,p,depth,n_trees,out,rows_block", [
    (128, 4, 3, 5, 1, 64),
    (256, 8, 4, 10, 3, 128),
    (512, 16, 7, 4, 2, 256),
    # odd row counts: the wrapper pads to the block and slices the output
    # (regression — used to hard-crash on `assert n % rows_block == 0`,
    # e.g. a 96-row serving bucket or an oversize exact-size request)
    (96, 4, 3, 5, 1, 64),
    (130, 8, 4, 3, 2, 64),
    (300, 5, 3, 4, 1, 256),
    (1, 3, 3, 2, 1, 256),
    # pion widths (p = out = 533, depth 7): blocks from plan(), several
    # row blocks, several grid steps of 6 trees, single-output leaves
    (97, 533, 7, 4, 533, None),
    (300, 533, 7, 4, 533, 128),
    (130, 533, 7, 12, 533, 64),
    (257, 40, 7, 10, 1, None),
])
def test_tree_predict_matches_ref(n, p, depth, n_trees, out, rows_block):
    """Bit for bit: every select in the kernel is exact and leaves are
    added in tree order, as the reference adds them."""
    x, feat, thr, leaf = _forest(np.random.default_rng(1), n, p, depth,
                                 n_trees, out)
    ref = forest_predict_ref(x, feat, thr, leaf, depth)
    got = forest_predict_pallas(x, feat, thr, leaf, depth,
                                rows_block=rows_block, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_tree_predict_bit_identical_at_pion_widths_vmapped():
    """The solve's call: vmapped over 3 classes at p = out = 533, depth 7,
    a row count that is no multiple of the block."""
    x, feat, thr, leaf = _forest(np.random.default_rng(3), 200, 533, 7, 4,
                                 533, lead=(3,))
    ref = jax.vmap(lambda *a: forest_predict_ref(*a, 7))(x, feat, thr, leaf)
    got = jax.vmap(lambda *a: forest_predict_pallas(
        *a, 7, rows_block=64, interpret=True))(x, feat, thr, leaf)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_split3_parts_add_back_exactly():
    rng = np.random.default_rng(4)
    a = np.concatenate([
        rng.normal(size=4096), rng.uniform(-1e30, 1e30, 512),
        rng.uniform(-1e-30, 1e-30, 512) * 10, [0.0, -0.0, 2.0 ** -110,
                                                3.4e38, -3.4e38],
    ]).astype(np.float32)
    parts = [np.asarray(v) for v in split3(jnp.asarray(a))]
    assert all(v.dtype == jnp.bfloat16 for v in parts)
    hi, mid, lo = (v.astype(np.float32) for v in parts)
    np.testing.assert_array_equal((hi + mid) + lo, a)


@pytest.mark.parametrize("depth", [1, 3, 7])
def test_path_matrix_marks_each_leafs_path(depth):
    """``cmp @ P == n_right`` holds for exactly the leaf the level-by-level
    walk reaches, for every assignment of comparisons."""
    P, n_right = path_matrix(depth)
    rng = np.random.default_rng(depth)
    cmp = rng.integers(0, 2, (256, 2 ** depth - 1)).astype(np.float32)
    node = np.zeros(256, int)
    for level in range(depth):
        heap = node + 2 ** level - 1
        node = node * 2 + cmp[np.arange(256), heap].astype(int)
    reached = (cmp @ P) == n_right
    np.testing.assert_array_equal(reached.sum(1), 1)
    np.testing.assert_array_equal(reached.argmax(1), node)


def test_tree_predict_plan_fits_vmem():
    """Blocks from the shape: the pion solve's bucket walks 512 rows and 10
    trees a step; widths whose working set cannot fit get no plan."""
    assert plan(1024, 100, 533, 533, 7) == (512, 10)
    assert plan(64, 100, 368, 368, 7) == (64, 10)
    assert plan(97, 7, 14, 1, 3) == (112, 7)
    for args in [(1024, 100, 533, 533, 7), (16, 1, 368, 1, 4)]:
        rows, trees = plan(*args)
        assert vmem_bytes(rows, trees, *args[2:]) <= VMEM_LIMIT // 2
    assert plan(16, 1, 40000, 40000, 7) is None
    assert plan(16, 1, 64, 64, 14) is None


def test_tree_predict_matches_trained_forest():
    """The kernel must agree with predictions of an actually-trained forest."""
    from repro.config import ForestConfig
    from repro.forest.binning import edges_with_sentinel, fit_bins, transform
    from repro.forest.boosting import fit_boosted

    rng = np.random.default_rng(2)
    x = rng.normal(size=(512, 5)).astype(np.float32)
    y = (np.sin(x[:, 0]) + x[:, 1]).astype(np.float32)[:, None]
    edges = fit_bins(jnp.asarray(x), 16)
    codes = transform(jnp.asarray(x), edges)
    fcfg = ForestConfig(n_trees=8, max_depth=4, n_bins=16, reg_lambda=1.0)
    res = fit_boosted(codes, jnp.asarray(y), jnp.ones((512,), jnp.float32),
                      edges_with_sentinel(edges), codes, jnp.asarray(y),
                      jnp.ones((512,), jnp.float32), fcfg)
    ref = forest_predict_ref(jnp.asarray(x), res.feat, res.thr_val, res.leaf, 4)
    got = forest_predict_pallas(jnp.asarray(x), res.feat, res.thr_val,
                                res.leaf, 4, rows_block=256, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


# ---------------------------------------------------------------------------
# flash attention kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,hq,hkv,sq,skv,d,causal,dtype", [
    (1, 2, 2, 128, 128, 32, True, jnp.float32),
    (2, 4, 2, 256, 256, 64, True, jnp.float32),
    (1, 8, 1, 128, 256, 64, False, jnp.float32),
    (2, 4, 4, 128, 128, 64, True, jnp.bfloat16),
    (1, 6, 3, 192, 192, 32, True, jnp.float32),
])
def test_flash_attention_matches_ref(b, hq, hkv, sq, skv, d, causal, dtype):
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(b, hq, sq, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, hkv, skv, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, hkv, skv, d)), dtype)
    ref = attention_ref(q, k, v, causal)
    got = flash_attention_pallas(q, k, v, causal=causal, bq=64, bk=64,
                                 interpret=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_matches_mea():
    """The model-side blocked attention and the kernel agree too."""
    from repro.models.attention import mea_attention
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.normal(size=(1, 4, 256, 32)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 2, 256, 32)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 2, 256, 32)).astype(np.float32))
    a = mea_attention(q, k, v, causal=True, q_block=64, kv_block=64)
    b_ = flash_attention_pallas(q, k, v, causal=True, bq=64, bk=64,
                                interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=2e-4,
                               atol=2e-4)
