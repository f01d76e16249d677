"""Compile the main path for a described TPU v5e (no chip needed).

Mosaic refuses kernels that interpret mode accepts (misaligned blocks, VMEM
overruns) and XLA refuses programs that do not fit HBM; both show up here,
at the CaloChallenge photon widths ``chip_smoke.py`` runs and the pion
widths of the benchmark's generation cell, without spending chip time.
Nothing executes, so these say nothing about results or speed.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library at a time, and every pytest-xdist
worker imports every test file.
"""
import os
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import chip_smoke  # noqa: E402
from repro.kernels.hist.hist_kernel import histogram_pallas  # noqa: E402
from repro.kernels.tree_predict.ops import default_impl  # noqa: E402
from repro.kernels.tree_predict.tree_kernel import \
    forest_predict_pallas  # noqa: E402
from repro.tabgen.fitting import single_fit_program  # noqa: E402
from repro.tabgen.samplers import get_sampler  # noqa: E402
from repro.tabgen.sampling import _solve_all_classes  # noqa: E402

P = 368                       # CaloChallenge photons
P_PIONS = 533
DEPTH, N_BINS = 7, 64
V5E_HBM_BYTES = 16 * 10 ** 9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("p,out", [(P, P), (P, 1), (P_PIONS, P_PIONS)])
def test_tree_predict_kernel_compiles_for_v5e(one_chip, p, out):
    n, trees = 4096, 20
    heap, leaves = 2 ** DEPTH - 1, 2 ** DEPTH
    fn = jax.jit(lambda x, f, t, l: forest_predict_pallas(x, f, t, l, DEPTH))
    compiled = fn.lower(_spec(one_chip, (n, p), jnp.float32),
                        _spec(one_chip, (trees, heap), jnp.int32),
                        _spec(one_chip, (trees, heap), jnp.float32),
                        _spec(one_chip, (trees, leaves, out), jnp.float32)
                        ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pion_solve_compiles_for_v5e_with_the_default_impl(one_chip):
    """The generation cell's whole solve (n_t 50, 3 classes, 100 trees of
    depth 7, p 533, 1024 rows a class) with the impl the default rule picks
    on a TPU: one Mosaic call, named by the ``tree_predict`` scope, no
    gather left in the traversal, and it fits one chip's HBM."""
    n_t, n_y, trees, m = 50, 3, 100, 1024
    heap, leaves = 2 ** DEPTH - 1, 2 ** DEPTH
    impl = default_impl("tpu", P_PIONS, P_PIONS, None, DEPTH)
    assert impl == "pallas"
    compiled = _solve_all_classes.lower(
        _spec(one_chip, (n_t, n_y, 1, trees, heap), jnp.int32),
        _spec(one_chip, (n_t, n_y, 1, trees, heap), jnp.float32),
        _spec(one_chip, (n_t, n_y, 1, trees, leaves, P_PIONS), jnp.float32),
        _spec(one_chip, (n_y, 2), jnp.uint32),
        _spec(one_chip, (n_y, P_PIONS), jnp.float32),
        _spec(one_chip, (n_y, P_PIONS), jnp.float32),
        _spec(one_chip, (n_t,), jnp.float32),
        solver_fn=get_sampler("euler").fn, m=m, depth=DEPTH, n_t=n_t,
        multi_output=True, eps=1e-3, impl=impl).compile()
    lines = compiled.as_text().splitlines()
    custom = [ln for ln in lines if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(custom) == 1
    assert "/tree_predict/" in custom[0].split('op_name="')[1].split('"')[0]
    assert not [ln for ln in lines if " gather(" in ln and "tree_predict" in ln]
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM_BYTES


@pytest.mark.parametrize("out", [1, P])
def test_hist_kernel_compiles_for_v5e(one_chip, out):
    n, n_nodes = 8192, 2 ** (DEPTH - 1)
    fn = jax.jit(lambda c, i, g, w: histogram_pallas(c, i, g, w, n_nodes,
                                                     N_BINS))
    compiled = fn.lower(_spec(one_chip, (n, P), jnp.int32),
                        _spec(one_chip, (n,), jnp.int32),
                        _spec(one_chip, (n, out), jnp.float32),
                        _spec(one_chip, (n,), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the result itself is [nodes, p, bins, out] f32; padding may not
    # multiply it (trailing size-1 lanes used to pad 128x)
    result = n_nodes * P * N_BINS * (out + 1) * 4
    assert compiled.memory_analysis().temp_size_in_bytes <= 2 * result


def test_smoke_fit_step_fits_v5e_hbm(one_chip):
    """The single-device fit step at ``chip_smoke``'s shapes and
    ``ENSEMBLES_PER_BATCH`` fits one 16 GB chip."""
    fcfg = chip_smoke.smoke_config()
    _, y = chip_smoke.make_data(chip_smoke.SHOWERS_PER_CLASS, seed=0)
    counts = np.bincount(y)
    bs = chip_smoke.ENSEMBLES_PER_BATCH
    compiled = single_fit_program(fcfg).lower(
        _spec(one_chip, (len(counts), int(counts.max()), P), jnp.float32),
        _spec(one_chip, (len(counts), int(counts.max())), jnp.float32),
        _spec(one_chip, (2,), jnp.uint32),
        _spec(one_chip, (bs,), jnp.float32),
        _spec(one_chip, (bs,), jnp.int32),
        _spec(one_chip, (bs,), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    need = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes)
    assert need < V5E_HBM_BYTES, need
