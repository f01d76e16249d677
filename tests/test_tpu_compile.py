"""Compile the main path for a described TPU v5e (no chip needed).

Mosaic refuses kernels that interpret mode accepts (misaligned blocks, VMEM
overruns) and XLA refuses programs that do not fit HBM; both show up here,
at the CaloChallenge photon widths ``chip_smoke.py`` runs, without spending
chip time. Nothing executes, so these say nothing about results or speed.

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
from repro.kernels.tree_predict.tree_kernel import \
    forest_predict_pallas  # noqa: E402
from repro.tabgen.fitting import single_fit_program  # noqa: E402

P = 368                       # CaloChallenge photons
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


@pytest.mark.parametrize("out", [P, 1])
def test_tree_predict_kernel_compiles_for_v5e(one_chip, out):
    n, trees = 4096, 20
    heap, leaves = 2 ** DEPTH - 1, 2 ** DEPTH
    fn = jax.jit(lambda x, f, t, l: forest_predict_pallas(x, f, t, l, DEPTH))
    compiled = fn.lower(_spec(one_chip, (n, P), jnp.float32),
                        _spec(one_chip, (trees, heap), jnp.int32),
                        _spec(one_chip, (trees, heap), jnp.float32),
                        _spec(one_chip, (trees, leaves, out), jnp.float32)
                        ).compile()
    assert "tpu_custom_call" in compiled.as_text()


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
