"""Inputs and weights of every cell, made from the run's seed.

The shower generator is a copy of the program's
``repro.data.calorimeter.generate`` (CaloChallenge 2022 dataset-1 schema:
cylindrical voxel grid, 15 log-spaced incident energies 2^8..2^22 MeV,
heavy sparsity), kept here because the data is part of the yardstick. One
change: every class gets exactly ``per_class`` showers, so every seed
gives the same shapes and the same work.

Random forests stand in for trained ones in the generation and serving
cells: traversal does the same work whatever the split values are, and a
random forest of Table-9 size needs no hour-long fit first.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# (layers, radial, angular) voxel grids; "tiny" is for rehearsals on the CPU
GEOMETRY = {"photons": (5, 8, 9), "pions": (7, 8, 9), "tiny": (2, 2, 3)}
P_TARGET = {"photons": 368, "pions": 533, "tiny": 14}
N_CLASSES = 15


FOREST_KEYS = ("method", "n_t", "duplicate_k", "n_trees", "max_depth",
               "n_bins", "multi_output", "learning_rate", "reg_lambda",
               "min_child_weight", "early_stop_rounds")


def forest_config(cfg: dict, **overrides):
    """The program's ``ForestConfig`` for a configuration as run."""
    from repro.config import ForestConfig
    return ForestConfig(**{k: cfg[k] for k in FOREST_KEYS}, **overrides)


def sub_seeds(seed: int, n: int) -> list:
    """``n`` independent non-negative int31 seeds from any whole number."""
    state = np.random.SeedSequence(int(seed)).generate_state(n, np.uint32)
    return [int(s) & 0x7FFFFFFF for s in state]


def showers(dataset: str, per_class: int, seed: int):
    """(X [15 * per_class, p] fp32 energies, y [n] int64 class labels),
    rows in a seeded random order."""
    layers, nr, na = GEOMETRY[dataset]
    p = P_TARGET[dataset]
    rng = np.random.default_rng(seed)
    y = rng.permutation(np.repeat(np.arange(N_CLASSES), per_class))
    n = len(y)
    e_inc = 2.0 ** (y + 8)
    depth = np.arange(layers)[None, :]
    peak = 1.0 + 0.15 * y[:, None] + 0.3 * rng.normal(size=(n, 1))
    long_prof = np.exp(-0.5 * ((depth - peak) / 1.2) ** 2)
    long_prof /= long_prof.sum(1, keepdims=True)
    r = np.arange(nr)[None, :]
    rad_prof = np.exp(-r / (1.0 + 0.05 * y[:, None]))
    rad_prof /= rad_prof.sum(1, keepdims=True)
    phase = rng.uniform(0, 2 * np.pi, size=(n, 1))
    ang = 1.0 + 0.3 * np.cos(np.linspace(0, 2 * np.pi, na)[None, :] + phase)
    ang /= ang.sum(1, keepdims=True)
    vox = (e_inc[:, None, None, None] * long_prof[:, :, None, None]
           * rad_prof[:, None, :, None] * ang[:, None, None, :])
    vox = vox * rng.lognormal(0.0, 0.35, size=vox.shape)
    vox[vox < 0.01 * e_inc[:, None, None, None] / vox.shape[1]] = 0.0
    X = vox.reshape(n, -1).astype(np.float32)
    if X.shape[1] < p:
        pad = np.zeros((n, p - X.shape[1]), np.float32)
        pad[:, 0] = X.sum(1)
        if pad.shape[1] > 1:
            pad[:, 1] = (X > 0).sum(1)
        X = np.concatenate([X, pad], axis=1)
    return X[:, :p], y.astype(np.int64)


@partial(jax.jit, static_argnames=("n_t", "n_y", "n_trees", "depth", "p"))
def _forest(key, *, n_t, n_y, n_trees, depth, p):
    k_f, k_t, k_l, k_s = jax.random.split(key, 4)
    H, L = 2 ** depth - 1, 2 ** depth
    lead = (n_t, n_y, 1, n_trees)
    feat = jax.random.randint(k_f, lead + (H,), 0, p, jnp.int32)
    thr = jax.random.normal(k_t, lead + (H,), jnp.float32)
    # 100 trees of this spread sum to a velocity of about unit size, as a
    # fitted flow's do; the ODE state stays within a few units of 0
    leaf = 0.1 * jax.random.normal(k_l, lead + (L, p), jnp.float32)
    span = jnp.exp(jax.random.normal(k_s, (n_y, p), jnp.float32))
    maxs = span * (2.0 ** (8 + jnp.arange(n_y, dtype=jnp.float32)))[:, None]
    return feat, thr, leaf, jnp.zeros((n_y, p), jnp.float32), maxs


def random_forest(seed: int, *, n_t: int, n_y: int, n_trees: int,
                  depth: int, p: int):
    """(feat, thr_val, leaf, mins, maxs) on the device, in the types the
    program serves (int32 / fp32), made in one jitted call."""
    return _forest(jax.random.PRNGKey(seed), n_t=n_t, n_y=n_y,
                   n_trees=n_trees, depth=depth, p=p)
