"""Plain reference of a ForestFlow deployment: fit decisions and the solve.

Written from the paper's method (arXiv:2408.16046 §2, App. B) in plain
``jax.numpy``, float32, imports nothing of the program and takes nothing
it made: the rows, noise, bin edges and codes are rebuilt here from the
same data and seeds. Two parts:

* ``check_fit`` follows the tree the program grew, level by level, and
  asks at each node whether the program's split is the best one the
  reference's own histogram offers (``split_regret``) and whether each
  leaf holds the Newton step of the rows routed to it (``leaf_gap``).
  Following the program's routing keeps the check exact under ties: two
  features that split a node's rows the same way are equally right.
* ``solve_rows`` integrates the flow ODE (Euler, t = 1 -> 0) for chosen
  rows through every step and tree, with the noise the program's sampler
  draws for that row, in ``dtype`` (float32, or bfloat16 for the control).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


# -- conventions copied from the program (per-class min-max scalers) --------

def scaler_span(mins, maxs):
    gt = maxs > mins
    return (maxs - mins) * gt + (1 - gt)


def rescale(x, mins, maxs):
    """Data space -> model space [-1, 1]."""
    return (x - mins) / scaler_span(mins, maxs) * 2.0 - 1.0


def unscale(x, mins, maxs):
    return (x + 1.0) / 2.0 * scaler_span(mins, maxs) + mins


def sample_labels(counts, n: int) -> np.ndarray:
    """Class index per row under empirical proportions (paper C.4)."""
    counts = np.asarray(counts)
    reps = np.floor(n * counts / counts.sum()).astype(int)
    rem = n - reps.sum()
    frac = n * counts / counts.sum() - reps
    reps[np.argsort(-frac)[:rem]] += 1
    return np.repeat(np.arange(len(counts)), reps)


def row_origin(counts, n: int, seed: int):
    """For each of the ``n`` rows a ``sample(n, seed=seed)`` call returns:
    (class index, index of the row inside its class's noise stream)."""
    label_idx = sample_labels(counts, n)
    per_class = np.bincount(label_idx, minlength=len(counts))
    start = np.concatenate([[0], np.cumsum(per_class)[:-1]])
    perm = np.random.default_rng(seed).permutation(n)
    cls = label_idx[perm]
    return cls, perm - start[cls]


# -- fit ---------------------------------------------------------------------

def _score(g, h, lam):
    return jnp.sum(jnp.square(g), axis=-1) / (h + lam + 1e-12)


@partial(jax.jit, static_argnames=("n_bins",))
def _fit_inputs(x0, seed, eid, t, *, n_bins: int):
    """Noised rows, targets and bin codes of one (t, class) ensemble;
    ``x0`` is the class's rows in model space, each repeated K times."""
    k = jax.random.fold_in(jax.random.PRNGKey(seed), 2 * eid)
    k_noise, _ = jax.random.split(k)
    x1 = jax.random.normal(k_noise, x0.shape, jnp.float32)
    xt = t * x1 + (1.0 - t) * x0
    tgt = x1 - x0
    n = xt.shape[0]
    s = jnp.sort(xt, axis=0)
    qs = jnp.arange(1, n_bins, dtype=jnp.float32) / n_bins
    idx = jnp.clip((qs * (jnp.float32(n) - 1.0)).astype(jnp.int32), 0, n - 1)
    edges = s[idx].T                                         # [p, n_bins-1]
    codes = jax.vmap(lambda col, e: jnp.searchsorted(e, col, side="left"),
                     in_axes=(1, 0), out_axes=1)(xt, edges).astype(jnp.int32)
    return xt, tgt, codes


@partial(jax.jit, static_argnames=("n_nodes", "n_bins", "block"))
def _best_gains(codes, g, node, lam, mcw, *, n_nodes: int, n_bins: int,
                block: int):
    """Best split gain per node over every (feature, bin) of the
    reference's own histogram: [n_nodes]."""
    n, p = codes.shape
    out = g.shape[1]
    pad = -p % block
    codes = jnp.pad(codes, ((0, 0), (0, pad)))      # pad features: one bin
    h = jnp.ones((n,), jnp.float32)

    def one_block(cb):                               # cb: [n, block]
        seg = node[:, None] * n_bins + cb

        def hist(s):
            G = jax.ops.segment_sum(g, s, num_segments=n_nodes * n_bins)
            H = jax.ops.segment_sum(h, s, num_segments=n_nodes * n_bins)
            return (G.reshape(n_nodes, n_bins, out),
                    H.reshape(n_nodes, n_bins))

        G, H = jax.vmap(hist, in_axes=1)(seg)        # [block, nodes, bins, ..]
        # running sums by adds alone (an associative scan), so no
        # compiler rewrites them into a lower-precision product
        GL = jax.lax.associative_scan(jnp.add, G, axis=2)
        HL = jax.lax.associative_scan(jnp.add, H, axis=2)
        GT, HT = GL[:, :, -1:], HL[:, :, -1:]
        gain = (_score(GL, HL, lam) + _score(GT - GL, HT - HL, lam)
                - _score(GT, HT, lam))
        valid = (HL >= mcw) & (HT - HL >= mcw)
        return jnp.max(jnp.where(valid, gain, -jnp.inf), axis=(0, 2))

    blocks = codes.reshape(n, (p + pad) // block, block).transpose(1, 0, 2)
    return jnp.max(jax.lax.map(one_block, blocks), axis=0)


@partial(jax.jit, static_argnames=("n_nodes",))
def _split_gain(g, node, right, lam, *, n_nodes: int):
    """Gain per node of the split the program chose (from its routing)."""
    r = right[:, None].astype(g.dtype)
    GR = jax.ops.segment_sum(g * r, node, num_segments=n_nodes)
    GL = jax.ops.segment_sum(g * (1 - r), node, num_segments=n_nodes)
    HR = jax.ops.segment_sum(r[:, 0], node, num_segments=n_nodes)
    HL = jax.ops.segment_sum(1 - r[:, 0], node, num_segments=n_nodes)
    return (_score(GL, HL, lam) + _score(GR, HR, lam)
            - _score(GL + GR, HL + HR, lam))


@partial(jax.jit, static_argnames=("n_leaves",))
def _leaves(g, node, lam, lr, *, n_leaves: int):
    G = jax.ops.segment_sum(g, node, num_segments=n_leaves)
    H = jax.ops.segment_sum(jnp.ones_like(node, jnp.float32), node,
                            num_segments=n_leaves)
    return -lr * G / (H[:, None] + lam + 1e-12)


def check_fit(X_class, *, seed: int, eid: int, t: float, forest: dict,
              trees: dict, block: int = 16) -> dict:
    """Check one fitted ensemble of one class.

    ``X_class`` [m, p]: the class's rows in data space, in input order.
    ``forest``: the configuration as run (``duplicate_k``, ``n_bins``,
    ``max_depth``, ``learning_rate``, ``reg_lambda``, ``min_child_weight``).
    ``trees``: the program's ``feat`` / ``thr_val`` [T, H] and ``leaf``
    [T, L, out] of this ensemble. Returns the worst ``split_regret`` (share
    of a level's best achievable gain that the program's splits missed)
    and ``leaf_gap`` (largest leaf difference over the tree's largest
    reference leaf) over all trees and levels.
    """
    with jax.default_matmul_precision("highest"):
        mins, maxs = X_class.min(axis=0), X_class.max(axis=0)
        x0 = np.repeat(rescale(X_class, mins, maxs).astype(np.float32),
                       forest["duplicate_k"], axis=0)
        xt, tgt, codes = _fit_inputs(jnp.asarray(x0), seed, eid,
                                     jnp.float32(t), n_bins=forest["n_bins"])
        depth = forest["max_depth"]
        lam = jnp.float32(forest["reg_lambda"])
        mcw = jnp.float32(forest["min_child_weight"])
        lr = jnp.float32(forest["learning_rate"])
        feat = jnp.asarray(trees["feat"])
        thr = jnp.asarray(trees["thr_val"])
        leaf_p = np.asarray(trees["leaf"])
        rows = jnp.arange(xt.shape[0])
        pred = jnp.zeros_like(tgt)
        regret, leaf_gap = 0.0, 0.0
        for r in range(feat.shape[0]):
            g = pred - tgt
            node = jnp.zeros((xt.shape[0],), jnp.int32)
            for level in range(depth):
                n_nodes = 2 ** level
                best = np.asarray(_best_gains(
                    codes, g, node, lam, mcw, n_nodes=2 ** (depth - 1),
                    n_bins=forest["n_bins"], block=block))[:n_nodes]
                heap = node + (n_nodes - 1)
                right = xt[rows, feat[r][heap]] > thr[r][heap]
                got = np.asarray(_split_gain(g, node, right, lam,
                                             n_nodes=n_nodes))
                best = np.where(np.isfinite(best), np.maximum(best, 0.0), 0.0)
                if best.sum() > 0:
                    missed = np.maximum(best - got, 0.0).sum()
                    regret = max(regret, float(missed / best.sum()))
                node = node * 2 + right.astype(jnp.int32)
            leaf_r = _leaves(g, node, lam, lr, n_leaves=2 ** depth)
            scale = float(jnp.max(jnp.abs(leaf_r)))
            gap = float(np.max(np.abs(leaf_p[r] - np.asarray(leaf_r))))
            leaf_gap = max(leaf_gap, gap / max(scale, 1e-30))
            pred = pred + leaf_r[node]
    return {"split_regret": regret, "leaf_gap": leaf_gap}


# -- solve -------------------------------------------------------------------

@partial(jax.jit, static_argnames=("depth", "dtype"))
def _solve_class(x, feat, thr, leaf, hs, *, depth: int, dtype):
    """Euler steps t = 1 -> 0 for rows of one class. feat/thr/leaf carry
    the steps in solve order: [steps, T, ...]."""
    x = x.astype(dtype)
    rows = jnp.arange(x.shape[0])

    def step(x, inp):
        h, f_s, t_s, l_s = inp

        def tree(acc, tr):
            f, t, lv = tr
            node = jnp.zeros((x.shape[0],), jnp.int32)
            for level in range(depth):
                heap = node + (2 ** level - 1)
                node = node * 2 + (x[rows, f[heap]] > t[heap]).astype(jnp.int32)
            return acc + lv[node], None

        v, _ = jax.lax.scan(tree, jnp.zeros_like(x),
                            (f_s, t_s.astype(dtype), l_s.astype(dtype)))
        return x - h.astype(dtype) * v, None

    x, _ = jax.lax.scan(step, x, (hs, feat, thr, leaf))
    return x.astype(jnp.float32)


def solve_rows(feat, thr, leaf, *, seeds, cls, idx, depth: int,
               dtype=jnp.float32, block: int = 256) -> np.ndarray:
    """Model-space rows [r, p]: row ``j`` is the row that a
    ``sample(seed=seeds[j])`` call draws for class ``cls[j]`` at in-class
    index ``idx[j]`` (see :func:`row_origin`), solved through every step
    and tree in ``dtype``.

    ``feat``/``thr`` [n_t, n_y, 1, T, H], ``leaf`` [n_t, n_y, 1, T, L, p]:
    the flow grid in timestep order (t = 0 first).
    """
    n_t, n_y = feat.shape[:2]
    p = leaf.shape[-1]
    ts = jnp.linspace(0.0, 1.0, n_t)
    hs = (ts[1:] - ts[:-1])[::-1]
    cls, idx = np.asarray(cls), np.asarray(idx)
    seeds = np.broadcast_to(np.asarray(seeds), cls.shape)
    # each row's noise key: the call's per-class key, split once, as the
    # sampler derives it (host-side, so any seed size is exact)
    keys = np.zeros((len(cls), 2), np.uint32)
    for s in np.unique(seeds):
        per_class = jax.random.split(jax.random.PRNGKey(int(s) + 7), n_y)
        k_x1 = np.asarray(jax.vmap(lambda k: jax.random.split(k)[0])(
            per_class))
        keys[seeds == s] = k_x1[cls[seeds == s]]
    draw = jax.jit(jax.vmap(lambda k, i: jax.random.normal(
        jax.random.fold_in(k, i), (p,), jnp.float32)))
    out = np.zeros((len(cls), p), np.float32)
    for c in np.unique(cls):
        sel = np.flatnonzero(cls == c)
        # the grid for this class in solve order: t = 1 first, t = 0 unused
        f_c = feat[:, c, 0][::-1][: n_t - 1]
        t_c = thr[:, c, 0][::-1][: n_t - 1]
        l_c = leaf[:, c, 0][::-1][: n_t - 1]
        for b in range(0, len(sel), block):
            rows = sel[b:b + block]
            # every block padded to ``block`` rows: one compiled program
            k_pad = np.zeros((block, 2), np.uint32)
            i_pad = np.zeros((block,), np.int64)
            k_pad[:len(rows)], i_pad[:len(rows)] = keys[rows], idx[rows]
            x1 = draw(jnp.asarray(k_pad), jnp.asarray(i_pad))
            out[rows] = np.asarray(_solve_class(
                x1, f_c, t_c, l_c, hs, depth=depth, dtype=dtype))[:len(rows)]
    return out
