"""Generation: one jitted, class-vmapped solve per call.

The seed ``generate()`` looped over classes in Python, re-wrapped (and
re-uploaded) each class's forests into a :class:`PackedForest`, and launched
one solver program per class — ``n_y`` device dispatches per call. Here the
whole call is a single program: noise is drawn on device, the chosen sampler
integrates all classes at once (``vmap`` over the stacked ``[n_y]`` axis of
:class:`ForestArtifacts`), per-class unscaling happens inside the same
program, and padding rows (classes get unequal row counts) are dropped on
the host afterwards.

``pad_to`` rounds the per-class row budget up to a fixed bucket so a serving
host (:mod:`repro.launch.serve_forest`) can pre-compile one program per
(sampler, bucket) and reuse it for every request size below the bucket.

``mesh`` shards the solve the way ``fit_artifacts`` shards training: the
class-vmapped axis over the ``model`` mesh axis, rows over the data axes
(GSPMD sharding constraints inside the one jitted program — noise is drawn
per (class, row) counter, so the sharded solve is value-identical to the
single-device one). ``impl`` picks the tree-traversal backend and is
resolved per call (argument > ``ForestConfig.predict_impl`` >
``REPRO_TREE_PREDICT_IMPL`` > ``xla``).
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.core import interpolants as itp
from repro.forest.packed import PackedForest
from repro.kernels.dispatch import resolve_impl
from repro.kernels.tree_predict.ops import ENV_VAR as _PREDICT_ENV
from repro.tabgen.artifacts import ForestArtifacts, solve_axes, unscale
from repro.tabgen.samplers import default_sampler, get_sampler


def sample_labels(counts: np.ndarray, n: int, rng: np.random.Generator,
                  mode: str = "label") -> np.ndarray:
    """Class indices for ``n`` rows. ``label`` = deterministic empirical
    proportions (paper C.4); ``multinomial`` = iid draws."""
    counts = np.asarray(counts)
    if mode == "multinomial":
        probs = counts / counts.sum()
        idx = rng.choice(len(counts), size=n, p=probs)
    else:
        reps = np.floor(n * counts / counts.sum()).astype(int)
        rem = n - reps.sum()
        frac = n * counts / counts.sum() - reps
        extra = np.argsort(-frac)[:rem]
        reps[extra] += 1
        idx = np.repeat(np.arange(len(counts)), reps)
    idx.sort()
    return idx


def resolve_mesh(mesh):
    """``"auto"`` | Mesh | None -> Mesh | None (mirrors ``fit_artifacts``).

    Public: the serving host (:mod:`repro.launch.serve_forest`) resolves its
    ``mesh=`` knob through the same contract as :func:`sample`.
    """
    if mesh is None or isinstance(mesh, Mesh):
        return mesh
    if mesh == "auto":
        from repro.launch.mesh import auto_forest_mesh
        return auto_forest_mesh()
    raise ValueError(f"mesh={mesh!r}: expected a Mesh, None, or 'auto'")


@partial(jax.jit, static_argnames=("solver_fn", "m", "depth", "n_t",
                                   "multi_output", "eps", "impl", "mesh"))
def _solve_all_classes(feat, thr_val, leaf, keys, mins, maxs, ts, *,
                       solver_fn, m: int, depth: int, n_t: int,
                       multi_output: bool, eps: float, impl: str = "xla",
                       mesh: Optional[Mesh] = None):
    """[n_t, n_y, ...] forests -> [n_y, m, p] unscaled samples; one program.

    The jit cache key is (solver fn, bucket m, forest shapes, impl, mesh) —
    repeat calls at the same bucket reuse the compiled program, and keying
    on the resolved *function* (not its registry name) means re-registering
    a sampler under an existing name correctly invalidates the cache.

    With a ``mesh``, sharding constraints partition the program: the class
    axis over ``model`` (when divisible), rows over the data axes. All the
    math is per-(class, row) deterministic, so the partitioned program
    computes the same values as the single-device one.
    """
    if mesh is not None:
        model, rows = solve_axes(mesh, feat.shape[1])

        def cns(arr, *spec):
            return jax.lax.with_sharding_constraint(
                arr, NamedSharding(mesh, PartitionSpec(*spec)))

        feat = cns(feat, None, model)
        thr_val = cns(thr_val, None, model)
        leaf = cns(leaf, None, model)
        keys = cns(keys, model)
        mins = cns(mins, model)
        maxs = cns(maxs, model)

    def one_class(feat_c, thr_c, leaf_c, key_c, mn, mx):
        k_x1, k_solve = jax.random.split(key_c)
        # counter-based per-row noise: row i draws the same x1 whatever the
        # bucket m, so deterministic samplers are padding-invariant (a
        # request served at bucket 256 equals the same request at 1024)
        row_keys = jax.vmap(jax.random.fold_in, (None, 0))(k_x1, jnp.arange(m))
        x1 = jax.vmap(
            lambda k: jax.random.normal(k, (mn.shape[0],), jnp.float32)
        )(row_keys)
        forests = PackedForest(feat_c, thr_c, leaf_c, multi_output)
        x0 = solver_fn(x1, forests, depth=depth, n_t=n_t, ts=ts,
                       key=k_solve, eps=eps, impl=impl)
        return unscale(x0, mn, mx)

    out = jax.vmap(one_class, in_axes=(1, 1, 1, 0, 0, 0))(
        feat, thr_val, leaf, keys, mins, maxs)
    if mesh is not None:
        out = cns(out, model, rows, None)
    return out


def _resolve_sampler(fcfg, sampler: Optional[str]):
    """Name -> spec, validated against the artifacts' interpolant family."""
    name = sampler or default_sampler(fcfg.method, fcfg.diff_sampler)
    spec = get_sampler(name)
    if spec.method != fcfg.method:
        raise ValueError(
            f"sampler {name!r} integrates {spec.method!r} but artifacts "
            f"were trained with method={fcfg.method!r}")
    return name, spec


class SampleHandle:
    """An in-flight :func:`sample`: device work dispatched, host finish
    deferred.

    Holds the (asynchronously executing) ``[n_y, m, p]`` device array plus
    the host-side bookkeeping needed to finish the call. ``result()`` blocks
    until the device values are ready, then unpads and shuffles exactly the
    way the synchronous path does — so ``sample_async(...).result()`` is
    bit-identical to ``sample(...)``. A serving waiter thread can resolve
    handles while the dispatcher admits the next batch (in-flight batching:
    queue wait no longer stacks on device time)."""

    def __init__(self, x_dev, per_class, classes, rng):
        self._x_dev = x_dev
        self._per_class = per_class
        self._classes = classes
        self._rng = rng
        # trace context, stamped by the serving scheduler via tag(): which
        # coalesced batch this dispatch is, and which request traces ride it
        self.batch_id: Optional[int] = None
        self.trace_ids: Tuple[str, ...] = ()

    def tag(self, *, batch_id: Optional[int] = None,
            trace_ids: Sequence[str] = ()) -> "SampleHandle":
        """Attach serving trace context (best-effort metadata; never read
        by the sampling math).  Returns self for chaining."""
        self.batch_id = batch_id
        self.trace_ids = tuple(trace_ids)
        return self

    @property
    def sharding(self):
        """Placement of the in-flight ``[n_y, m, p]`` device result — under
        ``mesh=`` it spans every device of the mesh."""
        return self._x_dev.sharding

    def result(self) -> Tuple[np.ndarray, np.ndarray]:
        x_all = np.asarray(self._x_dev)             # blocks: [n_y, m, p]
        X = np.concatenate([x_all[yi, :c]
                            for yi, c in enumerate(self._per_class)])
        y = np.repeat(self._classes, self._per_class)
        perm = self._rng.permutation(len(X))
        return X[perm], y[perm]


def sample_async(artifacts: ForestArtifacts, n: int, *,
                 sampler: Optional[str] = None, seed: int = 0,
                 pad_to: Optional[int] = None, mesh=None,
                 impl: Optional[str] = None) -> SampleHandle:
    """Dispatch a generate call without blocking on the device.

    Everything up to (and including) the jitted solve runs here — jax
    dispatch is asynchronous, so this returns as soon as the program is
    enqueued. The returned :class:`SampleHandle` finishes the call;
    :func:`sample` is literally ``sample_async(...).result()``, so both
    paths share one jit cache and one output distribution by construction.
    """
    fcfg = artifacts.config
    _, spec = _resolve_sampler(fcfg, sampler)
    impl = resolve_impl(impl, fcfg.predict_impl, env_var=_PREDICT_ENV)
    mesh = resolve_mesh(mesh)
    if mesh is not None and impl == "pallas":
        # GSPMD cannot partition a Mosaic kernel; it needs a shard_map
        # route, which the solve does not have yet
        raise ValueError("impl='pallas' has no mesh route: sample with "
                         "mesh=None for the kernel, or impl='xla' under "
                         "a mesh")
    rng = np.random.default_rng(seed)
    label_idx = sample_labels(artifacts.counts, n, rng, fcfg.label_sampler)
    n_y = artifacts.n_y
    per_class = np.bincount(label_idx, minlength=n_y)
    m = int(per_class.max())
    if pad_to is not None:
        if pad_to < m:
            raise ValueError(f"pad_to={pad_to} < largest class batch {m}")
        m = int(pad_to)
    ts = jnp.asarray(itp.timesteps(fcfg.method, fcfg.n_t, fcfg.eps_diff,
                                   fcfg.t_schedule))
    keys = jax.random.split(jax.random.PRNGKey(seed + 7), n_y)
    x_all = _solve_all_classes(
        artifacts.feat, artifacts.thr_val, artifacts.leaf, keys,
        artifacts.mins, artifacts.maxs, ts,
        solver_fn=spec.fn, m=m, depth=fcfg.max_depth, n_t=fcfg.n_t,
        multi_output=fcfg.multi_output, eps=fcfg.eps_diff, impl=impl,
        mesh=mesh)
    return SampleHandle(x_all, per_class, np.asarray(artifacts.classes), rng)


def sample(artifacts: ForestArtifacts, n: int, *,
           sampler: Optional[str] = None, seed: int = 0,
           pad_to: Optional[int] = None, mesh=None,
           impl: Optional[str] = None):
    """Generate ``n`` rows (and their labels) from trained artifacts.

    One device dispatch regardless of the number of classes. ``pad_to``
    fixes the per-class row bucket (>= the largest per-class request) for
    jit-cache-friendly serving. ``mesh`` (``"auto"`` | Mesh | None) shards
    the solve — classes on the model axis, rows on the data axes — for a
    fixed seed the output matches the single-device solve. ``impl`` picks
    the tree-predict backend; pre-shard the artifacts once with
    :meth:`ForestArtifacts.shard` to avoid a per-call reshard when serving.
    """
    return sample_async(artifacts, n, sampler=sampler, seed=seed,
                        pad_to=pad_to, mesh=mesh, impl=impl).result()


def sample_loop_reference(artifacts: ForestArtifacts, n: int, *,
                          sampler: Optional[str] = None, seed: int = 0
                          ) -> Tuple[np.ndarray, np.ndarray]:
    """The pre-redesign path: one solver dispatch per class, host-side
    unscaling. Kept as the baseline for ``benchmarks/bench_generation.py``
    (and as executable documentation of what the vmapped path replaced)."""
    fcfg = artifacts.config
    _, spec = _resolve_sampler(fcfg, sampler)
    rng = np.random.default_rng(seed)
    label_idx = sample_labels(artifacts.counts, n, rng, fcfg.label_sampler)
    key = jax.random.PRNGKey(seed + 7)
    ts = jnp.asarray(itp.timesteps(fcfg.method, fcfg.n_t, fcfg.eps_diff,
                                   fcfg.t_schedule))
    mins = np.asarray(artifacts.mins)
    maxs = np.asarray(artifacts.maxs)
    outs, labels = [], []
    for yi in range(artifacts.n_y):
        n_c = int((label_idx == yi).sum())
        if n_c == 0:
            continue
        key, k1, k2 = jax.random.split(key, 3)
        x1 = jax.random.normal(k1, (n_c, artifacts.p), jnp.float32)
        x0 = spec.fn(x1, artifacts.class_forest(yi), depth=fcfg.max_depth,
                     n_t=fcfg.n_t, ts=ts, key=k2, eps=fcfg.eps_diff)
        outs.append(unscale(np.asarray(x0), mins[yi], maxs[yi]))
        labels.append(np.full((n_c,), artifacts.classes[yi]))
    X = np.concatenate(outs, axis=0)
    y = np.concatenate(labels, axis=0)
    perm = rng.permutation(len(X))
    return X[perm], y[perm]
