"""Names on the profiler's clock: scoped spans always enter a
``jax.profiler.TraceAnnotation``, the generation path records its host
phases as ``sample.*`` spans, and the device programs carry the named
scopes ``tree_predict``, ``sample.noise``, ``sample.unscale`` and ``hist``
in their ops' ``op_name`` metadata (what a trace reads as ``tf_op``)."""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ForestConfig
from repro.forest.hist import build_histogram
from repro.obs import Tracer, default_tracer
from repro.tabgen import ForestArtifacts, sample
from repro.tabgen.sampling import _solve_all_classes, sample_async
from repro.tabgen.samplers import get_sampler

PHASES = ("sample.prepare", "sample.dispatch", "sample.wait",
          "sample.fetch", "sample.finish")


def tiny_artifacts(impl="xla", n_t=3, n_y=2, trees=2, depth=2, p=4):
    rng = np.random.default_rng(0)
    lead = (n_t, n_y, 1, trees)
    H, L = 2 ** depth - 1, 2 ** depth
    zeros = np.zeros((n_t, n_y, 1), np.int32)
    return ForestArtifacts(
        feat=rng.integers(0, p, lead + (H,)).astype(np.int32),
        thr_val=rng.normal(size=lead + (H,)).astype(np.float32),
        leaf=(0.1 * rng.normal(size=lead + (L, p))).astype(np.float32),
        best_round=zeros + trees - 1, rounds_run=zeros + trees,
        val_curve=np.zeros((n_t, n_y, 1, trees), np.float32),
        mins=np.zeros((n_y, p), np.float32),
        maxs=np.ones((n_y, p), np.float32),
        classes=np.arange(n_y), counts=np.full((n_y,), 10),
        config=ForestConfig(method="flow", n_t=n_t, n_trees=trees,
                            max_depth=depth, multi_output=True,
                            predict_impl=impl))


def op_names(compiled_text: str) -> set:
    return set(re.findall(r'op_name="([^"]*)"', compiled_text))


def has_component(names, component):
    """Whether a name holds ``component`` whole, bare or inside a
    transform's wrapper (``vmap(sample.noise)``)."""
    whole = re.compile(r"(^|[/;(])" + re.escape(component) + r"([/;)]|$)")
    return any(whole.search(n) for n in names)


class _Recording:
    """Stands in for ``jax.profiler.TraceAnnotation``."""
    log = []

    def __init__(self, name, **kwargs):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))


def test_scoped_span_enters_a_trace_annotation(monkeypatch):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Recording)
    _Recording.log = []
    tr = Tracer()
    with tr.span("outer"):
        with tr.span("inner", rows=3):
            pass
    assert _Recording.log == [("enter", "outer"), ("enter", "inner"),
                              ("exit", "inner"), ("exit", "outer")]
    # split-form spans cross threads and carry no annotation
    tr.start("split").end()
    assert len(_Recording.log) == 4
    assert [s.name for s in tr.spans()] == ["inner", "outer", "split"]


def test_scoped_span_reaches_a_profiler_capture(tmp_path):
    from jax.profiler import ProfileData
    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("probe.span"):
            jax.block_until_ready(jnp.ones(3) + 1)
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    names = {ev.name for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for ev in line.events}
    assert "probe.span" in names


def test_sample_records_its_phases_in_order():
    art = tiny_artifacts()
    tracer = default_tracer()
    tracer.clear()
    X, y = sample(art, 7, seed=3, pad_to=8)
    spans = tracer.spans()
    assert [s.name for s in spans] == list(PHASES)
    for s in spans:
        assert s.attrs == {"rows": 7, "bucket": 8, "classes": 2}
    for a, b in zip(spans, spans[1:]):
        assert a.t_end <= b.t_start
    assert X.shape == (7, 4) and y.shape == (7,)


def test_sample_equals_its_async_form_bit_for_bit():
    art = tiny_artifacts()
    X1, y1 = sample(art, 9, seed=11)
    X2, y2 = sample_async(art, 9, seed=11).result()
    np.testing.assert_array_equal(X1, X2)
    np.testing.assert_array_equal(y1, y2)


def _solve_op_names(impl):
    art = tiny_artifacts(impl)
    fcfg = art.config
    keys = jax.random.split(jax.random.PRNGKey(7), art.n_y)
    ts = jnp.linspace(1.0, 0.0, fcfg.n_t)
    lowered = _solve_all_classes.lower(
        art.feat, art.thr_val, art.leaf, keys, art.mins, art.maxs, ts,
        solver_fn=get_sampler("euler").fn, m=8, depth=fcfg.max_depth,
        n_t=fcfg.n_t, multi_output=True, eps=fcfg.eps_diff, impl=impl)
    return op_names(lowered.compile().as_text())


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_solve_program_carries_the_traversal_scope(impl):
    names = _solve_op_names(impl)
    assert has_component(names, "tree_predict"), sorted(names)[:20]
    assert has_component(names, "sample.noise")
    assert has_component(names, "sample.unscale")
    # the scope names the traversal, not the whole program
    assert not all(has_component([n], "tree_predict") for n in names)


@functools.partial(jax.jit, static_argnames=("impl",))
def _histogram(codes, node_id, g, w, impl):
    return build_histogram(codes, node_id, g, w, 2, 8, impl=impl)


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_histogram_carries_the_hist_scope(impl):
    rng = np.random.default_rng(0)
    codes = jnp.asarray(rng.integers(0, 8, (64, 3)), jnp.int32)
    node_id = jnp.asarray(rng.integers(0, 2, 64), jnp.int32)
    g = jnp.asarray(rng.normal(size=(64, 2)), jnp.float32)
    w = jnp.ones((64,), jnp.float32)
    lowered = _histogram.lower(codes, node_id, g, w, impl=impl)
    names = op_names(lowered.compile().as_text())
    assert has_component(names, "hist"), sorted(names)[:20]
