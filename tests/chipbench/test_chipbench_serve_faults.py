"""The serving cell's check fails its control and each fault it can
have.

Rehearsed on the CPU at tiny size: the harness's look for a chip is
skipped and the rest of a run drives the program with the timed path
broken underneath. The control is the plain reference, in bfloat16, in
the program's place.
"""
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench_tiny import tiny_tree  # noqa: E402

from chipbench import run as harness  # noqa: E402

CELL = "calo_photons.serve"


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return tiny_tree(tmp_path_factory.mktemp("bench"))


def run_cell(root, cell, control=False):
    return harness.run(root, cell, 2 ** 31 + 57, 0.6, False, platform=None,
                       control=control, compile_cache=False)


def _wrap_solver(monkeypatch, edit):
    # the solve is jitted: clear the compiled programs so the broken
    # solver is traced in, and again afterwards so it does not linger
    import repro.core.generate as G
    real = G.flow_euler

    def broken(x1, *a, **k):
        return edit(x1, real(x1, *a, **k))
    jax.clear_caches()
    monkeypatch.setattr(G, "flow_euler", broken)


def _fault_unchanged(monkeypatch):
    # the solve's steps return the state they were given
    _wrap_solver(monkeypatch, lambda x1, x0: x1)


def _fault_half_batch(monkeypatch):
    # half of a call's rows left out: zeros where they should be
    from repro.tabgen import sampling
    real = sampling.SampleHandle.result

    def halved(self):
        X, y = real(self)
        X = X.copy()
        X[len(X) // 2:] = 0.0
        return X, y
    monkeypatch.setattr(sampling.SampleHandle, "result", halved)


def _fault_altered(monkeypatch):
    # one feature of every row altered where the solve produces it
    _wrap_solver(monkeypatch, lambda x1, x0: x0.at[:, 0].add(0.01))


def _fault_sliced(monkeypatch):
    # the control plane hands each request its neighbour's rows
    from repro.tabgen import sampling
    real = sampling.SampleHandle.result

    def shifted(self):
        X, y = real(self)
        return np.roll(X, 1, axis=0), np.roll(y, 1)
    monkeypatch.setattr(sampling.SampleHandle, "result", shifted)


def test_control_is_caught(tiny):
    res = run_cell(tiny, CELL, control=True)
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("fault", [_fault_half_batch, _fault_sliced,
                                   _fault_altered, _fault_unchanged])
def test_fault_is_caught(tiny, monkeypatch, request, fault):
    request.addfinalizer(jax.clear_caches)
    fault(monkeypatch)
    res = run_cell(tiny, CELL)
    assert res["correct"] is False, res["checks"]
