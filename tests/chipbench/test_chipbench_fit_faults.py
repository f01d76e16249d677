"""The fit cell's check fails its control and each fault it can have.

Rehearsed on the CPU at tiny size: the harness's look for a chip is
skipped and the rest of a run drives the program with the timed path
broken underneath. The control is the program's own bf16 histogram path.
"""
import sys
from pathlib import Path

import jax
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench_tiny import tiny_tree  # noqa: E402

from chipbench import run as harness  # noqa: E402

CELL = "calo_photons.fit"


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return tiny_tree(tmp_path_factory.mktemp("bench"))


@pytest.fixture
def fresh_programs():
    from repro.tabgen.fitting import single_fit_program
    single_fit_program.cache_clear()
    jax.clear_caches()
    yield
    single_fit_program.cache_clear()
    jax.clear_caches()


def run_cell(root, control=False):
    return harness.run(root, CELL, 2 ** 31 + 31, 0.3, False, platform=None,
                       control=control, compile_cache=False)


def _wrap_fit_ensemble(monkeypatch, edit):
    import repro.tabgen.fitting as fitting
    real = fitting.fit_ensemble

    def broken(*a, **k):
        res = real(*a, **k)
        return res._replace(leaf=edit(res.leaf))
    monkeypatch.setattr(fitting, "fit_ensemble", broken)


def _fault_unchanged(monkeypatch):
    # the fit step returns the model it was given: no tree adds anything
    _wrap_fit_ensemble(monkeypatch, lambda leaf: leaf * 0.0)


def _fault_altered(monkeypatch):
    # one leaf of every tree altered where the step produces it
    _wrap_fit_ensemble(monkeypatch, lambda leaf: leaf.at[..., 0, :].add(0.5))


def _fault_half_batch(monkeypatch):
    # half of the rows left out: weight 0, the sums taken over the rest
    import repro.tabgen.fitting as fitting
    real = fitting.ensemble_inputs

    def broken(*a, **k):
        codes, tgt, w, *rest = real(*a, **k)
        return (codes, tgt, w.at[w.shape[0] // 2:].set(0.0), *rest)
    monkeypatch.setattr(fitting, "ensemble_inputs", broken)


def test_sound_program_is_correct(tiny, fresh_programs):
    assert run_cell(tiny)["correct"] is True


@pytest.mark.parametrize("fault", [_fault_unchanged, _fault_altered,
                                   _fault_half_batch])
def test_fault_is_caught(tiny, fresh_programs, monkeypatch, fault):
    fault(monkeypatch)
    res = run_cell(tiny)
    assert res["correct"] is False, res["checks"]
