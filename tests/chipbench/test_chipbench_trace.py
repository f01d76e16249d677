"""The trace reduction: hand-built intervals, and a small trace recorded
on one TPU v5e chip (``recorded/small.xplane.pb``: three calls of a jitted
``small_step`` inside the ``chipbench.window`` annotation, 10 ms apart)."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import trace as tr  # noqa: E402

RECORDED = Path(__file__).resolve().parent / "recorded" / "small.xplane.pb"
MS = 1_000_000


def test_op_names_are_the_instruction_names():
    assert tr.op_name("%fusion.12 = f32[8,256]{1,0} fusion(%a), kind=kLoop"
                      ) == "fusion.12"
    assert tr.op_name("jit_small_step(88)") == "jit_small_step(88)"


def test_union_merges_overlaps_and_nesting():
    assert tr.union([(5, 9), (0, 2), (1, 3), (6, 7), (9, 10)]) == [(0, 3),
                                                                  (5, 10)]


def test_clip_to_window():
    assert tr.clip([(0, 4), (6, 12), (20, 30)], 2, 10) == [(2, 4), (6, 10)]


@pytest.fixture
def hand_trace():
    # chip 0: ops [0,4] and [2,6] (nested), a gap, then [10,12];
    # chip 1: one op [0,2]. Window [0,20] ms.
    d0 = tr.Device("/device:TPU:0",
                   ops=[("fusion.1", 0, 4 * MS), ("fusion.2", 2 * MS, 6 * MS),
                        ("all-reduce.3", 10 * MS, 12 * MS)],
                   modules=[("jit_fit_one(1)", 0, 6 * MS),
                            ("jit_other(2)", 10 * MS, 12 * MS)])
    d1 = tr.Device("/device:TPU:1", ops=[("fusion.1", 0, 2 * MS)],
                   modules=[("jit_fit_one(1)", 0, 2 * MS)])
    host = [("PjitFunction(fit_one)", 5 * MS, 9 * MS),
            ("python_loop", 0, 20 * MS)]
    return tr.Trace([d0, d1], host, (0, 20 * MS))


def test_busy_is_the_union_averaged_over_chips(hand_trace):
    # chip 0 busy 6 + 2 = 8 ms, chip 1 busy 2 ms
    assert hand_trace.busy_s == pytest.approx(5e-3)
    assert hand_trace.window_s == pytest.approx(20e-3)


def test_module_and_collective_seconds(hand_trace):
    assert hand_trace.module_s("fit_one") == pytest.approx(4e-3)
    assert hand_trace.module_count("fit_one") == 1
    assert hand_trace.collective_s == pytest.approx(1e-3)


def test_breakdown_names_ops_and_gaps(hand_trace):
    top = dict(hand_trace.top_ops())
    assert top["fusion.1"] == pytest.approx(3e-3)
    gaps = hand_trace.idle_gaps()
    # chip 0 idles [6,10] (host in fit_one's dispatch most of it) and
    # [12,20] (only the loop)
    assert gaps[0] == ["python_loop", pytest.approx(8e-3)]
    assert gaps[1] == ["PjitFunction(fit_one)", pytest.approx(4e-3)]


def test_recorded_trace():
    t = tr.load(str(RECORDED))
    assert len(t.devices) == 1
    assert t.module_count("small_step") == 3
    assert 0 < t.busy_s < t.window_s
    assert 0 < t.module_s("small_step") <= t.window_s
    assert t.collective_s == 0.0
    assert t.top_ops() and all(s > 0 for _, s in t.top_ops())
    assert not any(n.startswith(("%", "while")) for n, _ in t.top_ops())
    gaps = t.idle_gaps()
    assert gaps and sum(s for _, s in gaps) <= t.window_s - t.busy_s + 1e-9


def test_gen_mfu_reads_the_busy_seconds_of_the_trace(hand_trace):
    from types import SimpleNamespace

    from chipbench import counts
    from chipbench.run import ROOT, load_file_module
    reader = load_file_module(ROOT / "chipbench" / "metrics" / "gen_mfu.py",
                              "chipbench_metric_gen_mfu")
    kind = "TPU v5 lite"
    ctx = SimpleNamespace(devices=[SimpleNamespace(device_kind=kind)] * 2)
    facts = {"rows_computed": 30, "calls": 3, "calls_traced": 2,
             "steps": 4, "trees": 5, "depth": 3, "p": 7, "chips": 2,
             "wall_s": 1e9}       # the host's clock plays no part
    ops = 2 * counts.solve_ops(10, 4, 5, 3, 7)
    want = 100.0 * ops / (5e-3 * 2 * counts.peaks(kind)["flops_per_s"])
    assert reader.read(ctx, facts, hand_trace) == pytest.approx(want)
    assert reader.read(ctx, facts, None) is None
