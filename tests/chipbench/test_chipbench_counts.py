"""Op and byte counts against hand counts at small shapes; the peak table."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import counts  # noqa: E402


def test_fit_tree_ops_hand_count():
    # rows 4, p 2, out 3, depth 2, bins 4
    # level 0: hist 4*2*(3+1) = 32, split 6*1*2*4*3 = 144, route 4
    # level 1: hist 32, split 6*2*2*4*3 = 288, route 4
    # leaves: 3 * 4 * 3 = 36
    assert counts.fit_tree_ops(4, 2, 3, 2, 4) == 32 + 144 + 4 + 32 + 288 + 4 + 36


def test_solve_ops_hand_count():
    # rows 2, steps 3, trees 4, depth 2, out 5:
    # per row and step 4 * (2 + 5) + 2 * 5 = 38
    assert counts.solve_ops(2, 3, 4, 2, 5) == 2 * 3 * 38


def test_solve_bytes_hand_count():
    # 1 step, 1 class, 1 tree of depth 1 (1 split, 2 leaves), p = out = 2,
    # 3 rows: forest 1 * (8 + 2 * 2 * 4) = 24, state 3 * 1 * 2 * 2 * 4 = 48
    assert counts.solve_bytes(3, 1, 1, 1, 1, 2, 2) == 24 + 48


@pytest.mark.parametrize("ops,nbytes,bound", [(197e12, 1.0, "ops"),
                                              (1.0, 819e9, "bytes")])
def test_least_time_names_its_bound(ops, nbytes, bound):
    peak = counts.peaks("TPU v5 lite")
    t, which = counts.least_time(ops, nbytes, peak)
    assert which == bound and t == pytest.approx(1.0)


def test_peaks_of_v5e():
    peak = counts.peaks("TPU v5 lite")
    assert peak["flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        counts.peaks("TPU v99")
