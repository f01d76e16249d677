"""Stalled calls of the generate job's window: the rule
(``chipbench.stalls``), the ``gen.stall_share`` reader on hand-made call
times and in a traced rehearsal on the CPU, and its entry in
``BENCHMARK.json``."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))
sys.path.insert(0, str(HERE))

from chipbench import stalls  # noqa: E402
from chipbench.run import ROOT, benchmark, load_file_module  # noqa: E402

CTX = SimpleNamespace(log=lambda **kv: None)


def stall_share(call_s, wall_s=None):
    read = load_file_module(ROOT / "chipbench" / "metrics"
                            / "gen.stall_share.py",
                            "chipbench_metric_gen.stall_share").read
    facts = {"call_s": list(call_s),
             "wall_s": sum(call_s) if wall_s is None else wall_s}
    return read(CTX, facts, None)


def test_a_steady_window_holds_no_stall():
    calls = [0.16, 0.161, 0.159, 0.47, 0.162, 0.158]   # 0.47 < 3 x median
    assert stalls.excess_s(calls) == [0.0] * len(calls)
    assert stall_share(calls) == 0.0


def test_one_stall_counts_only_its_excess_over_the_median_call():
    calls = [0.16] * 9 + [2.16]
    excess = stalls.excess_s(calls)
    assert excess[:9] == [0.0] * 9
    assert excess[9] == pytest.approx(2.0)
    assert stall_share(calls) == pytest.approx(100.0 * 2.0 / 3.6)
    # the rule is more than three times the median call, not three
    assert stalls.excess_s([1.0, 1.0, 3.0]) == [0.0, 0.0, 0.0]
    assert stalls.excess_s([1.0, 1.0, 3.01])[2] == pytest.approx(2.01)


def test_a_uniformly_slower_window_holds_no_stall():
    calls = [0.16, 0.161, 0.159, 0.162, 2.16, 0.158, 0.16]
    slow = [4.0 * c for c in calls]
    assert stalls.excess_s(slow) == pytest.approx(
        [4.0 * e for e in stalls.excess_s(calls)])
    assert sum(1 for e in stalls.excess_s(slow) if e > 0) == 1
    assert stall_share(slow) == pytest.approx(stall_share(calls))
    assert stall_share([4.0 * 0.16] * 20) == 0.0


def test_nothing_to_read_gives_no_value():
    assert stall_share([]) is None
    read = load_file_module(ROOT / "chipbench" / "metrics"
                            / "gen.stall_share.py",
                            "chipbench_metric_gen.stall_share").read
    assert read(CTX, {"wall_s": 1.0}, None) is None


def test_stall_share_is_an_entry_of_the_benchmark():
    bench = benchmark(ROOT)
    m = next(m for m in bench["per_layer"] if m["name"] == "gen.stall_share")
    assert m["workloads"] == ["calo_pions.generate"]
    assert m["moves"] == "gen_rows_per_s"
    assert (m["unit"], m["better"], m["source"]) == ("%", "lower",
                                                     "host_clock")


def test_traced_generate_rehearsal_reads_the_stall_share(tmp_path):
    from chipbench import run as harness
    from chipbench_tiny import tiny_tree
    root = tiny_tree(tmp_path)
    res = harness.run(root, "calo_pions.generate", 2 ** 31 + 979, 1.0, True,
                      platform=None, compile_cache=False)
    assert res["correct"] is True
    share = res["metrics"]["gen.stall_share"]
    assert share["unit"] == "%" and 0.0 <= share["value"] < 100.0


def _timed_sample(monkeypatch, extra_s):
    """Wrap the program's ``sample`` so that call ``i`` first sleeps
    ``extra_s(i)`` seconds; returns the list of each call's seconds."""
    import time

    import repro.tabgen as tabgen
    real, took = tabgen.sample, []

    def slow(*a, **k):
        t0 = time.perf_counter()
        time.sleep(extra_s(len(took)))
        out = real(*a, **k)
        took.append(time.perf_counter() - t0)
        return out
    monkeypatch.setattr(tabgen, "sample", slow)
    return took


@pytest.fixture
def tiny_with_wall(tmp_path):
    """A tiny tree whose traced runs also report the window's wall time."""
    from chipbench_tiny import edit_json, tiny_tree
    root = tiny_tree(tmp_path)
    (root / "chipbench" / "metrics" / "gen.wall_s.py").write_text(
        "def read(ctx, facts, trace):\n    return facts['wall_s']\n")
    edit_json(root / "BENCHMARK.json", lambda b: b["per_layer"].append(
        {"name": "gen.wall_s", "unit": "s", "better": "lower",
         "source": "host_clock", "layer": "x", "moves": "gen_rows_per_s",
         "workloads": ["calo_pions.generate"]}))
    return root


def _run(root, trace):
    from chipbench import run as harness
    return harness.run(root, "calo_pions.generate", 2 ** 31 + 981, 1.2,
                       trace, platform=None, compile_cache=False)


def test_a_stall_is_not_excused_from_the_rate_and_shows_in_the_share(
        tiny_with_wall, monkeypatch):
    # one call of the window stalls for 0.5 s; the warm-up is call 0
    took = _timed_sample(monkeypatch, lambda i: 0.5 if i == 3 else 0.0)
    res = _run(tiny_with_wall, trace=False)
    assert res["correct"] is True
    rows = 16 * res["attempted"]
    wall = rows / res["metrics"]["gen_rows_per_s"]["value"]
    # the rate is over all the window's time, the stall's whole 0.5 s in it
    assert wall >= sum(took[1:]) and max(took) >= 0.5
    took.clear()
    res = _run(tiny_with_wall, trace=True)
    m = res["metrics"]
    med = sorted(took[1:])[len(took[1:]) // 2]
    excess = m["gen.stall_share"]["value"] / 100.0 * m["gen.wall_s"]["value"]
    # the share counts the stall down to the median call, no further
    assert 0.5 - med <= excess <= max(took) - med + 0.2


def test_a_slower_program_lowers_the_rate_and_is_no_stall(
        tiny_with_wall, monkeypatch):
    took = _timed_sample(monkeypatch, lambda i: 0.02)
    res = _run(tiny_with_wall, trace=False)
    rows = 16 * res["attempted"]
    wall = rows / res["metrics"]["gen_rows_per_s"]["value"]
    # every call's 20 ms is in the rate: at most one call per 20 ms
    assert wall >= sum(took[1:]) >= 0.02 * (res["attempted"])
    assert res["metrics"]["gen_rows_per_s"]["value"] <= 16 / 0.02
    # the median moves with every call, so the slowdown holds no stall:
    # counted as one it would be 20 ms for each of the window's calls
    res = _run(tiny_with_wall, trace=True)
    m = res["metrics"]
    excess = m["gen.stall_share"]["value"] / 100.0 * m["gen.wall_s"]["value"]
    assert excess < 0.25 < 0.02 * res["attempted"]
