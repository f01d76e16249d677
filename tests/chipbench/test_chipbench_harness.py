"""The harness as data: rehearsals of every cell on the CPU at tiny size,
the result line's contract, the refusal to measure without a chip, and a
cell, config and metric added by files and entries alone."""
import json
import math
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench_tiny import edit_json, tiny_tree  # noqa: E402

from chipbench import run as harness  # noqa: E402

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
REHEARSAL = harness.benchmark(REPO)
JOB_CELLS = [w["name"] for w in REHEARSAL["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SEED = 2 ** 31 + 977


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return tiny_tree(tmp_path_factory.mktemp("bench"))


def rehearse(root, cell, trace=False, seconds=0.5):
    return harness.run(root, cell, SEED, seconds, trace, platform=None,
                       compile_cache=False)


def test_benchmark_file_keeps_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for p in BENCH["paths"]:
        assert (REPO / p).is_dir() and not p.startswith("/") and ".." not in p
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in BENCH["configs"]:
        assert (REPO / c["file"]).is_file()
        assert all(NAME.match(k) for k in c["reduced"])
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert (REPO / "chipbench" / "metrics" / f"{m['name']}.py").is_file()
        assert set(m["workloads"]) <= set(CELLS)
    for w in BENCH["workloads"]:
        spec = json.loads((REPO / "chipbench" / "workloads"
                           / f"{w['name']}.json").read_text())
        assert spec["config"] == w["config"] and spec["chips"] == w["chips"]
        assert (REPO / "chipbench" / "jobs" / f"{spec['job']}.py").is_file()
        reported = [m for m in BENCH["end_to_end"]
                    if "workloads" not in m or w["name"] in m["workloads"]]
        assert len(reported) >= 2
        assert any(harness.reports(m, w["name"], BENCH)
                   for m in BENCH["per_layer"])
    # a full check of 24 cells fits the driver's 12 hours
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_held_out_cells_are_apart_from_the_benchmark():
    held = json.loads((REPO / "chipbench" / "held_out.json").read_text())
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        assert not ({e["name"] for e in held[key]}
                    & {e["name"] for e in BENCH[key]})
    # bounds are set only where a cell is proved, in BENCHMARK.json
    assert all("bound" not in m for m in held["end_to_end"])
    assert all(w["held_out"] for w in held["workloads"])
    for m in held["per_layer"]:
        assert set(m["workloads"]) <= {w["name"] for w in held["workloads"]}


@pytest.mark.parametrize("cell", JOB_CELLS)
def test_rehearsal_prints_the_contract(tiny, cell):
    res = rehearse(tiny, cell)
    assert list(res)[-1] == "checks"
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    e2e = [m["name"] for m in REHEARSAL["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    assert sorted(res["metrics"]) == sorted(e2e)
    for m in res["metrics"].values():
        assert math.isfinite(m["value"]) and m["value"] >= 0
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert all(set(c) == {"value", "limit"} for c in res["checks"].values())


def test_traced_rehearsal_reads_the_span_metrics(tiny):
    res = rehearse(tiny, "calo_photons.serve", trace=True, seconds=1.5)
    assert res["correct"] is True
    assert {"serve.queue_wait_p95_s", "serve.rows_per_batch",
            "serve.front_end_p95_s"} <= set(res["metrics"])
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_a_new_config_cell_and_metric_run_by_name(tmp_path):
    root = tiny_tree(tmp_path)
    here = root / "chipbench"
    shutil.copy(here / "configs" / "calo_pions.json",
                here / "configs" / "calo_other.json")
    edit_json(here / "configs" / "calo_other.json",
              lambda c: c.update(name="calo_other", p=9))
    shutil.copy(here / "workloads" / "calo_pions.generate.json",
                here / "workloads" / "calo_other.generate.json")
    edit_json(here / "workloads" / "calo_other.generate.json",
              lambda w: w.update(config="calo_other"))
    (here / "metrics" / "gen.calls.py").write_text(
        "def read(ctx, facts, trace):\n    return float(facts['calls'])\n")

    def add(bench):
        bench["configs"].append({"name": "calo_other", "source": "x",
                                 "file": "chipbench/configs/calo_other.json",
                                 "reduced": [], "why": "x"})
        bench["workloads"].append({"name": "calo_other.generate",
                                   "config": "calo_other",
                                   "traffic": "generate", "chips": 1,
                                   "why": "x"})
        for m in bench["end_to_end"]:
            if m["name"] == "gen_rows_per_s":
                m["workloads"].append("calo_other.generate")
        bench["per_layer"].append({"name": "gen.calls", "unit": "calls",
                                   "better": "higher",
                                   "source": "host_clock", "layer": "x",
                                   "moves": "gen_rows_per_s",
                                   "workloads": ["calo_other.generate"]})
    edit_json(root / "BENCHMARK.json", add)
    res = harness.run(root, "calo_other.generate", 5, 0.3, False,
                      platform=None, compile_cache=False)
    assert res["correct"] and set(res["metrics"]) == {"setup_s",
                                                      "gen_rows_per_s"}
    res = harness.run(root, "calo_other.generate", 5, 0.3, True,
                      platform=None, compile_cache=False)
    assert res["metrics"]["gen.calls"]["value"] >= 1


def _cli(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chipbench/run.py", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_to_measure_on_the_cpu():
    out = _cli(REPO, "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
               "--trace", "0")
    assert out.returncode != 0 and out.stdout == ""
    assert "no tpu" in out.stderr.lower()


def test_refuses_without_the_program(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in BENCH["paths"]:
        shutil.copytree(REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _cli(tmp_path, "--workload", CELLS[0], "--seed", "1", "--seconds",
               "1", "--trace", "0")
    assert out.returncode != 0 and out.stdout == ""
