"""A copy of the benchmark at CPU size, for rehearsals in the tests.

``tiny_tree(dst)`` copies ``BENCHMARK.json`` and ``chipbench/`` (whose
``held_out.json`` adds the fit and serving cells that the chip has not
yet proved) into ``dst`` beside a link to the program's ``src/``, and
shrinks every configuration and cell through the same files a later
change would edit: widths, scale and traffic all cut until a run takes
seconds on the CPU. A config without ``cuts`` keeps none, and a job that
``JOB_CUTS`` does not know runs at the tiny scale uncut. Nothing in the
harness knows it is tiny.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

TINY_CUTS = {
    "dataset": "tiny", "p": 14, "showers_per_class": 8, "n_t": 4,
    "duplicate_k": 2, "n_trees": 3, "max_depth": 3, "n_bins": 8,
}
JOB_CUTS = {
    "fit": {"n_t": 1, "n_trees": 2},
    "generate": {"classes_held": 2},
    "serve": {"classes_held": 2},
}
TRAFFIC = {
    "calo_photons.fit": {"check_ensembles": 2},
    "calo_pions.generate": {"rows_per_call": 16, "check_rows": 24},
    "calo_photons.serve": {"rate": 20.0, "n_min": 2, "n_max": 8,
                           "buckets": [8, 32], "check_requests": 4,
                           "workers": 8, "trace_seconds": 1.0},
}


def edit_json(path: Path, fn) -> None:
    obj = json.loads(path.read_text())
    fn(obj)
    path.write_text(json.dumps(obj, indent=1))


def tiny_tree(dst: Path, source: Path = REPO) -> Path:
    """The tiny copy in ``dst`` of the benchmark in ``source`` (this
    repository's by default), beside a link to this repository's program."""
    dst, source = Path(dst), Path(source)
    dst.mkdir(parents=True, exist_ok=True)
    (dst / "src").symlink_to(REPO / "src")
    shutil.copy(source / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(source / "chipbench", dst / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for cfg in (dst / "chipbench" / "configs").glob("*.json"):
        def shrink(c):
            c.update(TINY_CUTS)
            if "cuts" in c:     # a job without a tiny cut runs uncut
                c["cuts"] = {job: dict(JOB_CUTS.get(job, {}))
                             for job in c["cuts"]}
        edit_json(cfg, shrink)
    for cell, traffic in TRAFFIC.items():
        edit_json(dst / "chipbench" / "workloads" / f"{cell}.json",
                  lambda w: w["traffic"].update(traffic))
    return dst
