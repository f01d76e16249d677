"""The benchmark harness fails loudly: a failed child fails its section."""
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from benchmarks.common import run_measured  # noqa: E402


def test_run_measured_returns_the_child_result():
    res = run_measured("result = {'rows': 3}")
    assert res["rows"] == 3 and res["wall_s"] >= 0
    assert res["peak_rss_bytes"] > 0


@pytest.mark.parametrize("snippet", ["raise SystemExit(3)",
                                     "import os; os._exit(0)"])
def test_run_measured_raises_when_the_child_fails(snippet):
    """A non-zero exit, or an exit with no result line, is an error."""
    with pytest.raises(RuntimeError):
        run_measured(snippet)


def test_run_rejects_an_unknown_section():
    proc = subprocess.run([sys.executable, "-m", "benchmarks.run", "--only",
                           "no_such_section"], cwd=REPO, capture_output=True,
                          text=True, timeout=60)
    assert proc.returncode != 0 and "unknown sections" in proc.stderr
