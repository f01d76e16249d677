"""Shared benchmark helpers: subprocess peak-RSS measurement + CSV output."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time


def run_measured(snippet: str, timeout: int = 900,
                 env_extra: dict = None) -> dict:
    """Run a python snippet in a subprocess; returns its printed JSON plus
    wall time and peak RSS (KiB->bytes). Each config gets a clean process so
    peak memory is per-config (ru_maxrss is monotonic within a process).
    A child that fails raises here, failing its section and the run.

    The calling process must not have touched a device yet: an accelerator
    belongs to one process at a time, so a parent holding it would leave
    the child to fail or hang.

    ``env_extra`` adds/overrides env vars — e.g. ``XLA_FLAGS`` to set a
    virtual device count, which must be in place before jax initialises."""
    wrapper = (
        "import resource, json, time\n"
        "t0 = time.time()\n"
        + snippet + "\n"
        "out = dict(result if isinstance(result, dict) else {})\n"
        "out['wall_s'] = time.time() - t0\n"
        "out['peak_rss_bytes'] = "
        "resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024\n"
        "print('\\n@@RESULT@@' + json.dumps(out))\n"
    )
    env = {"PYTHONPATH": "src", "HOME": os.environ.get("HOME", "."),
           "PATH": "/usr/bin:/bin"}
    if "JAX_PLATFORMS" in os.environ:    # scrubbed env: keep platform pin
        env["JAX_PLATFORMS"] = os.environ["JAX_PLATFORMS"]
    if env_extra:
        env.update(env_extra)
    proc = subprocess.run([sys.executable, "-c", wrapper],
                          capture_output=True, text=True, timeout=timeout,
                          env=env)
    if proc.returncode != 0:
        raise RuntimeError(f"benchmark child exited {proc.returncode}:\n"
                           + proc.stderr[-1500:])
    for line in proc.stdout.splitlines():
        if line.startswith("@@RESULT@@"):
            return json.loads(line[len("@@RESULT@@"):])
    raise RuntimeError("benchmark child printed no result line")


def emit(name: str, us_per_call, derived):
    print(f"{name},{us_per_call},{derived}", flush=True)
