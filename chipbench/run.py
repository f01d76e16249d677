"""Run one cell of the chip benchmark once and print its result line.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data, found by name:

* ``BENCHMARK.json`` (root of the checkout): the cell's config and chips,
  and the end-to-end and per-layer metrics each cell reports; cells not
  yet proved on the chip have the same entries in
  ``chipbench/held_out.json``;
* ``chipbench/workloads/<cell>.json``: the job that drives the window and
  its traffic, and the limit of each number the correctness check compares;
* ``chipbench/configs/<config>.json``: the deployment (published values,
  the cut each job runs at, and the plain reference that checks it, in
  ``chipbench/references/<reference>.py``);
* ``chipbench/jobs/<job>.py``: ``run(ctx)`` sets up, measures, and checks;
* ``chipbench/metrics/<metric>.py``: ``read(ctx, facts, trace)``, one
  per-layer metric, or None where the run has nothing to read.

A run refuses to measure anywhere but on as many TPU chips as the cell
asks for, keeps JAX's compile cache at ``.jax_cache`` in the checkout, and
prints, last on stdout, one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` with ``--trace 1``)
and, last, ``checks``: each number the correctness check compared, with
its limit. The same numbers are the last lines on stderr.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


class Refused(RuntimeError):
    """The run cannot measure here (no chip, too few chips, bad cell)."""


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def benchmark(root: Path) -> dict:
    """``BENCHMARK.json`` with the entries of ``chipbench/held_out.json``
    (cells not yet proved on the chip, and their configs and metrics)
    added where BENCHMARK.json has no entry of that name."""
    bench = load_json(root / "BENCHMARK.json")
    held = root / "chipbench" / "held_out.json"
    if held.is_file():
        extra = load_json(held)
        for key in ("configs", "workloads", "end_to_end", "per_layer"):
            have = {e["name"] for e in bench[key]}
            bench[key] = bench[key] + [e for e in extra.get(key, [])
                                       if e["name"] not in have]
    return bench


def cell_spec(root: Path, name: str):
    """(the benchmark, its workload entry, the workload file, the config
    as run by the workload's job)."""
    bench = benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise Refused(f"no workload {name!r} in BENCHMARK.json or "
                      "chipbench/held_out.json")
    here = root / "chipbench"
    workload = load_json(here / "workloads" / f"{name}.json")
    config = load_json(here / "configs" / f"{entry['config']}.json")
    run_cfg = {k: v for k, v in config.items() if k != "cuts"}
    run_cfg.update(config.get("cuts", {}).get(workload["job"], {}))
    return bench, entry, workload, run_cfg


def reports(metric: dict, cell: str, bench: dict) -> bool:
    """Whether ``cell`` reports ``metric``: listed in its ``workloads``, or,
    without the key, wherever the end-to-end metric it moves is reported."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    moves = next((m for m in bench["end_to_end"]
                  if m["name"] == metric.get("moves")), None)
    return moves is not None and ("workloads" not in moves
                                  or cell in moves["workloads"])


def load_file_module(path: Path, name: str):
    """The module in ``path``, loaded once per process and name."""
    key = f"{name}@{path}"
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[key] = mod
    return sys.modules[key]


class Context:
    """What a job sees: its config and traffic, the run's arguments, and
    the harness's clock, profiler and memory readings."""

    def __init__(self, root: Path, name: str, seed: int, seconds: float,
                 trace: bool, devices, config: dict, workload: dict,
                 control: bool = False):
        self.root, self.name, self.seed = root, name, int(seed)
        self.seconds, self.trace = float(seconds), bool(trace)
        self.devices, self.config, self.workload = devices, config, workload
        self.traffic = workload.get("traffic", {})
        self.limits = workload.get("limits", {})
        self.control = control
        self.reference = load_file_module(
            root / "chipbench" / "references" / f"{config['reference']}.py",
            "chipbench_reference_" + config["reference"])
        self.setup_s = None
        self.window_s = None
        self.trace_result = None
        self._mark = None
        self._stopper = None
        self._trace_dir = root / ".chipbench_trace" / name

    def log(self, **kv) -> None:
        print(json.dumps(kv, default=str), file=sys.stderr, flush=True)

    def setup_done(self) -> float:
        """Marks the end of set-up; returns the seconds since the process
        started (imports, backend start, data, weights, compile, warm-up)."""
        self.setup_s = time.perf_counter() - T_START
        return self.setup_s

    @contextlib.contextmanager
    def window(self):
        """The measured window. With ``--trace 1`` the profiler records it
        (or its first part, where the job calls :meth:`end_trace` early),
        inside the ``chipbench.window`` annotation."""
        import jax
        if self.trace:
            shutil.rmtree(self._trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(self._trace_dir))
            self._mark = jax.profiler.TraceAnnotation("chipbench.window")
            self._mark.__enter__()
        compiles: dict = {}

        def count(event, *args, **kwargs):
            if self.window_s is None and "compile" in event:
                compiles[event] = compiles.get(event, 0) + 1
        jax.monitoring.register_event_duration_secs_listener(count)
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.window_s = time.perf_counter() - t0
            self.log(phase="window_compiles", events=compiles)
            self.end_trace()
            if self._stopper is not None:
                self._stopper.join()
                self._stopper = None
                from chipbench import trace as tr
                path = tr.find_xplane(str(self._trace_dir))
                self.trace_result = tr.load(path) if path else None
                shutil.rmtree(self._trace_dir, ignore_errors=True)

    def end_trace(self) -> None:
        """Close the traced part of the window (on the window's thread)
        and stop the profiler on a thread of its own, so that a job that
        ends the trace early keeps its schedule; the trace is read after
        the window."""
        if self._mark is None:
            return
        import jax
        self._mark.__exit__(None, None, None)
        self._mark = None
        self._stopper = threading.Thread(target=jax.profiler.stop_trace,
                                         name="chipbench-trace-stop")
        self._stopper.start()

    def memory_peak_bytes(self) -> int:
        """Peak bytes on the fullest chip of the cell: the runtime's peak of
        buffers in use plus its peak reserved for programs' XLA temp space,
        which the TPU runtime keeps apart from ``peak_bytes_in_use``."""
        peaks = [sum((d.memory_stats() or {}).get(k, 0) for k in
                     ("peak_bytes_in_use", "peak_bytes_reserved"))
                 for d in self.devices]
        return int(max(peaks))


def enable_compile_cache(root: Path) -> None:
    """JAX's persistent cache at a fixed path inside the checkout, every
    program in it, so that only a checkout's first run compiles."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def devices_for(chips: int, platform):
    """The chips to run on; refuses when JAX has fewer ``platform``
    devices than the cell asks for (``platform=None`` takes any)."""
    import jax
    devs = jax.devices()
    if platform is not None and devs[0].platform != platform:
        raise Refused(f"JAX found no {platform} (platform "
                      f"{devs[0].platform!r}); nothing was measured")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def run(root: Path, name: str, seed: int, seconds: float, trace: bool,
        platform="tpu", control: bool = False,
        compile_cache: bool = True) -> dict:
    """One run of cell ``name``; returns the result object.

    ``platform=None`` skips the look for a chip and ``compile_cache=False``
    leaves JAX's cache settings alone: both for rehearsals on the CPU.
    ``control`` runs the cell's control in the program's place."""
    bench, entry, workload, run_cfg = cell_spec(root, name)
    for p in (root, root / "src"):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    import jax
    devices = devices_for(entry["chips"], platform)
    if compile_cache:
        enable_compile_cache(root)
    ctx = Context(root, name, seed, seconds, trace, devices, run_cfg,
                  workload, control=control)
    job = load_file_module(root / "chipbench" / "jobs" / f"{workload['job']}.py",
                           "chipbench_job_" + workload["job"])
    out = job.run(ctx)
    facts = out.get("facts", {})

    metrics = {}
    if not trace:
        values = dict(out["e2e"], setup_s=ctx.setup_s)
        for m in bench["end_to_end"]:
            if "workloads" in m and name not in m["workloads"]:
                continue
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        for m in bench["per_layer"]:
            if not reports(m, name, bench):
                continue
            reader = load_file_module(root / "chipbench" / "metrics"
                                      / f"{m['name']}.py",
                                      "chipbench_metric_" + m["name"])
            value = reader.read(ctx, facts, ctx.trace_result)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": None, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if trace and ctx.trace_result is not None:
        t = ctx.trace_result
        device.update(busy_s=t.busy_s, window_s=t.window_s)
        result["breakdown"] = {"device_ops": t.top_ops(),
                               "idle_gaps": t.idle_gaps()}
    checks = {k: {"value": v, "limit": ctx.limits[k]}
              for k, v in out["checks"].items()}
    result["correct"] = bool(checks) and all(
        c["value"] <= c["limit"] for c in checks.values())
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(ROOT, args.workload, args.seed, args.seconds,
                     bool(args.trace))
    except Refused as exc:
        print(f"chipbench: {exc}", file=sys.stderr, flush=True)
        return 3
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
