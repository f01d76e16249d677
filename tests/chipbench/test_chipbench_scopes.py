"""Name scopes and program spans in the trace reduction: each op's scope
read from the live programs' optimized HLO (``chipbench.scopes``), scope
seconds, the readers of ``tree_predict.roofline`` and
``gen.host_s_per_call``, a CPU capture of the program's ``sample.*``
spans, and two traces recorded on one TPU v5e chip:
``recorded/small.xplane.pb`` (whose readings stay as they were) and
``recorded/scoped.xplane.pb`` with ``recorded/scoped.hlo.txt``, the
optimized HLO of the program it ran (three calls of a jitted
``scoped_step``, a four-step ``lax.scan`` of a 256 x 256 matmul and tanh
under ``jax.named_scope("tree_predict")`` and an unscoped sum after it,
10 ms apart, inside the ``chipbench.window`` annotation; recorded by
``jax.profiler.start_trace`` around the calls, the HLO read from
``Client.live_executables()`` in the same process)."""
import importlib.util
import json
import os
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))
sys.path.insert(0, str(HERE))

from chipbench import counts, data, scopes  # noqa: E402
from chipbench import trace as tr  # noqa: E402
from chipbench.run import ROOT, benchmark, load_file_module  # noqa: E402
from chipbench_tiny import TINY_CUTS  # noqa: E402

SMALL = HERE / "recorded" / "small.xplane.pb"
SCOPED = HERE / "recorded" / "scoped.xplane.pb"
SCOPED_HLO = HERE / "recorded" / "scoped.hlo.txt"
MS = 1_000_000
KIND = "TPU v5 lite"
PHASES = ("sample.prepare", "sample.dispatch", "sample.wait",
          "sample.fetch", "sample.finish")
SOLVE = "jit__solve_all_classes"


def reader(name):
    return load_file_module(ROOT / "chipbench" / "metrics" / f"{name}.py",
                            "chipbench_metric_" + name)


def hlo_text(module, scope_of):
    """HLO text of a module whose instructions carry the given op_names
    ("" for an instruction without metadata)."""
    lines = [f"HloModule {module}, is_scheduled=true", "",
             "ENTRY %main.1 (p: f32[8]) -> f32[8] {"]
    for i, (op, path) in enumerate(scope_of.items()):
        meta = (f', metadata={{op_name="{path}" stack_frame_id=1}}'
                if path else "")
        root = "ROOT " if i == len(scope_of) - 1 else ""
        lines.append(f"  {root}%{op} = f32[8]{{0}} add(f32[8]{{0}} %p, "
                     f"f32[8]{{0}} %p){meta}")
    return "\n".join(lines + ["}"])


class FakeClient:
    """Stands in for a backend client: its live programs' HLO modules."""

    def __init__(self, programs):
        self.exes = [SimpleNamespace(hlo_modules=lambda m=m, t=t: [
            SimpleNamespace(name=m, to_string=lambda: t)])
            for m, t in ((m, hlo_text(m, s)) for m, s in programs)]

    def live_executables(self):
        return self.exes


def ctx_on(kind=KIND, chips=1, programs=()):
    client = FakeClient(programs)
    dev = SimpleNamespace(device_kind=kind, client=client)
    return SimpleNamespace(devices=[dev] * chips, log=lambda **kv: None)


FACTS = {"rows_computed": 30, "calls": 3, "calls_traced": 2, "steps": 4,
         "trees": 5, "depth": 3, "p": 7, "classes": 2, "chips": 1,
         "module": "_solve_all_classes"}

# the solve program's ops and scopes, and another program that reuses one
# of its op names unscoped
SOLVE_SCOPES = {
    "while": "jit(f)/tree_predict/while",
    "fusion.1": "jit(f)/tree_predict/while/body/mul",
    "fusion.2": "jit(f)/tree_predict/while/body/add",
    "gather": "jit(f)/vmap(tree_predict)/gather",
    "add": "jit(f)/tree_predict_other/add",
    "mul": "jit(f)/sample.unscale/mul",
}
PROGRAMS = ((SOLVE, SOLVE_SCOPES), ("jit_other", {"fusion.1": ""}))


def test_scope_components_see_through_transform_wrappers():
    assert scopes.scope_components("jit(f)/vmap(sample.noise)/add") == {
        "jit(f)", "f", "vmap(sample.noise)", "sample.noise", "add"}
    got = scopes.scope_components(
        "a/vmap(jit(g))/tree_predict/x;tree_predict/y")
    assert {"tree_predict", "g", "x", "y"} <= got
    assert "tree_predict_other" not in scopes.scope_components(
        "a/tree_predict/b")
    assert scopes.scope_components("") == frozenset()


def test_hlo_scopes_read_each_instructions_op_name():
    text = "\n".join([
        "HloModule jit_f, is_scheduled=true", "",
        "%fused_computation (param_0.2: f32[8,8]) -> f32[] {",
        '  %tanh.0 = f32[8,8]{1,0} tanh(%param_0.2), '
        'metadata={op_name="jit(f)/tree_predict/tanh" stack_frame_id=4}',
        "}", "",
        "ENTRY %main.5 (x.1: f32[8,8]) -> f32[] {",
        "  %x.1 = f32[8,8]{1,0} parameter(0)",
        '  ROOT %fusion = f32[] fusion(%x.1), kind=kLoop, '
        'calls=%fused_computation, metadata={op_type="tanh" '
        'op_name="jit(f)/tree_predict/tanh" source_file="a.py"}',
        "}"])
    assert scopes.hlo_scopes(text) == {
        "tanh.0": "jit(f)/tree_predict/tanh", "x.1": "",
        "fusion": "jit(f)/tree_predict/tanh"}


def test_module_name_drops_the_program_id():
    assert scopes.module_name("jit_scoped_step(16541253922142182908)") == (
        "jit_scoped_step")
    assert scopes.module_name("jit_f") == "jit_f"


@pytest.fixture
def scoped_hand_trace():
    # chip 0, in the solve program [0,25] ms: a scoped while [0,10] holding
    # scoped body ops [2,4] and [5,9]; a vmap-wrapped scoped op [12,13]; a
    # look-alike scope [20,22]; an unscoped op [14,15]. In another program
    # [26,30]: its own fusion.1 [27,28], unscoped there. chip 1: the solve
    # program's fusion.1 [0,2]. Window [1,30].
    d0 = tr.Device("/device:TPU:0", ops=[
        ("while", 0, 10 * MS), ("fusion.1", 2 * MS, 4 * MS),
        ("fusion.2", 5 * MS, 9 * MS), ("gather", 12 * MS, 13 * MS),
        ("mul", 14 * MS, 15 * MS), ("add", 20 * MS, 22 * MS),
        ("fusion.1", 27 * MS, 28 * MS)],
        modules=[(SOLVE + "(1)", 0, 25 * MS), ("jit_other(2)", 26 * MS,
                                               30 * MS)])
    d1 = tr.Device("/device:TPU:1", ops=[("fusion.1", 0, 2 * MS)],
                   modules=[(SOLVE + "(1)", 0, 3 * MS)])
    return tr.Trace([d0, d1], [], (1 * MS, 30 * MS))


def test_scope_seconds_are_a_union_clipped_to_the_window(scoped_hand_trace):
    progs = scopes.live_programs(ctx_on(programs=PROGRAMS).devices)
    # chip 0: [1,10] (the while and its body once) + [12,13] = 10 ms, and
    # not the other program's fusion.1; chip 1: [1,2] = 1 ms; mean 5.5 ms
    got = scopes.scope_s(scoped_hand_trace, "tree_predict", progs)
    assert got == pytest.approx(5.5e-3)
    assert scopes.scope_s(scoped_hand_trace, "sample.unscale",
                          progs) == pytest.approx(5e-4)
    assert scopes.scope_s(scoped_hand_trace, "absent", progs) == 0.0
    assert scopes.scope_s(tr.Trace([], [], (0, MS)), "tree_predict",
                          progs) == 0.0


def test_ops_are_looked_up_in_the_program_that_ran_them(scoped_hand_trace):
    progs = scopes.live_programs(ctx_on(programs=PROGRAMS).devices)
    chip0, chip1 = scopes.op_scopes(scoped_hand_trace, progs)
    assert [p for p, _, _ in chip0] == [
        SOLVE_SCOPES[n] for n in ("while", "fusion.1", "fusion.2", "gather",
                                  "mul", "add")] + [""]
    assert chip1 == [(SOLVE_SCOPES["fusion.1"], 0, 2 * MS)]
    # no live program of the name, or two that disagree: no scope
    assert all(p is None for ops in scopes.op_scopes(scoped_hand_trace, {})
               for p, _, _ in ops)
    twins = {SOLVE: [SOLVE_SCOPES, dict(SOLVE_SCOPES, gather="")],
             "jit_other": [{"fusion.1": ""}]}
    paths = [p for p, _, _ in scopes.op_scopes(scoped_hand_trace, twins)[0]]
    assert paths[3] is None and paths[0] == SOLVE_SCOPES["while"]


def test_live_programs_hold_a_jitted_functions_scopes():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def scoped_probe(x):
        with jax.named_scope("tree_predict"):
            y = jnp.tanh(x @ x)
        return y.sum()

    scoped_probe(jnp.ones((8, 8))).block_until_ready()
    progs = scopes.live_programs(jax.devices())
    maps = progs["jit_scoped_probe"]
    assert any("tree_predict" in scopes.scope_components(p)
               for m in maps for p in m.values())


def test_small_trace_keeps_every_reading():
    t = tr.load(str(SMALL))
    assert t.window == (43139934, 76581737)
    assert t.busy_s == 9.978e-06
    assert t.window_s == 0.033441803
    assert t.module_s("small_step") == 1.5e-05
    assert t.module_count("small_step") == 3
    assert t.collective_s == 0.0
    assert t.top_ops() == [["fusion", 8.769e-06], ["fusion.12", 3.463e-06],
                           ["copy-done", 2.0390000000000003e-06],
                           ["copy.11", 5.380000000000001e-07],
                           ["copy-start", 1.5000000000000002e-08]]
    assert t.idle_gaps() == [["$time sleep", 0.012152919000000002],
                             ["$time sleep", 0.010850307],
                             ["$time sleep", 0.010428593],
                             ["$time sleep", 2e-09], ["$time sleep", 1e-09],
                             ["$time sleep", 1e-09], ["$time sleep", 1e-09],
                             ["$time sleep", 1e-09]]


def raw_scopes(path):
    """Per device plane, the (tf_op scope, start ns, end ns) of each
    ``XLA Ops`` event, read from the raw XSpace proto: the profiler's own
    record of each op's scope, to hold the HLO reading against. The proto
    module loads by file path from the installed tensorflow tree, without
    importing tensorflow."""
    spec = importlib.util.find_spec("tensorflow")
    if spec is None:
        pytest.skip("no xplane_pb2.py installed")
    spec = importlib.util.spec_from_file_location(
        "test_xplane_pb2", os.path.join(spec.submodule_search_locations[0],
                                        "tsl", "profiler", "protobuf",
                                        "xplane_pb2.py"))
    pb2 = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pb2)
    space = pb2.XSpace()
    space.ParseFromString(Path(path).read_bytes())
    out = {}
    for plane in space.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        names = {k: m.name for k, m in plane.stat_metadata.items()}
        ops = out.setdefault(plane.name, [])
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                md = plane.event_metadata[ev.metadata_id]
                tf_op = next((st.str_value for st in md.stats
                              if names.get(st.metadata_id) == "tf_op"), "")
                s = line.timestamp_ns + ev.offset_ps // 1000
                ops.append((tf_op.rpartition(":")[0] if ":" in tf_op
                            else tf_op, s, s + ev.duration_ps // 1000))
    return out


def scoped_recording():
    t = tr.load(str(SCOPED))
    progs = {"jit_scoped_step": [scopes.hlo_scopes(SCOPED_HLO.read_text())]}
    return t, progs


def test_scoped_recording_matches_the_profilers_scopes_op_for_op():
    t, progs = scoped_recording()
    raw = raw_scopes(SCOPED)
    assert raw and set(raw) == {d.name for d in t.devices}
    for dev, ops in zip(t.devices, scopes.op_scopes(t, progs)):
        # the raw proto's intervals are ProfileData's, op for op
        assert [(a, b) for _, a, b in raw[dev.name]] == [
            (a, b) for _, a, b in dev.ops]
        whiles = [(p, a, b) for (n, a, b), (p, _, _) in zip(dev.ops, ops)
                  if n.startswith("while")]
        seen = {"same": 0, "container": 0, "inherited": 0}
        for (name, a, b), (hlo, _, _), (prof, _, _) in zip(
                dev.ops, ops, raw[dev.name]):
            if hlo == prof:
                seen["same"] += 1
            elif name.startswith("while"):
                # the profiler stamps no scope on a control-flow container;
                # its HLO metadata has one
                assert prof == "" and "tree_predict" in hlo.split("/")
                seen["container"] += 1
            else:
                # an instruction without metadata: the profiler names it
                # after the container it runs in
                assert hlo == "" and any(
                    p == prof and x <= a and b <= y for p, x, y in whiles)
                seen["inherited"] += 1
        assert all(seen.values()), seen


def test_scoped_recording_reads_the_traversal_scope():
    t, progs = scoped_recording()
    ops = scopes.op_scopes(t, progs)[0]
    lo, hi = t.window
    scoped = [(p, a, b) for p, a, b in ops if "tree_predict" in p.split("/")]
    assert scoped and len(scoped) < len(ops)
    # by hand: merge the scoped intervals inside the window in time order
    total, end = 0, None
    for _, a, b in sorted(scoped, key=lambda x: x[1]):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if end is not None and a < end:
            total += max(0, b - end)
            end = max(end, b)
        else:
            total += b - a
            end = b
    got = scopes.scope_s(t, "tree_predict", progs)
    assert got == pytest.approx(total * 1e-9)
    assert 0 < got < t.busy_s <= t.window_s
    assert scopes.scope_s(t, "tree_predict", {}) == 0.0


def test_scoped_recording_counts_a_container_and_its_body_once():
    t, progs = scoped_recording()
    dev = t.devices[0]
    ops = scopes.op_scopes(t, progs)[0]
    whiles = [(a, b) for n, a, b in dev.ops if n.startswith("while")]
    body = [(a, b) for (n, a, b), (p, _, _) in zip(dev.ops, ops)
            if "tree_predict" in p.split("/") and not n.startswith("while")]
    assert whiles and body
    assert all(any(x <= a and b <= y for x, y in whiles) for a, b in body)
    # the scan's ``while`` and its body both carry the scope: each instant
    # counts once, so the reading is the containers' own time
    while_s = sum(b - a for a, b in tr.clip(whiles, *t.window)) * 1e-9
    body_s = sum(b - a for a, b in tr.clip(body, *t.window)) * 1e-9
    got = scopes.scope_s(t, "tree_predict", progs)
    assert got == pytest.approx(while_s)
    assert 0 < body_s < got < while_s + body_s


def test_tree_predict_roofline_reads_the_scope(scoped_hand_trace):
    read = reader("tree_predict.roofline").read
    solve = reader("gen.solve_roofline").read
    ctx = ctx_on(chips=2, programs=PROGRAMS)
    peak = counts.peaks(KIND)
    rows = FACTS["rows_computed"] // FACTS["calls"]
    ops = 2 * counts.solve_ops(rows, 4, 5, 3, 7)
    nbytes = 2 * counts.solve_bytes(rows, 4, 2, 5, 3, 7, 7)
    least, _ = counts.least_time(ops, nbytes, peak)
    got = read(ctx, FACTS, scoped_hand_trace)
    assert got == pytest.approx(100.0 * least / 5.5e-3)
    assert got >= solve(ctx, FACTS, scoped_hand_trace)
    # a program without the scope (the parent's), no chip plane, no trace
    bare = ctx_on(chips=2, programs=((SOLVE, {n: "jit(f)/" + n for n in
                                                SOLVE_SCOPES}),))
    assert read(bare, FACTS, scoped_hand_trace) is None
    empty = tr.Trace([], [], (0, 30 * MS))
    assert read(ctx_on(programs=PROGRAMS), FACTS, empty) is None
    assert read(ctx_on(programs=PROGRAMS), FACTS, None) is None


def _host_trace(extra=()):
    host = [("chipbench.window", 0, 100 * MS)]
    for c in range(2):       # two calls of 40 ms, phases in order
        t0 = c * 50 * MS
        for name, a, b in zip(PHASES, (0, 2, 3, 33, 35), (2, 3, 33, 35, 40)):
            host.append((name, t0 + a * MS, t0 + b * MS))
    return tr.Trace([], host + list(extra), (0, 100 * MS))


def test_host_seconds_per_call_sum_the_host_phases():
    read = reader("gen.host_s_per_call").read
    # per call: prepare 2 + dispatch 1 + fetch 2 + finish 5 ms; the device
    # wait (30 ms) is not host work
    assert read(ctx_on(), FACTS, _host_trace()) == pytest.approx(10e-3)
    # events outside the window do not count
    late = _host_trace([("sample.fetch", 200 * MS, 300 * MS)])
    assert read(ctx_on(), FACTS, late) == pytest.approx(10e-3)
    none = tr.Trace([], [("chipbench.window", 0, 100 * MS),
                         ("sample.fetch", 200 * MS, 300 * MS)], (0, 100 * MS))
    assert read(ctx_on(), FACTS, none) is None
    assert read(ctx_on(), FACTS, None) is None


def _tiny_artifacts():
    cfg = json.loads((ROOT / "chipbench" / "configs"
                      / "calo_pions.json").read_text())
    cfg.update(TINY_CUTS, classes_held=2)
    gen = load_file_module(ROOT / "chipbench" / "jobs" / "generate.py",
                           "chipbench_job_generate")
    return gen.build_artifacts(cfg, data.sub_seeds(2 ** 31 + 5, 1)[0])


def test_cpu_capture_holds_each_calls_sample_spans(tmp_path):
    import jax
    from repro.tabgen import sample

    art = _tiny_artifacts()
    sample(art, 16, seed=1)                    # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(tr.WINDOW):
            for seed in (2, 3, 4):
                sample(art, 16, seed=seed)
    finally:
        jax.profiler.stop_trace()
    t = tr.load(tr.find_xplane(str(tmp_path)))
    assert t.devices == []
    lo, hi = t.window
    events = sorted((a, b, n) for n, a, b in t.host if n in PHASES)
    assert [n for _, _, n in events] == list(PHASES) * 3
    assert all(lo <= a <= b <= hi for a, b, _ in events)
    assert all(b <= a2 for (_, b, _), (a2, _, _) in zip(events, events[1:]))
    facts = dict(FACTS, calls_traced=3)
    assert reader("gen.host_s_per_call").read(ctx_on("cpu"), facts, t) > 0
    assert reader("tree_predict.roofline").read(
        SimpleNamespace(devices=jax.devices(), log=lambda **kv: None),
        facts, t) is None


def test_new_metrics_are_entries_of_the_benchmark():
    bench = benchmark(ROOT)
    new = {m["name"]: m for m in bench["per_layer"]
           if m["name"] in ("tree_predict.roofline", "gen.host_s_per_call")}
    assert set(new) == {"tree_predict.roofline", "gen.host_s_per_call"}
    for m in new.values():
        assert m["workloads"] == ["calo_pions.generate"]
        assert m["moves"] == "gen_rows_per_s"
    assert new["tree_predict.roofline"]["source"] == "device_trace"
    assert new["gen.host_s_per_call"]["source"] == "program_span"


def test_traced_generate_rehearsal_reports_host_seconds(tmp_path):
    from chipbench import run as harness
    from chipbench_tiny import tiny_tree
    root = tiny_tree(tmp_path)
    res = harness.run(root, "calo_pions.generate", 2 ** 31 + 977, 1.0, True,
                      platform=None, compile_cache=False)
    assert res["correct"] is True
    assert res["metrics"]["gen.host_s_per_call"]["value"] > 0
    # a capture with no chip plane reads no scopes, so no roofline share
    assert "tree_predict.roofline" not in res["metrics"]
