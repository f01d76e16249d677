"""jaxlint: each rule against a known-bad fixture reproducing the historical
bug it encodes, plus the known-good idioms the repo actually uses, the
suppression/baseline machinery, and the CLI exit-code contract."""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.analysis.lint import (Finding, lint_source, load_baseline,
                                 parse_suppressions, split_baselined,
                                 write_baseline)

REPO = Path(__file__).resolve().parents[1]


def findings(src, select=None):
    fs, _ = lint_source(textwrap.dedent(src), "fixture.py", select)
    return fs


def rules_hit(src, select=None):
    return sorted({f.rule for f in findings(src, select)})


# ---------------------------------------------------------------------------
# JX001 — PRNG key reuse (the PR-2 CFM-jitter bug)
# ---------------------------------------------------------------------------

PR2_BUG = """
    import jax

    def sample_bridge(key, x1, sigma):
        # the shipped bug: one key drew both the endpoint noise and the
        # "independent" jitter, so jitter == the same normal draw scaled
        noise = jax.random.normal(key, x1.shape)
        jitter = sigma * jax.random.normal(key, x1.shape)
        return x1 + noise + jitter
"""


def test_jx001_flags_the_pr2_bug():
    fs = findings(PR2_BUG)
    assert [f.rule for f in fs] == ["JX001"]
    assert "split" in fs[0].message


def test_jx001_split_is_clean():
    assert rules_hit("""
        import jax

        def sample_bridge(key, x1, sigma):
            k1, k2 = jax.random.split(key)
            noise = jax.random.normal(k1, x1.shape)
            jitter = sigma * jax.random.normal(k2, x1.shape)
            return x1 + noise + jitter
    """) == []


def test_jx001_flags_loop_reuse():
    fs = findings("""
        import jax

        def draws(key, n):
            out = []
            for i in range(n):
                out.append(jax.random.normal(key, (4,)))
            return out
    """)
    assert [f.rule for f in fs] == ["JX001"]
    assert "loop" in fs[0].message


def test_jx001_fold_in_per_iteration_is_clean():
    assert rules_hit("""
        import jax

        def draws(key, n):
            out = []
            for i in range(n):
                out.append(jax.random.normal(jax.random.fold_in(key, i), (4,)))
            return out
    """) == []


def test_jx001_carried_split_in_loop_is_clean():
    # the repo's training-loop idiom: the key is re-derived every iteration
    assert rules_hit("""
        import jax

        def train(key, n):
            for i in range(n):
                key, kr = jax.random.split(key)
                x = jax.random.normal(kr, (4,))
            return x
    """) == []


def test_jx001_helper_consumption_counts():
    # PR-2 consumed the key through a helper, not jax.random directly —
    # any call taking the bare key is a consumption
    assert rules_hit("""
        import jax

        def sample(key, itp, x1):
            base = jax.random.normal(key, x1.shape)
            return itp.sample_bridge(key, base)
    """) == ["JX001"]


def test_jx001_ignores_non_prng_key_params():
    # dict-style __getitem__(self, key) and attention's K tensor share the
    # *names* but never touch the PRNG — no finding
    assert rules_hit("""
        class Store:
            def __getitem__(self, key):
                if isinstance(key, int):
                    return self.take([key])
                if isinstance(key, slice):
                    return self.take(list(key.indices(self.n)))
                return self.take(key)

        def attention(q, k, v, causal):
            if causal:
                return ref(q, k, v)
            return fast(q, k, v)
    """) == []


def test_jx001_str_split_does_not_mint_keys():
    assert rules_hit("""
        def parse(args, fetch):
            name, n = args.calo.split(":")
            a = fetch(n)
            b = fetch(n)
            return name, a, b
    """) == []


def test_jx001_early_return_branches_are_exclusive():
    # one consumption in a returning arm + one on the fall-through path
    # never happen in the same execution
    assert rules_hit("""
        import jax

        def init(key, d, gated):
            k1, k2 = jax.random.split(key)
            if gated:
                return make_gated(k1, d)
            return make_plain(k1, d)
    """) == []


def test_jx001_reuse_inside_one_branch_still_flags():
    assert rules_hit("""
        import jax

        def init(key, d, gated):
            k1, k2 = jax.random.split(key)
            if gated:
                a = jax.random.normal(k1, (d,))
                b = jax.random.normal(k1, (d,))
                return a + b
            return make_plain(k2, d)
    """) == ["JX001"]


# ---------------------------------------------------------------------------
# JX002 — import-time env snapshot (the PR-4 REPRO_HIST_IMPL bug)
# ---------------------------------------------------------------------------

PR4_ENV_BUG = """
    import os

    _IMPL = os.environ.get("REPRO_HIST_IMPL", "xla")

    def hist(x):
        if _IMPL == "pallas":
            return hist_pallas(x)
        return hist_xla(x)
"""


def test_jx002_flags_the_pr4_snapshot():
    fs = findings(PR4_ENV_BUG)
    assert [f.rule for f in fs] == ["JX002"]
    assert "resolve_impl" in fs[0].message


@pytest.mark.parametrize("read", [
    'os.environ.get("X", "d")', 'os.getenv("X")', 'os.environ["X"]'])
def test_jx002_flags_every_read_spelling(read):
    assert rules_hit(f"import os\nC = {read}\n") == ["JX002"]


def test_jx002_function_scope_read_is_clean():
    assert rules_hit("""
        import os

        def impl():
            return os.environ.get("REPRO_HIST_IMPL", "xla")
    """) == []


def test_jx002_class_method_read_is_clean():
    # a per-call env read inside a method runs at call time, not import
    # time — PR-8 false-positive fix
    assert rules_hit("""
        import os

        class Predictor:
            def impl(self):
                return os.environ.get("REPRO_TREE_PREDICT_IMPL", "xla")
    """) == []


def test_jx002_env_write_is_clean():
    # configuring the process at import (e.g. conftest forcing a platform)
    # is not a snapshot
    assert rules_hit("""
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault("XLA_FLAGS", "")
    """) == []


# ---------------------------------------------------------------------------
# JX003 — jit cache fragmentation / recompile leaks
# ---------------------------------------------------------------------------

def test_jx003_flags_inline_jit_call():
    fs = findings("""
        import jax

        def serve(params, x):
            return jax.jit(lambda p, x: apply(p, x))(params, x)
    """)
    assert [f.rule for f in fs] == ["JX003"]
    assert "fresh wrapper" in fs[0].message


def test_jx003_flags_jit_built_in_loop():
    assert rules_hit("""
        import jax

        def warmup(fns, x):
            outs = []
            for f in fns:
                g = jax.jit(f)
                outs.append(g(x))
            return outs
    """) == ["JX003"]


def test_jx003_flags_unhashable_default():
    assert rules_hit("""
        import jax

        @jax.jit
        def f(x, scales=[1.0, 2.0]):
            return x
    """) == ["JX003"]


def test_jx003_module_level_wrapper_is_clean():
    assert rules_hit("""
        import jax

        fit_batch = jax.jit(jax.vmap(fit_one))

        @jax.jit
        def step(params, batch, lr=1e-3):
            return params
    """) == []


def test_jx003_partial_jit_decorator_checked():
    assert rules_hit("""
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("n",))
        def f(x, n, init=jax.numpy.zeros(4)):
            return x
    """) == ["JX003"]


# ---------------------------------------------------------------------------
# TH001 — lock discipline (the PR-4 serving stats race)
# ---------------------------------------------------------------------------

PR4_STATS_RACE = """
    import threading

    class ForestServer:
        def __init__(self):
            self._stats_lock = threading.Lock()
            self.stats = {"rows": 0}

        def _dispatch(self, n):
            with self._stats_lock:
                self.stats["rows"] += n

        def submit(self, n):
            self.stats["requests"] = n   # unlocked write: the race
"""


def test_th001_flags_the_pr4_stats_race():
    fs = findings(PR4_STATS_RACE)
    assert [f.rule for f in fs] == ["TH001"]
    assert "submit" in fs[0].message


def test_th001_locked_suffix_convention_is_clean():
    assert rules_hit("""
        import threading

        class Scheduler:
            def __init__(self):
                self._lock = threading.Lock()
                self.queue = []

            def submit(self, r):
                with self._lock:
                    self.queue.append(r)
                    self._start_locked(r)

            def _start_locked(self, r):
                self.queue.append(r)   # caller holds the lock
    """) == []


def test_th001_container_mutator_counts_as_write():
    # the GridManifest shape: .add under the lock, bulk assignment outside
    assert rules_hit("""
        import threading

        class Manifest:
            def __init__(self):
                self._lock = threading.Lock()
                self._done = set()

            def mark(self, k):
                with self._lock:
                    self._done.add(k)

            def load(self, entries):
                self._done = set(entries)
    """) == ["TH001"]


def test_th001_locked_read_is_guard_evidence():
    # the PR-8 admission bug: per-tenant dict mutated via an unlocked
    # setdefault helper, while the only *locked* access is the snapshot
    # read — no locked write anywhere, so the pre-PR-8 rule stayed silent
    fs = findings("""
        import threading

        class Admission:
            def __init__(self):
                self._cond = threading.Condition()
                self._tenants = {}

            def _tenant_stats(self, tenant):
                return self._tenants.setdefault(tenant, {"admitted": 0})

            def stats_snapshot(self):
                with self._cond:
                    return {t: dict(v) for t, v in self._tenants.items()}
    """)
    assert [f.rule for f in fs] == ["TH001"]
    assert "_tenant_stats" in fs[0].message


def test_th001_locked_read_respects_locked_suffix():
    # same shape, but the mutating helper declares its contract: clean
    assert rules_hit("""
        import threading

        class Admission:
            def __init__(self):
                self._cond = threading.Condition()
                self._tenants = {}

            def _tenant_stats_locked(self, tenant):
                return self._tenants.setdefault(tenant, {"admitted": 0})

            def stats_snapshot(self):
                with self._cond:
                    return {t: dict(v) for t, v in self._tenants.items()}
    """) == []


def test_th001_unguarded_attrs_are_clean():
    assert rules_hit("""
        import threading

        class Worker:
            def __init__(self):
                self._lock = threading.Lock()
                self.scratch = None

            def run(self):
                self.scratch = 1   # never touched under the lock: no claim
    """) == []


# ---------------------------------------------------------------------------
# PL001 — Pallas grid divisibility (the PR-4 odd-bucket crash)
# ---------------------------------------------------------------------------

PL_BAD = """
    import jax.experimental.pallas as pl

    def predict(x, block):
        n = x.shape[0]
        return pl.pallas_call(kern, grid=(n // block,), out_shape=None)(x)
"""


def test_pl001_flags_unguarded_floordiv_grid():
    fs = findings(PL_BAD)
    assert [f.rule for f in fs] == ["PL001"]
    assert "pad" in fs[0].message


@pytest.mark.parametrize("guard", [
    "assert n % block == 0",
    "n = -(-n // block) * block",
    "x = pad_rows(x, block)",
    "if n % block:\n                raise ValueError('pad first')",
])
def test_pl001_each_guard_style_is_clean(guard):
    src = f"""
        import jax.experimental.pallas as pl

        def predict(x, block):
            n = x.shape[0]
            {guard}
            return pl.pallas_call(kern, grid=(n // block,), out_shape=None)(x)
    """
    assert rules_hit(src) == []


def test_pl001_cdiv_grid_is_clean():
    assert rules_hit("""
        import jax.experimental.pallas as pl

        def predict(x, block):
            n = x.shape[0]
            return pl.pallas_call(kern, grid=(pl.cdiv(n, block),),
                                  out_shape=None)(x)
    """) == []


# ---------------------------------------------------------------------------
# suppression + baseline machinery
# ---------------------------------------------------------------------------

def test_inline_suppression_same_line():
    src = textwrap.dedent(PR4_ENV_BUG).replace(
        '"xla")', '"xla")  # jaxlint: disable=JX002')
    fs, n_sup = lint_source(src, "fixture.py", None)
    assert fs == [] and n_sup == 1


def test_suppression_comment_line_above():
    src = ('import os\n'
           '# jaxlint: disable=JX002 — CI toggles this before any import\n'
           'C = os.environ.get("X")\n')
    fs, n_sup = lint_source(src, "fixture.py", None)
    assert fs == [] and n_sup == 1


def test_suppression_is_rule_specific():
    src = textwrap.dedent(PR4_ENV_BUG).replace(
        '"xla")', '"xla")  # jaxlint: disable=JX001')
    fs, n_sup = lint_source(src, "fixture.py", None)
    assert [f.rule for f in fs] == ["JX002"] and n_sup == 0


def test_suppress_all():
    src = textwrap.dedent(PR4_ENV_BUG).replace(
        '"xla")', '"xla")  # jaxlint: disable=all')
    fs, _ = lint_source(src, "fixture.py", None)
    assert fs == []


def test_parse_suppressions_multiple_rules():
    sup = parse_suppressions("x = 1  # jaxlint: disable=JX001, TH001\n")
    assert sup[1] == {"JX001", "TH001"}


def test_syntax_error_reports_jx000():
    fs, _ = lint_source("def f(:\n", "broken.py", None)
    assert [f.rule for f in fs] == ["JX000"]


def test_baseline_round_trip(tmp_path):
    fs = findings(PR4_ENV_BUG)
    path = tmp_path / "baseline.json"
    write_baseline(str(path), fs)
    baseline = load_baseline(str(path))
    new, grandfathered = split_baselined(fs, baseline)
    assert new == [] and grandfathered == fs
    # a finding that moved (different line) is new again
    moved = [Finding(f.rule, f.path, f.line + 5, f.col, f.message)
             for f in fs]
    new, _ = split_baselined(moved, baseline)
    assert new == moved


def test_baseline_file_shape(tmp_path):
    path = tmp_path / "baseline.json"
    write_baseline(str(path), findings(PR4_ENV_BUG))
    data = json.loads(path.read_text())
    assert set(data) == {"comment", "findings"}


# ---------------------------------------------------------------------------
# OB001 — span leaks (unended Tracer.start spans never record)
# ---------------------------------------------------------------------------

def test_ob001_early_return_leaks_span():
    # the motivating bug shape: validation bails before the span ends
    src = """
        def submit(self, n, ok):
            sp = self.tracer.start("serve.queue", rows=n)
            if not ok:
                return None
            sp.end()
    """
    fs = findings(src, select=("OB001",))
    assert [f.rule for f in fs] == ["OB001"]
    assert "every path" in fs[0].message
    assert "tracer.span(" in fs[0].message  # suggests the context manager


def test_ob001_never_ended_flags():
    assert rules_hit("""
        def f(tracer):
            sp = tracer.start("x")
            do_work()
    """, select=("OB001",)) == ["OB001"]


def test_ob001_raise_arm_without_end_flags():
    assert rules_hit("""
        def f(tracer, ok):
            sp = tracer.start("x")
            if not ok:
                raise ValueError("no")
            sp.end()
    """, select=("OB001",)) == ["OB001"]


def test_ob001_swallowing_handler_flags():
    # body ends the span but the except arm falls through without ending
    assert rules_hit("""
        def f(tracer):
            sp = tracer.start("x")
            try:
                work()
                sp.end()
            except Exception:
                pass
    """, select=("OB001",)) == ["OB001"]


def test_ob001_end_only_inside_loop_flags():
    # zero iterations is always a possible path
    assert rules_hit("""
        def f(tracer, items):
            sp = tracer.start("x")
            for it in items:
                sp.end()
    """, select=("OB001",)) == ["OB001"]


def test_ob001_clean_shapes_pass():
    good = [
        # the suggested fix: scoped context manager
        """
        def f(tracer):
            with tracer.span("x") as sp:
                work(sp)
        """,
        # try/finally always ends
        """
        def f(tracer):
            sp = tracer.start("x")
            try:
                work()
            finally:
                sp.end()
        """,
        # both branches end (with distinct outcomes)
        """
        def f(tracer, ok):
            sp = tracer.start("x")
            if ok:
                sp.end(outcome="ok")
            else:
                sp.end(outcome="bad")
        """,
        # end-then-terminate in the early arm is fine
        """
        def f(tracer, ok):
            sp = tracer.start("x")
            if not ok:
                sp.end(outcome="rejected")
                return None
            sp.end()
        """,
        # handler ends before re-raising
        """
        def f(tracer):
            sp = tracer.start("x")
            try:
                work()
                sp.end()
            except Exception:
                sp.end(outcome="error")
                raise
        """,
    ]
    for src in good:
        assert rules_hit(src, select=("OB001",)) == [], src


def test_ob001_escaped_spans_are_not_flagged():
    # ownership moved: the scheduler pattern (span rides a Request /
    # _Inflight record and is ended by another thread)
    escapes = [
        """
        def submit(self):
            sp = self.tracer.start("serve.queue")
            req = Request(span=sp)
            self.admission.offer(req)
        """,
        """
        def dispatch(self, batch):
            dspan = self.tracer.start("serve.device")
            return Inflight(batch, dspan)
        """,
    ]
    for src in escapes:
        assert rules_hit(src, select=("OB001",)) == [], src


def test_ob001_closure_end_and_foreign_receivers_skip():
    # end inside a nested def = closure owns the span: out of scope
    assert rules_hit("""
        def f(tracer):
            sp = tracer.start("x")
            def cb():
                sp.end()
            register(cb)
    """, select=("OB001",)) == []
    # receiver must *look like* a tracer: thread/pool .start() never match
    assert rules_hit("""
        def f(self):
            t = self.pool.start("worker")
            h = self.thread.start()
    """, select=("OB001",)) == []


# ---------------------------------------------------------------------------
# CLI contract
# ---------------------------------------------------------------------------

def run_cli(*args, cwd):
    return subprocess.run(
        [sys.executable, str(REPO / "scripts" / "jaxlint.py"), *args],
        cwd=cwd, capture_output=True, text=True)


def test_cli_exit_codes(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent(PR4_ENV_BUG))
    good = tmp_path / "good.py"
    good.write_text("def f():\n    return 1\n")

    r = run_cli(str(bad), "--no-baseline", cwd=tmp_path)
    assert r.returncode == 1
    assert "JX002" in r.stdout
    assert run_cli(str(good), "--no-baseline", cwd=tmp_path).returncode == 0
    assert run_cli(str(bad), "--select", "NOPE",
                   cwd=tmp_path).returncode == 2


def test_cli_write_baseline_grandfathers(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent(PR4_ENV_BUG))
    baseline = tmp_path / "b.json"
    assert run_cli(str(bad), "--baseline", str(baseline), "--write-baseline",
                   cwd=tmp_path).returncode == 0
    # grandfathered: exit 0; --no-baseline still reports it
    assert run_cli(str(bad), "--baseline", str(baseline),
                   cwd=tmp_path).returncode == 0
    assert run_cli(str(bad), "--no-baseline", cwd=tmp_path).returncode == 1


def test_cli_lists_all_rules():
    r = run_cli("--list-rules", cwd=REPO)
    assert r.returncode == 0
    for rule_id in ("JX001", "JX002", "JX003", "TH001", "PL001", "OB001"):
        assert rule_id in r.stdout


def test_repo_tree_is_clean():
    """The merged tree lints clean — the CI gate this PR turns on."""
    r = run_cli("src", "tests", "benchmarks", "scripts", cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr
