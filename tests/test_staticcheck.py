"""`pio check` engine tests: per-rule positive/negative fixtures
(compiled from strings, never from repo files), suppression + baseline
semantics, the JSON report schema, and the repo-wide gates — zero
unbaselined findings, and the PIO006 knob registry doubling as the
env-var docs-drift gate (both directions, mirroring the metric gate).
"""

import json
import pathlib
import re
import textwrap

import pytest

from predictionio_tpu.analysis import (
    Baseline, Project, all_rules, run_check,
)
from predictionio_tpu.analysis import registry as reg
from predictionio_tpu.analysis.checkers.knobs import env_knob_reads

ROOT = pathlib.Path(__file__).resolve().parent.parent


def check_src(code, path="predictionio_tpu/mod.py", rules=None,
              files=None, aux=None):
    sources = {path: textwrap.dedent(code)}
    if files:
        sources.update({p: textwrap.dedent(t) for p, t in files.items()})
    project = Project.from_sources(sources, aux=aux)
    return run_check(project, rules=rules)


def rules_of(report):
    return [f.rule for f in report.findings]


# ---------------------------------------------------------------------------
# PIO001 — bare jit outside the fn_cache ledger
# ---------------------------------------------------------------------------

def test_pio001_flags_jit_built_per_call():
    r = check_src("""
        import jax

        def serve(x):
            return jax.jit(lambda a: a)(x)
    """, rules=["PIO001"])
    assert rules_of(r) == ["PIO001"]


def test_pio001_flags_jit_decorator_on_nested_def():
    r = check_src("""
        import jax

        def train(x):
            @jax.jit
            def step(a):
                return a
            return step(x)
    """, rules=["PIO001"])
    assert rules_of(r) == ["PIO001"]


def test_pio001_allows_module_level_jit():
    r = check_src("""
        import functools
        import jax

        F = jax.jit(lambda a: a)

        @jax.jit
        def g(a):
            return a

        @functools.partial(jax.jit, static_argnames=("n",))
        def h(a, n):
            return a * n
    """, rules=["PIO001"])
    assert rules_of(r) == []


def test_pio001_allows_fn_cache_builders_transitively():
    """build() -> make_fn() -> jax.jit(...) is routed: the whole-program
    pass follows the call graph from the registered builder."""
    r = check_src("""
        import jax
        from predictionio_tpu.ops.fn_cache import mesh_cached_fn

        def make_fn():
            def f(a):
                return a
            return jax.jit(f)

        def cached(mesh):
            def build():
                return make_fn()
            return mesh_cached_fn("fam", mesh, (), build)
    """, rules=["PIO001"])
    assert rules_of(r) == []


def test_pio001_allows_lambda_builders():
    r = check_src("""
        import jax
        from predictionio_tpu.ops.fn_cache import shape_cached_fn

        def cached(key):
            return shape_cached_fn("fam", key, lambda: jax.jit(lambda a: a))
    """, rules=["PIO001"])
    assert rules_of(r) == []


# ---------------------------------------------------------------------------
# PIO002 — durable writes must commit via temp-write + rename
# ---------------------------------------------------------------------------

def test_pio002_flags_bare_durable_write():
    r = check_src("""
        def save(path, doc):
            with open(path, "w") as f:
                f.write(doc)
    """, rules=["PIO002"])
    assert rules_of(r) == ["PIO002"]


def test_pio002_flags_fs_open_write():
    r = check_src("""
        class Store:
            def put(self, path, blob):
                with self.fs.open(path, "wb") as f:
                    f.write(blob)
    """, rules=["PIO002"])
    assert rules_of(r) == ["PIO002"]


def test_pio002_allows_same_function_commit():
    r = check_src("""
        import os

        def save(path, doc):
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                f.write(doc)
            os.replace(tmp, path)
    """, rules=["PIO002"])
    assert rules_of(r) == []


def test_pio002_allows_writer_reached_from_committer():
    r = check_src("""
        import os

        def _write_parts(tmp, doc):
            with open(tmp, "w") as f:
                f.write(doc)

        def commit(path, doc):
            _write_parts(path + ".tmp", doc)
            os.replace(path + ".tmp", path)
    """, rules=["PIO002"])
    assert rules_of(r) == []


def test_pio002_allows_sink_class_with_commit_method():
    """The batchpredict sink shape: open in __init__, rename in
    commit() — same class (or a base) owning the commit is enough."""
    r = check_src("""
        import os

        class Sink:
            def __init__(self, target):
                self.target = target
                self.tmp = target + ".tmp"
                self._f = open(self.tmp, "w")

            def commit(self):
                self._f.close()
                os.replace(self.tmp, self.target)

        class JsonlSink(Sink):
            def reopen(self):
                self._f = open(self.tmp, "w")
    """, rules=["PIO002"])
    assert rules_of(r) == []


def test_pio002_reads_are_not_writes():
    r = check_src("""
        def load(path):
            with open(path) as f:
                return f.read()
    """, rules=["PIO002"])
    assert rules_of(r) == []


# ---------------------------------------------------------------------------
# PIO003 — thread hops must carry the trace plane
# ---------------------------------------------------------------------------

def test_pio003_flags_uncarried_thread():
    r = check_src("""
        import threading

        def start(fn):
            threading.Thread(target=fn, daemon=True).start()
    """, rules=["PIO003"])
    assert rules_of(r) == ["PIO003"]


def test_pio003_flags_uncarried_executor_submit():
    r = check_src("""
        def fan_out(executor, task):
            return executor.submit(task, 1)
    """, rules=["PIO003"])
    assert rules_of(r) == ["PIO003"]


def test_pio003_allows_submitter_that_captures_context():
    r = check_src("""
        import threading
        from predictionio_tpu.obs.tracing import capture_context, carried

        def start():
            ctx = capture_context()

            def run():
                with carried(ctx, "worker"):
                    pass

            threading.Thread(target=run, daemon=True).start()
    """, rules=["PIO003"])
    assert rules_of(r) == []


def test_pio003_allows_target_that_carries_transitively():
    """Thread(target=self._worker) where _worker -> _flush -> carried."""
    r = check_src("""
        import threading
        from predictionio_tpu.obs.tracing import carried

        class Buffer:
            def start(self):
                threading.Thread(target=self._worker).start()

            def _worker(self):
                self._flush()

            def _flush(self):
                with carried(None, "flush"):
                    pass
    """, rules=["PIO003"])
    assert rules_of(r) == []


def test_pio003_ignores_non_executor_submit():
    """MicroBatcher.submit(query) is an enqueue, not a thread hop."""
    r = check_src("""
        def enqueue(batcher, query):
            return batcher.submit(query)
    """, rules=["PIO003"])
    assert rules_of(r) == []


# ---------------------------------------------------------------------------
# PIO004 — no blocking work under a held lock
# ---------------------------------------------------------------------------

def test_pio004_flags_sleep_under_swap_lock():
    r = check_src("""
        import threading
        import time

        class Server:
            def __init__(self):
                self._swap_lock = threading.Lock()

            def swap(self, unit):
                with self._swap_lock:
                    time.sleep(0.1)
                    self._unit = unit
    """, path="predictionio_tpu/deploy/mod.py", rules=["PIO004"])
    assert rules_of(r) == ["PIO004"]


def test_pio004_flags_future_result_under_lock():
    r = check_src("""
        def wait(lock, fut):
            with lock:
                return fut.result(timeout=30)
    """, path="predictionio_tpu/data/write_buffer.py", rules=["PIO004"])
    assert rules_of(r) == ["PIO004"]


def test_pio004_allows_blocking_outside_lock_and_nested_defs():
    r = check_src("""
        import time

        class Server:
            def swap(self, unit):
                time.sleep(0.1)            # before the critical section
                with self._swap_lock:
                    self._unit = unit

                    def later():
                        time.sleep(1)      # deferred, runs unlocked
                    self._cb = later
    """, path="predictionio_tpu/deploy/mod.py", rules=["PIO004"])
    assert rules_of(r) == []


def test_pio004_out_of_scope_modules_are_exempt():
    r = check_src("""
        import time

        def slow(lock):
            with lock:
                time.sleep(1)
    """, path="predictionio_tpu/models/mod.py", rules=["PIO004"])
    assert rules_of(r) == []


# ---------------------------------------------------------------------------
# PIO005 — kill points stay lethal
# ---------------------------------------------------------------------------

def test_pio005_flags_swallowed_base_exception():
    r = check_src("""
        def tick(fn):
            try:
                fn()
            except BaseException:
                pass
    """, rules=["PIO005"])
    assert rules_of(r) == ["PIO005"]


def test_pio005_flags_bare_except_without_reraise():
    r = check_src("""
        def tick(fn):
            try:
                fn()
            except:
                return None
    """, rules=["PIO005"])
    assert rules_of(r) == ["PIO005"]


def test_pio005_allows_reraise_and_relay():
    r = check_src("""
        def guarded(fn, fut, errs):
            try:
                fn()
            except BaseException:
                errs.clear()
                raise
            try:
                fn()
            except BaseException as e:
                fut.set_exception(e)
    """, rules=["PIO005"])
    assert rules_of(r) == []


def test_pio005_plain_exception_is_fine():
    r = check_src("""
        def tick(fn):
            try:
                fn()
            except Exception:
                pass
    """, rules=["PIO005"])
    assert rules_of(r) == []


# ---------------------------------------------------------------------------
# PIO006 — PIO_* knobs: registered, and read by their owner
# ---------------------------------------------------------------------------

def test_pio006_flags_unregistered_knob():
    r = check_src("""
        import os

        def flag():
            return os.environ.get("PIO_TOTALLY_NEW_KNOB", "0")
    """, rules=["PIO006"])
    assert rules_of(r) == ["PIO006"]
    assert "registered nowhere" in r.findings[0].message


def test_pio006_flags_read_outside_owner():
    r = check_src("""
        import os

        def tracing_on():
            return os.environ.get("PIO_TRACING", "1") != "0"
    """, path="predictionio_tpu/server/mod.py", rules=["PIO006"])
    assert rules_of(r) == ["PIO006"]
    assert "obs/tracing.py" in r.findings[0].message


def test_pio006_allows_owner_and_server_config():
    r = check_src("""
        import os

        TRACING_ENV = "PIO_TRACING"

        def enabled():
            return os.environ.get(TRACING_ENV, "1") != "0"
    """, path="predictionio_tpu/obs/tracing.py", rules=["PIO006"],
        files={
            "predictionio_tpu/utils/server_config.py": """
                import os

                def load():
                    return os.environ.get("PIO_MY_SERVER_KNOB")
            """})
    assert rules_of(r) == []


def test_pio006_resolves_constants_and_subscripts():
    """Reads through module constants and __getitem__/in shapes are
    still seen (the DISPATCH_ENV pattern)."""
    project = Project.from_sources({"predictionio_tpu/x.py": textwrap.dedent("""
        import os

        KNOB = "PIO_SOME_KNOB"

        def read():
            if KNOB in os.environ:
                return os.environ[KNOB]
            return os.getenv("PIO_OTHER_KNOB")
    """)})
    knobs = {k for _, _, k in env_knob_reads(project)}
    assert knobs == {"PIO_SOME_KNOB", "PIO_OTHER_KNOB"}


# ---------------------------------------------------------------------------
# PIO007 — nondeterminism inside traced fns
# ---------------------------------------------------------------------------

def test_pio007_flags_wall_clock_in_jitted_fn():
    r = check_src("""
        import time

        import jax

        @jax.jit
        def f(x):
            return x * time.time()
    """, rules=["PIO007"])
    assert rules_of(r) == ["PIO007"]


def test_pio007_flags_random_in_fn_passed_to_jit():
    r = check_src("""
        import random

        import jax

        def noisy(x):
            return x + random.random()

        F = jax.jit(noisy)
    """, rules=["PIO007"])
    assert rules_of(r) == ["PIO007"]


def test_pio007_untraced_fns_may_use_the_clock():
    r = check_src("""
        import time

        def measure(fn):
            t0 = time.perf_counter()
            fn()
            return time.perf_counter() - t0
    """, rules=["PIO007"])
    assert rules_of(r) == []


# ---------------------------------------------------------------------------
# PIO008 — wire determinism
# ---------------------------------------------------------------------------

def test_pio008_flags_mutable_default_args():
    r = check_src("""
        def serve(q, extras=[], opts={}):
            return q
    """, rules=["PIO008"])
    assert rules_of(r) == ["PIO008", "PIO008"]


def test_pio008_flags_set_iteration_on_wire_path():
    r = check_src("""
        def to_wire(names):
            out = []
            for n in set(names):
                out.append(n)
            return out
    """, path="predictionio_tpu/data/event.py", rules=["PIO008"])
    assert rules_of(r) == ["PIO008"]


def test_pio008_sorted_sets_and_non_wire_modules_pass():
    r = check_src("""
        def to_wire(names):
            return [n for n in sorted(set(names))]

        def hot_path(names, cache=None):
            for n in set(names):      # not a wire module iteration
                pass
    """, path="predictionio_tpu/models/mod.py", rules=["PIO008"])
    assert rules_of(r) == []


# ---------------------------------------------------------------------------
# PIO009 — telemetry segment writers ride the committed-write helpers
# ---------------------------------------------------------------------------

def test_pio009_flags_segment_write_outside_the_helpers():
    r = check_src("""
        import os

        class TSDB:
            def _commit_file(self, name, records):
                tmp = name + ".tmp"
                with open(tmp, "wb") as f:
                    f.write(records)
                os.replace(tmp, name)

            def quick_fix(self, path, buf):
                with open(path, "ab") as f:   # bypasses the framing
                    f.write(buf)
    """, path="predictionio_tpu/obs/tsdb.py", rules=["PIO009"])
    assert rules_of(r) == ["PIO009"]
    assert "quick_fix" in r.findings[0].message


def test_pio009_registered_helpers_and_other_modules_pass():
    code = """
        import os

        class TSDB:
            def _ensure_active(self, path):
                self._f = open(path, "ab")

            def _append_payload(self, buf):
                self._f.write(buf)

            def _commit_file(self, name, records):
                tmp = name + ".tmp"
                with open(tmp, "wb") as f:
                    f.write(records)
                os.replace(tmp, name)
    """
    r = check_src(code, path="predictionio_tpu/obs/tsdb.py",
                  rules=["PIO009"])
    assert rules_of(r) == []
    # the rule is scoped: the same write elsewhere is not its business
    # (PIO002 owns the general commit discipline)
    r = check_src("""
        def write(path, buf):
            with open(path, "ab") as f:
                f.write(buf)
    """, path="predictionio_tpu/models/mod.py", rules=["PIO009"])
    assert rules_of(r) == []


def test_pio009_helper_registry_matches_the_real_module(repo_project):
    """Rot guard: every registered committed-write helper exists in the
    module it is registered for (a rename would silently un-protect
    the store)."""
    paths = {f.path: f for f in repo_project.files}
    for path, helpers in reg.SEGMENT_WRITE_HELPERS.items():
        f = paths.get(path)
        assert f is not None, f"SEGMENT_WRITE_HELPERS names missing {path}"
        names = {i.name for i in repo_project.functions.infos
                 if i.file is f}
        for helper in helpers:
            assert helper in names, (
                f"{path}: registered helper {helper} does not exist")


# ---------------------------------------------------------------------------
# PIO100/PIO101/PIO102 — the ported legacy gates
# ---------------------------------------------------------------------------

def test_pio100_print_fixture():
    bad = check_src("def f():\n    print('x')\n", rules=["PIO100"])
    assert rules_of(bad) == ["PIO100"]
    good = check_src("def f(x):\n    return fingerprint(x)\n",
                     rules=["PIO100"])
    assert rules_of(good) == []


def test_pio101_metric_drift_fixture():
    code = """
        def install(registry):
            registry.counter("pio_good_total", "ok")
            registry.counter("pio_undocumented_total", "drifted")
    """
    r = check_src(code, rules=["PIO101"],
                  aux={"OBSERVABILITY.md":
                       "pio_good_total\npio_ghost_total\n"})
    msgs = sorted(f.message for f in r.findings)
    assert len(msgs) == 2
    assert "pio_undocumented_total" in msgs[1]
    assert "pio_ghost_total" in msgs[0]
    clean = check_src(code.replace('"pio_undocumented_total", "drifted"',
                                   '"pio_good_total", "ok"'),
                      rules=["PIO101"],
                      aux={"OBSERVABILITY.md": "pio_good_total\n"})
    assert rules_of(clean) == []


def test_pio102_engine_row_find_fixture():
    bad = check_src("""
        def train(ctx):
            return list(EventStoreClient.find(app_name="a"))
    """, path="predictionio_tpu/engines/mod.py", rules=["PIO102"])
    assert rules_of(bad) == ["PIO102"]
    good = check_src("""
        def serve(ctx):
            return EventStoreClient.find_by_entity("a", "user", "u1")
    """, path="predictionio_tpu/engines/mod.py", rules=["PIO102"])
    assert rules_of(good) == []
    elsewhere = check_src("""
        def migrate():
            return list(EventStoreClient.find(app_name="a"))
    """, path="predictionio_tpu/data/mod.py", rules=["PIO102"])
    assert rules_of(elsewhere) == []


# ---------------------------------------------------------------------------
# suppression semantics
# ---------------------------------------------------------------------------

def test_inline_suppression_with_reason():
    r = check_src("""
        def save(path, doc):
            with open(path, "w") as f:  # pio: ignore[PIO002]: one-shot marker
                f.write(doc)
    """, rules=["PIO002"])
    assert rules_of(r) == []


def test_standalone_suppression_shields_next_line():
    r = check_src("""
        def save(path, doc):
            # pio: ignore[PIO002]: one-shot marker file
            with open(path, "w") as f:
                f.write(doc)
    """, rules=["PIO002"])
    assert rules_of(r) == []


def test_file_level_suppression():
    r = check_src("""
        # pio: ignore-file[PIO002]: append-only log, framing handles torn tails
        def a(p):
            open(p, "w").write("x")

        def b(p):
            open(p, "w").write("y")
    """, rules=["PIO002"])
    assert rules_of(r) == []


def test_suppression_is_rule_specific():
    r = check_src("""
        def save(path, doc):
            with open(path, "w") as f:  # pio: ignore[PIO001]: wrong rule
                f.write(doc)
    """, rules=["PIO002"])
    assert rules_of(r) == ["PIO002"]


def test_suppression_without_reason_is_pio090_and_does_not_suppress():
    r = check_src("""
        def save(path, doc):
            with open(path, "w") as f:  # pio: ignore[PIO002]
                f.write(doc)
    """, rules=["PIO002", "PIO090"])
    assert sorted(rules_of(r)) == ["PIO002", "PIO090"]


def test_malformed_suppression_is_pio090():
    r = check_src("""
        X = 1  # pio: ignore PIO002 forgot the brackets
    """, rules=["PIO090"])
    assert rules_of(r) == ["PIO090"]


# ---------------------------------------------------------------------------
# baseline semantics
# ---------------------------------------------------------------------------

BASELINE_SRC = """
    def a(path):
        with open(path, "w") as f:
            f.write("1")

    def b(path):
        with open(path, "w") as f:
            f.write("2")
"""


def test_baseline_absorbs_known_findings(tmp_path):
    first = check_src(BASELINE_SRC, rules=["PIO002"])
    assert len(first.findings) == 2
    baseline = Baseline.from_findings(first.findings)
    path = tmp_path / "baseline.json"
    baseline.save(path)
    again = run_check(
        Project.from_sources(
            {"predictionio_tpu/mod.py": textwrap.dedent(BASELINE_SRC)}),
        rules=["PIO002"], baseline=Baseline.load(path))
    assert again.findings == []
    assert len(again.baselined) == 2
    assert again.ok


def test_baseline_is_a_multiset_and_survives_line_drift(tmp_path):
    first = check_src(BASELINE_SRC, rules=["PIO002"])
    baseline = Baseline.from_findings(first.findings[:1])   # absorb ONE
    shifted = "# a new comment shifts every line\n" + \
        textwrap.dedent(BASELINE_SRC)
    report = run_check(
        Project.from_sources({"predictionio_tpu/mod.py": shifted}),
        rules=["PIO002"], baseline=baseline)
    # content-keyed: line drift doesn't resurface the baselined one,
    # and the second identical write is NOT absorbed by a count-1 entry
    assert len(report.findings) == 1
    assert len(report.baselined) == 1


def test_baseline_json_shape(tmp_path):
    first = check_src(BASELINE_SRC, rules=["PIO002"])
    path = tmp_path / "baseline.json"
    Baseline.from_findings(first.findings).save(path)
    doc = json.loads(path.read_text())
    assert doc["version"] == 1
    assert {"rule", "path", "snippet", "count"} <= set(
        doc["findings"][0].keys())


# ---------------------------------------------------------------------------
# report schema / engine surface
# ---------------------------------------------------------------------------

def test_json_report_schema():
    report = check_src("def f():\n    print('x')\n", rules=["PIO100"])
    doc = report.to_json()
    assert set(doc) == {"version", "ok", "rules", "filesChecked",
                        "findings", "baselinedCount", "parseErrors"}
    assert doc["ok"] is False and doc["baselinedCount"] == 0
    f = doc["findings"][0]
    assert set(f) == {"path", "line", "rule", "message", "snippet", "col"}
    assert f["rule"] == "PIO100" and f["line"] == 2


def test_unknown_rule_is_an_error():
    with pytest.raises(ValueError):
        check_src("X = 1\n", rules=["PIO999"])


def test_all_rules_inventory():
    rules = all_rules()
    expected = {"PIO001", "PIO002", "PIO003", "PIO004", "PIO005",
                "PIO006", "PIO007", "PIO008", "PIO009", "PIO090",
                "PIO100", "PIO101", "PIO102"}
    assert set(rules) == expected
    assert all(rules.values()), "every rule carries a title"


# ---------------------------------------------------------------------------
# the repo-wide gates
# ---------------------------------------------------------------------------

def test_repo_is_clean_under_pio_check(repo_project):
    """THE gate: `pio check` exits 0 on the tree — zero findings outside
    the committed baseline, no parse errors."""
    baseline = Baseline.load(ROOT / "conf" / "pio_check_baseline.json")
    report = run_check(repo_project, baseline=baseline)
    assert not report.parse_errors, report.parse_errors
    assert not report.findings, "\n" + report.render()


def test_project_scan_is_the_package_only(tmp_path):
    """`Project.from_root` scans `predictionio_tpu/**/*.py` and nothing
    else: a script at the root is not part of the checked program (its
    names would join the call graph and its findings the report)."""
    pkg = tmp_path / "predictionio_tpu" / "sub"
    pkg.mkdir(parents=True)
    (pkg / "mod.py").write_text("x = 1\n")
    (tmp_path / "script.py").write_text("print('a root-level script')\n")
    (tmp_path / "README.md").write_text("# readme\n")
    project = Project.from_root(tmp_path)
    assert [f.path for f in project.files] == ["predictionio_tpu/sub/mod.py"]
    assert project.doc_text("README.md") == "# readme\n"
    assert not run_check(project).findings
    # an explicit path is still honoured
    named = Project.from_root(tmp_path, paths=["script.py"])
    assert [f.path for f in named.files] == ["script.py"]


def test_baseline_has_not_rotted(repo_project):
    """Every grandfathered entry still matches a live finding — a fixed
    finding must leave the baseline too (shrink-only discipline)."""
    baseline = Baseline.load(ROOT / "conf" / "pio_check_baseline.json")
    report = run_check(repo_project, baseline=baseline)
    absorbed = sum(baseline.entries.values())
    assert len(report.baselined) == absorbed, (
        f"baseline lists {absorbed} findings but only "
        f"{len(report.baselined)} still exist — remove the fixed "
        "entries from conf/pio_check_baseline.json")


def test_path_filter_keeps_whole_program_context(repo_project):
    """`pio check <one file>` must still index the FULL tree: a
    path-restricted run may not invent findings a full run doesn't have
    (e.g. PIO101 calling every metric stale because only one module's
    registrations were parsed)."""
    baseline = Baseline.load(ROOT / "conf" / "pio_check_baseline.json")
    report = run_check(repo_project, baseline=baseline,
                       paths=["predictionio_tpu/deploy/foldin.py",
                              "predictionio_tpu/deploy"])
    assert not report.findings, "\n" + report.render()


def test_cli_check_json(tmp_path):
    """`pio check --json -r PIO102` through the click surface."""
    from click.testing import CliRunner

    from predictionio_tpu.cli.main import cli

    result = CliRunner().invoke(cli, ["check", "--json", "-r", "PIO102"])
    assert result.exit_code == 0, result.output
    doc = json.loads(result.output)
    assert doc["ok"] is True and doc["rules"] == ["PIO102"]


def test_cli_check_rejects_partial_baseline_rewrite():
    """--write-baseline on a filtered run would silently drop every
    other rule's grandfathered entries — refused outright."""
    from click.testing import CliRunner

    from predictionio_tpu.cli.main import cli

    result = CliRunner().invoke(
        cli, ["check", "-r", "PIO002", "--write-baseline"])
    assert result.exit_code == 2
    assert "cannot be combined" in result.output


def test_cli_check_rejects_unmatched_paths():
    """A mistyped PATH must error, not silently filter every finding
    away and report clean; `./`-relative spellings normalize."""
    from click.testing import CliRunner

    from predictionio_tpu.cli.main import cli

    bad = CliRunner().invoke(cli, ["check", "predictionio_tpu/nope.py"])
    assert bad.exit_code == 2
    assert "matches no scanned file" in bad.output
    dotted = CliRunner().invoke(
        cli, ["check", "./predictionio_tpu/deploy/foldin.py"])
    assert dotted.exit_code == 0, dotted.output


# ---------------------------------------------------------------------------
# knob-docs drift gate (the PIO006 registry doubling as a docs gate)
# ---------------------------------------------------------------------------

KNOB_TOKEN_RE = re.compile(r"\bPIO_[A-Z0-9_]+\b")


def _documented_knob_tokens():
    text = (ROOT / "README.md").read_text() + \
        (ROOT / "OBSERVABILITY.md").read_text()
    return set(KNOB_TOKEN_RE.findall(text))


def test_every_read_knob_is_documented(repo_project):
    """Every PIO_* env var the package reads appears in
    README.md/OBSERVABILITY.md — a knob you can set but cannot find is
    config rot."""
    read = {k for _, _, k in env_knob_reads(repo_project)}
    tokens = _documented_knob_tokens()
    prefixes = {t for t in tokens if t.endswith("_")}
    missing = sorted(
        k for k in read
        if k not in tokens and not any(k.startswith(p) for p in prefixes))
    assert not missing, (
        f"env knobs read in code but documented nowhere: {missing} — "
        "add them to the README configuration table")


def test_every_documented_knob_is_real(repo_project):
    """Every PIO_* token the docs mention is either read in code or
    registered in the knob table — the inventory can't rot forward."""
    read = {k for _, _, k in env_knob_reads(repo_project)}
    table = reg.knob_table(repo_project)
    known = read | set(table) | set(reg.KNOB_PREFIXES)
    prefixes = {p for p in known if p.endswith("_")}
    stale = sorted(
        t for t in _documented_knob_tokens()
        if t not in known
        and not any(t.startswith(p) for p in prefixes)
        and not (t.endswith("_") and any(k.startswith(t) for k in known)))
    assert not stale, (
        f"docs mention PIO_* names nothing reads or registers: {stale}")


def test_knob_registry_owners_exist(repo_project):
    """The registry can't rot either: every owner path in KNOB_OWNERS
    names a real module (or the tests/ escape), and every registered
    knob is actually read somewhere it is allowed."""
    paths = {f.path for f in repo_project.files}
    for knob, owners in reg.KNOB_OWNERS.items():
        for owner in owners:
            assert owner == "tests/" or owner in paths, (
                f"{knob} names missing owner {owner}")
