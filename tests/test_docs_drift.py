"""Doc drift gate: the OBSERVABILITY.md metric inventory can no longer
silently rot.

Now a thin wrapper over the `pio check` engine — the collector moved to
predictionio_tpu/analysis/checkers/legacy.py as rule PIO101. The same
two assertions hold:

* every ``pio_*`` metric name registered anywhere under
  ``predictionio_tpu/`` appears in OBSERVABILITY.md;
* every ``pio_*`` token OBSERVABILITY.md mentions is registered in code.

When this fails you either added a metric without documenting it, or
removed/renamed one without updating the inventory — fix the doc, not
the test.
"""

import fnmatch
import json
import os
import pathlib
import re

import pytest

from predictionio_tpu.analysis import run_check
from predictionio_tpu.analysis.checkers.legacy import (
    documented_metric_names, registered_metric_names,
)


def test_every_registered_metric_is_documented(repo_project):
    registered = registered_metric_names(repo_project)
    assert registered, "collector found no metrics — the gate is broken"
    documented = documented_metric_names(
        repo_project.doc_text("OBSERVABILITY.md"))
    missing = sorted(set(registered) - documented)
    assert not missing, (
        f"metrics registered in code but absent from OBSERVABILITY.md: "
        f"{missing} — add them to the inventory")


def test_every_documented_metric_is_registered(repo_project):
    report = run_check(repo_project, rules=["PIO101"])
    stale = [f.message for f in report.findings
             if f.path == "OBSERVABILITY.md"]
    assert not stale, (
        "OBSERVABILITY.md mentions pio_* names no code registers — the "
        f"inventory rotted; remove or fix them: {stale}")
    assert not report.findings, [f.message for f in report.findings]


def test_collector_sees_the_known_corners(repo_project):
    """The gate only has teeth if the collector actually resolves the
    tricky registration shapes: constants passed by name, and metrics
    registered inside methods."""
    registered = registered_metric_names(repo_project)
    for probe in (
            "pio_jax_compile_total",            # module constant, by Name
            "pio_device_dispatch_seconds_total",  # same, obs/profiler.py
            "pio_obs_label_overflow_total",     # registry-internal
            "pio_span_duration_seconds",        # helper-function literal
            "pio_http_request_duration_seconds",
            "pio_slo_burn_rate",
            "pio_foldin_event_to_applied_seconds"):
        assert probe in registered, probe


# ---------------------------------------------------------------------------
# paths and commands the documents name exist in the tree
# ---------------------------------------------------------------------------

ROOT = pathlib.Path(__file__).resolve().parent.parent
PACKAGE = "predictionio_tpu"
GATED_DOCS = ["README.md", "OBSERVABILITY.md", "PARITY.md",
              ".claude/skills/verify/SKILL.md"]

FENCE_RE = re.compile(r"^```.*?^```", re.S | re.M)
SPAN_RE = re.compile(r"`([^`\n]+)`")
#: a path, then an optional `:line` or `:first-last`
PATH_TOKEN_RE = re.compile(r"^([\w.*<>/-]+?)(?::(\d+)(?:-(\d+))?)?$")
#: `chip_smoke.py`, `PERF.md`, `PERF_LEDGER.jsonl`: a root-level script, or
#: a document or record named in upper case (a user's `engine.json` is none)
ROOT_FILE_RE = re.compile(r"^(?:[\w-]+\.py|[A-Z][A-Z0-9]+[\w-]*\.(?:md|jsonl?))$")
PIO_VERB_RE = re.compile(r"\bpio ([a-z][a-z0-9_-]*)")
PYTHON_RE = re.compile(r"\bpython3? (-m )?([\w./-]+)")


def tree_files():
    """The files of the checkout, as the driver's copy has them: what
    building, testing and running leave behind (hidden directories but
    `.claude`, caches) is no part of the tree."""
    files = []
    for base, dirs, names in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d == ".claude" or not (
            d.startswith(".") or d in ("__pycache__", "chiprun_out"))]
        rel = pathlib.Path(base).relative_to(ROOT)
        files += [(rel / n).as_posix() for n in names]
    return sorted(files)


@pytest.fixture(scope="module")
def tree():
    files = tree_files()
    dirs = {str(parent) for f in files
            for parent in pathlib.PurePosixPath(f).parents} - {"."}
    return {
        "files": set(files), "dirs": dirs,
        "top": {d for d in dirs if "/" not in d},
        "sub": {d.split("/", 1)[1] for d in dirs
                if d.startswith(PACKAGE + "/") and d.count("/") == 1},
        "suffixes": {pathlib.PurePosixPath(f).suffix for f in files},
    }


def inline_spans(text):
    return SPAN_RE.findall(FENCE_RE.sub("", text))


def code_text(text):
    """Inline spans and fenced blocks: where a document shows commands."""
    return "\n".join(inline_spans(text) + FENCE_RE.findall(text))


def repo_path(token, tree):
    """`token` rooted in the tree (None when it is not a repo path): at
    a top-level directory, at a sub-package of the package, or a
    root-level script or upper-case document."""
    first, _, rest = token.partition("/")
    if not rest:
        return token if ROOT_FILE_RE.match(token) else None
    suffix = pathlib.PurePosixPath(token.rstrip("/")).suffix
    if re.fullmatch(r"\.[a-z]+", suffix) and suffix not in tree["suffixes"]:
        return None         # `core/src/.../Engine.scala`: the reference's
    if first in tree["top"]:
        return token
    if first in tree["sub"]:
        return f"{PACKAGE}/{token}"
    return None


def resolve(path, tree):
    """The tracked file or directory `path` names: as written, as a
    module (`obs/tracing` -> `.py`), as a module's attribute
    (`obs/tracing.span`), or as a pattern (`layer_metrics/<name>.json`)."""
    path = path.rstrip("/")
    pattern = re.sub(r"<[^>]*>", "*", path)
    if "*" in pattern:
        hits = fnmatch.filter(tree["files"] | tree["dirs"], pattern)
        return hits[0] if hits else None
    stem = path
    for _ in range(3):
        for candidate in (stem, stem + ".py"):
            if candidate in tree["files"] or candidate in tree["dirs"]:
                return candidate
        stem, dot, _attr = stem.rpartition(".")
        if not dot or "/" not in stem:
            return None
    return None


@pytest.mark.parametrize("doc", GATED_DOCS)
def test_documented_paths_exist(doc, tree):
    """Every code-spanned repo path names a file the tree has, and a
    line that file has."""
    text = (ROOT / doc).read_text()
    missing, checked = [], 0
    for span in inline_spans(text):
        for raw in span.split():
            m = PATH_TOKEN_RE.match(raw.strip("()[],;"))
            path = repo_path(m.group(1), tree) if m else None
            if path is None:
                continue
            checked += 1
            found = resolve(path, tree)
            if found is None:
                missing.append(raw)
            elif m.group(2) and found in tree["files"]:
                last = int(m.group(3) or m.group(2))
                n_lines = len((ROOT / found).read_text().splitlines())
                if last > n_lines:
                    missing.append(f"{raw} ({found} has {n_lines} lines)")
    assert checked, f"{doc}: the gate found no path to check"
    assert not missing, (
        f"{doc} names paths the tree does not have: {sorted(set(missing))}")


@pytest.mark.parametrize("doc", GATED_DOCS)
def test_documented_commands_exist(doc, tree):
    """Every `pio <verb>` is a registered subcommand and every
    `python <script>` (or `-m module`) is in the tree."""
    from predictionio_tpu.cli.main import cli

    code = code_text((ROOT / doc).read_text())
    verbs = set(PIO_VERB_RE.findall(code))
    unknown = sorted(verbs - set(cli.commands))
    assert not unknown, f"{doc} names `pio` verbs the CLI lacks: {unknown}"
    missing = []
    for module, target in PYTHON_RE.findall(code):
        if target.startswith("-"):
            continue
        path = target.replace(".", "/") if module else target
        if module and path.split("/")[0] not in tree["top"]:
            continue                      # `-m pytest`: installed, not ours
        if not module and not target.endswith(".py"):
            continue                      # `python -c`, prose
        if resolve(path, tree) is None:
            missing.append(target)
    assert not missing, (
        f"{doc} runs scripts the tree does not have: {sorted(set(missing))}")
    assert verbs or doc == "PARITY.md", f"{doc}: no `pio` command found"


def test_readme_names_the_benchmark_command():
    """The README's Development section shows the command the driver
    runs (`BENCHMARK.json`'s `command`) and where its record lives."""
    command = " ".join(json.loads(
        (ROOT / "BENCHMARK.json").read_text())["command"])
    readme = (ROOT / "README.md").read_text()
    section = readme.split("\n## Development", 1)[1].split("\n## ", 1)[0]
    assert command in "\n".join(FENCE_RE.findall(section)), (
        f"README.md's Development block does not show `{command}`")
    for name in ("PERF.md", "PERF_LEDGER.jsonl", "BENCHMARK.json"):
        assert f"`{name}`" in section, name
