"""The room for a training cell of another template is real: in a
temporary copy, a `sessionrec` configuration is added as new files
(tests/data/sessionrec: its configs/, events/ and checks/ file) and
appended entries alone, and `run.py --tiny` trains it through `pio
train`'s own path to a result line. It is test data, not a supported
model: nothing in BENCHMARK.json names it."""
import filecmp
import json
import os
import shutil

import pytest

from benchmarks.lib import manifest
from benchmarks.tests.test_manifest import add_sessionrec_cell
from benchmarks.tests.test_rehearsal import bench_run

ROOT = manifest.ROOT
CELL = "seq-tiny.train"


@pytest.fixture
def checkout(tmp_path):
    """BENCHMARK.json and benchmarks/ copied, the program linked."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "predictionio_tpu"),
               tmp_path / "predictionio_tpu")
    return str(tmp_path)


def run_cell(checkout, *more):
    return bench_run("--workload", CELL, "--seed", str(2**31 + 26),
                     "--seconds", "1", "--tiny", *more, cwd=checkout,
                     script=os.path.join(checkout, "benchmarks", "run.py"))


def only_appended(old, new) -> bool:
    """`new` is `old` with entries appended to lists, at any depth."""
    if isinstance(old, list):
        return isinstance(new, list) and len(new) >= len(old) and all(
            only_appended(a, b) for a, b in zip(old, new))
    if isinstance(old, dict):
        return isinstance(new, dict) and set(new) == set(old) and all(
            only_appended(old[k], new[k]) for k in old)
    return old == new


@pytest.mark.parametrize("trace", ["0", "1"])
def test_a_sessionrec_train_is_added_as_files_and_entries(checkout, trace):
    add_sessionrec_cell(checkout)
    done = run_cell(checkout, "--trace", trace)
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert list(line)[-1] == "checks" and set(line["checks"]) == {
        "seqrec_nll_vs_start", "seqrec_items_unknown"}
    assert done.stderr.strip().splitlines()[-1].startswith(
        "CHECK seqrec_items_unknown value=0 limit=0 ok")
    if trace == "0":
        assert line["metrics"]["train_wall_s"]["value"] > 0
        assert line["metrics"]["setup_s"]["value"] > 0
    else:
        assert line["metrics"]["train_wall_median_s"]["value"] > 0
    # no file that was there differs from the repo's, byte for byte
    seen = 0
    for folder, _dirs, files in os.walk(os.path.join(ROOT, "benchmarks")):
        if "__pycache__" in folder:
            continue
        for fn in files:
            ours = os.path.join(folder, fn)
            theirs = os.path.join(checkout, os.path.relpath(ours, ROOT))
            assert filecmp.cmp(ours, theirs, shallow=False), ours
            seen += 1
    assert seen > 60
    # and BENCHMARK.json differs by appended entries and list members only
    with open(os.path.join(checkout, "BENCHMARK.json")) as f:
        new = json.load(f)
    old = manifest.load_benchmark()
    assert only_appended(old, new) and new != old
    assert len(new["configs"]) == len(old["configs"]) + 1
    assert len(new["workloads"]) == len(old["workloads"]) + 1
    # the repo's own manifest names nothing of it
    assert "seq" not in json.dumps(old)


def test_a_check_row_not_ok_ends_not_correct(checkout):
    add_sessionrec_cell(checkout)
    path = os.path.join(checkout, "benchmarks", "checks", "seqrec_loss.py")
    with open(path, "a") as f:
        f.write("\n\nsound = check\n\n\ndef check(run):\n"
                "    return sound(run) + [('seqrec_planted_fault', 1.0, 0.0, False)]\n")
    done = run_cell(checkout)
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is False
    assert line["checks"]["seqrec_planted_fault"] == {
        "value": 1.0, "limit": 0.0, "ok": False}
    assert line["checks"]["seqrec_nll_vs_start"]["ok"] is True
    assert "CHECK seqrec_planted_fault value=1 limit=0 NOT OK" in done.stdout
    assert done.stderr.strip().splitlines()[-1].endswith("NOT OK")


@pytest.mark.parametrize("missing", ["checks/seqrec_loss.py",
                                     "events/sessions.py"])
def test_a_missing_part_fails_before_the_child_starts(checkout, missing):
    add_sessionrec_cell(checkout)
    os.remove(os.path.join(checkout, "benchmarks", missing))
    bad = manifest.check(manifest.load_benchmark(checkout), checkout)
    assert any(missing in b for b in bad), bad
    done = run_cell(checkout)
    assert done.returncode != 0 and done.stdout.strip() == ""
    assert "FAILED: config seq-tiny: no file " + missing in done.stderr
    assert "Traceback" not in done.stderr and "[child" not in done.stderr
