"""The CPU rehearsal (--tiny) runs every cell end to end, and without
--tiny and without a chip the command fails and prints no result."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks.lib import manifest

ROOT = manifest.ROOT
BENCH = manifest.load_benchmark()
CELLS = [c["name"] for c in BENCH["workloads"]]


def bench_run(*args, cwd=ROOT, script=None, timeout=600):
    script = script or os.path.join(ROOT, "benchmarks", "run.py")
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["BENCH_RUN"] = "ignored"
    return subprocess.run([sys.executable, script, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("trace", ["0", "1"])
@pytest.mark.parametrize("workload", CELLS)
def test_tiny_runs_the_cell_end_to_end(workload, trace):
    done = bench_run("--workload", workload, "--seed", str(2**31 + 5),
                     "--seconds", "1", "--trace", trace, "--tiny")
    assert done.returncode == 0, done.stderr[-3000:]
    lines = done.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert "busy_s" not in line["device"] and "breakdown" not in line
    assert any(ln.startswith("JOB ") for ln in lines)
    assert any(ln.startswith("CHECK ") and " limit=" in ln for ln in lines)
    group = "per_layer" if trace == "1" else "end_to_end"
    known = {m["name"]: m for m in manifest.metrics_of_cell(
        BENCH, workload, group)}
    assert line["metrics"] and set(line["metrics"]) <= set(known)
    for name, m in line["metrics"].items():
        assert m["unit"] == known[name]["unit"]
        # no CPU number under a device metric's name
        assert known[name]["source"] != "device_trace"
        assert not name.startswith("hbm_")
    if trace == "0":
        assert set(line["metrics"]) == set(known)
        assert line["metrics"]["setup_s"]["value"] > 0


def test_without_a_chip_there_is_no_result():
    done = bench_run("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                     "--trace", "0", timeout=900)
    assert done.returncode != 0
    assert not any(ln.startswith("{") for ln in done.stdout.splitlines())


def test_alone_in_a_directory_it_fails(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    done = bench_run("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                     "--trace", "0", "--tiny", cwd=str(tmp_path),
                     script=str(tmp_path / "benchmarks" / "run.py"))
    assert done.returncode != 0 and done.stdout.strip() == ""


def test_an_unknown_workload_fails():
    done = bench_run("--workload", "no.such.cell", "--seed", "1", "--tiny")
    assert done.returncode != 0 and done.stdout.strip() == ""


@pytest.mark.parametrize("trace", ["0", "1"])
def test_tiny_runs_the_batchpredict_cell_added_as_entries(tmp_path, trace):
    """The `batchpredict` kind end to end on the CPU. The cell exists only
    in a temporary copy of the manifest (PERF.md, Open questions: proven
    on the chip in PR 23, its memory peak is under the driver's floor)."""
    from benchmarks.tests.test_manifest import add_batchpredict_cell

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    os.symlink(os.path.join(ROOT, "predictionio_tpu"),
               tmp_path / "predictionio_tpu")
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    add_batchpredict_cell(str(tmp_path))
    done = bench_run("--workload", "msd-r128.batchpredict", "--seed", "9",
                     "--seconds", "1", "--trace", trace, "--tiny",
                     cwd=str(tmp_path),
                     script=str(tmp_path / "benchmarks" / "run.py"))
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["attempted"] % 512 == 0
    if trace == "0":
        assert set(line["metrics"]) == {"batchpredict_rows_per_s", "setup_s"}
    else:
        assert {"chunk_wall_ms", "pad_waste_pct",
                "job_rows_per_s_median"} <= set(line["metrics"])
