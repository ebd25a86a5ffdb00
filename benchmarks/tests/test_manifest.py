"""BENCHMARK.json against the driver's rules, and the harness as data:
a configuration, a traffic mix, a layer metric and a cell are added as
files only, in a temporary copy, and the harness finds each."""
import argparse
import json
import os
import shutil

import pytest

from benchmarks import run
from benchmarks.lib import layer_readers, manifest

ROOT = manifest.ROOT


def test_the_repo_manifest_keeps_the_rules():
    bench = manifest.load_benchmark()
    assert manifest.check(bench) == []
    for cell in bench["workloads"]:
        assert cell["chips"] == 1
    # every layer metric's `moves` is reported by each of its cells
    e2e = {m["name"]: m.get("workloads") for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        for cell in m["workloads"]:
            assert e2e[m["moves"]] is None or cell in e2e[m["moves"]]


@pytest.mark.parametrize("field,value", [
    ("name", "has space"), ("name", "sl/ash"), ("name", "x" * 65),
    ("unit", "tokens per second"), ("unit", "µs"), ("better", "faster"),
    ("moves", "no_such_metric"), ("source", "guess")])
def test_check_refuses_what_the_driver_refuses(field, value):
    bench = manifest.load_benchmark()
    bench["per_layer"][0][field] = value
    assert manifest.check(bench)


def test_check_refuses_a_metric_its_cell_does_not_report():
    bench = manifest.load_benchmark()
    m = next(m for m in bench["per_layer"] if m["moves"] == "train_wall_s")
    bench["workloads"].append({
        "name": "other.cell", "config": bench["configs"][0]["name"],
        "traffic": "batchpredict-file", "chips": 1, "why": "reports no train"})
    m["workloads"] = ["other.cell"]
    assert any("does not report" in b for b in manifest.check(bench))


@pytest.fixture
def copy(tmp_path):
    """BENCHMARK.json and the benchmark's directory, copied."""
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return str(root)


DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def add_sessionrec_cell(copy: str, parts=("configs/seq-tiny.json",
                                          "events/sessions.py",
                                          "checks/seqrec_loss.py")) -> dict:
    """README.md's recipe "a training configuration of another template",
    carried out with the test data under tests/data/sessionrec: new files
    under configs/, events/ and checks/, a cell over the mix that is
    there, and the cell appended to the metrics it reports. Nothing that
    was there is edited."""
    for part in parts:
        shutil.copy(os.path.join(DATA, "sessionrec", os.path.basename(part)),
                    os.path.join(copy, "benchmarks", part))
    path = os.path.join(copy, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "seq-tiny",
        "source": "https://example.org/test-data-not-a-supported-model",
        "file": "benchmarks/configs/seq-tiny.json", "reduced": [],
        "why": "test data: a sessionrec train at a size the CPU holds"})
    bench["workloads"].append({
        "name": "seq-tiny.train", "config": "seq-tiny",
        "traffic": "train-backtoback", "chips": 1,
        "why": "test data: whole sessionrec trains back to back"})
    for group, name in (("end_to_end", "train_wall_s"),
                        ("per_layer", "train_wall_median_s"),
                        ("per_layer", "compiles_in_window.train")):
        next(m for m in bench[group] if m["name"] == name)[
            "workloads"].append("seq-tiny.train")
    with open(path, "w") as f:
        json.dump(bench, f)
    return bench


def test_additions_are_files_only(copy):
    """README.md's recipes, carried out."""
    bdir = os.path.join(copy, "benchmarks")
    # a training configuration of another template: three files, entries
    bench = add_sessionrec_cell(copy)
    assert manifest.check(bench, copy) == []
    args = argparse.Namespace(tiny=True, seed=3, seconds=1.0, trace=0)
    spec = run.build_spec(bench, manifest.find_cell(bench, "seq-tiny.train"),
                          args, "/nonexistent", copy)
    assert (spec["config"]["template"], spec["config"]["events"],
            spec["config"]["check"]) == ("sessionrec", "sessions", "seqrec_loss")
    assert spec["config"]["algorithm_params"]["d_model"] == 32
    # a configuration: a file of sizes
    with open(os.path.join(bdir, "configs", "rec-ml20m-r64.json")) as f:
        cfg = json.load(f)
    cfg.update(name="rec-new-r32",
               algorithm_params={"rank": 32, "num_iterations": 20})
    with open(os.path.join(bdir, "configs", "rec-new-r32.json"), "w") as f:
        json.dump(cfg, f)
    bench["configs"].append({
        "name": "rec-new-r32", "source": "https://example.org/dataset",
        "file": "benchmarks/configs/rec-new-r32.json", "reduced": ["n_events"],
        "why": "the same template at rank 32"})
    # a traffic mix: a file of parameters the general generator reads
    with open(os.path.join(bdir, "traffic", "train-cold.json"), "w") as f:
        json.dump({"kind": "train", "warm_jobs": 0}, f)
    # a cell: an entry of `workloads`, with its own numbers laid over the mix
    bench["workloads"].append({
        "name": "new-r32.train-cold", "config": "rec-new-r32",
        "traffic": "train-cold", "chips": 1, "why": "trains with no warm job"})
    with open(os.path.join(bdir, "traffic", "new-r32.train-cold.json"), "w") as f:
        json.dump({"warm_jobs": 2}, f)
    for m in bench["end_to_end"]:
        if m["name"] == "train_wall_s":
            m["workloads"].append("new-r32.train-cold")
    # a layer metric: a file naming what it reads and a reduction of the menu
    reader = {"name": "ingest_intern_s", "layer": "event store + ingest",
              "moves": "train_wall_s", "unit": "s", "better": "lower",
              "source": "program_span",
              "reader": {"kind": "span_median_per_job", "span": "ingest_intern"}}
    with open(os.path.join(bdir, "layer_metrics", "ingest_intern_s.json"), "w") as f:
        json.dump(reader, f)
    bench["per_layer"].append({
        "name": "ingest_intern_s", "unit": "s", "better": "lower",
        "source": "program_span", "layer": "event store + ingest",
        "moves": "train_wall_s", "workloads": ["new-r32.train-cold"]})

    assert manifest.check(bench, copy) == []
    cell = manifest.find_cell(bench, "new-r32.train-cold")
    args = argparse.Namespace(tiny=True, seed=3, seconds=1.0, trace=0)
    spec = run.build_spec(bench, cell, args, "/nonexistent", copy)
    # the tiny section applies
    assert spec["config"]["algorithm_params"]["rank"] == 8
    assert spec["traffic"] == {"kind": "train", "warm_jobs": 2}
    args.tiny = False
    spec = run.build_spec(bench, cell, args, "/nonexistent", copy)
    assert spec["config"]["algorithm_params"]["rank"] == 32
    assert spec["config"]["n_users"] == 138_493
    # the new metric is read from evidence by its file alone
    evidence = {"kind": "train", "jobs": [
        {"wall_s": 1.0, "spans": {"ingest_intern": 0.25}},
        {"wall_s": 1.2, "spans": {"ingest_intern": 0.35}},
        {"wall_s": 1.1, "spans": {"ingest_intern": 0.30}}]}
    found = manifest.load_layer_reader("ingest_intern_s", copy)
    assert layer_readers.read(evidence, found) == pytest.approx(0.30)
    names = [m["name"] for m in manifest.metrics_of_cell(
        bench, "new-r32.train-cold", "per_layer")]
    assert names == ["ingest_intern_s"]


def test_a_reader_with_nothing_to_read_returns_nothing():
    empty = {"jobs": [], "registry_before": {}, "registry_after": {},
             "trace": None, "memory": {}}
    bench = manifest.load_benchmark()
    for m in bench["per_layer"]:
        reader = manifest.load_layer_reader(m["name"])
        value = layer_readers.read(empty, reader)
        assert value is None or (reader["reader"].get("absent_is_zero")
                                 and value == 0.0)


def test_a_reduction_outside_the_menu_is_an_error():
    with pytest.raises(ValueError):
        layer_readers.read({}, {"name": "x", "reader": {"kind": "magic"}})


def test_layer_readers_on_hand_made_evidence():
    ev = {"jobs": [{"wall_s": 10.0, "rows": 100, "pad_waste": 28},
                   {"wall_s": 10.4, "rows": 100, "pad_waste": 0},
                   {"wall_s": 10.2, "rows": 100, "pad_waste": 0}],
          "registry_before": {"h": [[{"stage": "a"}, {"sum": 1.0, "count": 2}]],
                              "c": [[{"family": "x"}, 3.0]]},
          "registry_after": {"h": [[{"stage": "a"}, {"sum": 4.0, "count": 8}],
                                   [{"stage": "b"}, {"sum": 9.0, "count": 1}]],
                             "c": [[{"family": "x"}, 5.0], [{"family": "y"}, 1.0]]},
          "memory": {"peak_bytes_in_use": 123}}
    rd = lambda r: layer_readers.read(ev, {"reader": r})  # noqa: E731
    assert rd({"kind": "histogram_mean", "metric": "h", "labels": {"stage": "a"},
               "scale": 1000.0}) == pytest.approx(500.0)
    assert rd({"kind": "counter_delta", "metric": "c"}) == pytest.approx(3.0)
    assert rd({"kind": "counter_delta", "metric": "nope"}) is None
    assert rd({"kind": "job_ratio", "numerator": "pad_waste",
               "denominator": ["rows", "pad_waste"], "scale": 100.0}
              ) == pytest.approx(100 * 28 / 328)
    assert rd({"kind": "job_range_pct", "field": "wall_s"}) == pytest.approx(
        100 * 0.4 / 10.2)
    assert rd({"kind": "evidence_value", "path": ["memory", "peak_bytes_in_use"]}) == 123


def add_batchpredict_cell(copy: str) -> dict:
    """The batchpredict cell PERF.md keeps for later (proven on the chip
    in PR 23, under the driver's memory floor), added as entries only:
    its configuration, mix, layer-metric files and the harness's
    `batchpredict` kind are already here."""
    cell = "msd-r128.batchpredict"
    path = os.path.join(copy, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "rec-msd-r128",
        "source": "http://millionsongdataset.com/tasteprofile/",
        "file": "benchmarks/configs/rec-msd-r128.json", "reduced": ["train"],
        "why": "ALS factors at Taste Profile widths, rank 128"})
    bench["workloads"].append({
        "name": cell, "config": "rec-msd-r128", "traffic": "batchpredict-file",
        "chips": 1, "why": "jobs of 131,072 JSONL queries in chunks of 1024"})
    bench["end_to_end"].append({
        "name": "batchpredict_rows_per_s", "unit": "rows/s",
        "better": "higher", "bound": 0.05, "source": "host_clock",
        "workloads": [cell]})
    ldir = os.path.join(copy, "benchmarks", "layer_metrics")
    for fn in sorted(os.listdir(ldir)):
        with open(os.path.join(ldir, fn)) as f:
            r = json.load(f)
        if r["moves"] == "batchpredict_rows_per_s":
            bench["per_layer"].append({
                "name": r["name"], "unit": r["unit"], "better": r["better"],
                "source": r["source"], "layer": r["layer"],
                "moves": r["moves"], "workloads": [cell]})
    with open(path, "w") as f:
        json.dump(bench, f)
    return bench


def test_the_batchpredict_cell_is_entries_only(copy):
    bench = add_batchpredict_cell(copy)
    assert manifest.check(bench, copy) == []
    names = {m["name"] for m in manifest.metrics_of_cell(
        bench, "msd-r128.batchpredict", "per_layer")}
    assert {"chunk_wall_ms", "pad_waste_pct", "job_rows_per_s_median",
            "topk_kernel_ms.batchpredict", "topk_batchpredict_roofline",
            "device_idle_pct.batchpredict"} <= names


def test_a_count_function_and_a_reduction_are_files(copy, monkeypatch):
    """A new kernel's roofline names its count function, a new reduction
    its module: both are found by name, no file that is there is edited."""
    import importlib
    import sys

    bdir = os.path.join(copy, "benchmarks")
    with open(os.path.join(bdir, "counts", "copy_kernel.py"), "w") as f:
        f.write("def counts(evidence, reader, n_events):\n"
                "    return 0.0, n_events * 819e9\n")
    with open(os.path.join(bdir, "readers", "job_count.py"), "w") as f:
        f.write("def read(evidence, reader):\n"
                "    return float(len(evidence.get('jobs', [])))\n")
    for mod in [m for m in sys.modules if m.split(".")[0] == "benchmarks"]:
        monkeypatch.delitem(sys.modules, mod)
    monkeypatch.syspath_prepend(copy)
    readers = importlib.import_module("benchmarks.lib.layer_readers")
    ev = {"jobs": [{}, {}], "device": {"kind": "TPU v5 lite"}, "shapes": {},
          "trace": {"ops": [["copy_kernel.1", 4, 8.0]], "modules": []}}
    assert readers.read(ev, {"reader": {"kind": "job_count"}}) == 2.0
    assert readers.read(ev, {"reader": {
        "kind": "trace_roofline", "pattern": "copy_kernel",
        "counts": "copy_kernel"}}) == pytest.approx(50.0)


@pytest.mark.parametrize("lack", ["events", "check", "algorithm_params",
                                  "template"])
def test_a_train_configuration_must_bring_its_parts(copy, lack):
    """A `train` cell's configuration that names no generator or check
    (or names one with no file) is refused before any run, by check()
    and by run.py's build_spec alike."""
    bench = add_sessionrec_cell(copy)
    cell = manifest.find_cell(bench, "seq-tiny.train")
    args = argparse.Namespace(tiny=True, seed=3, seconds=1.0, trace=0)
    path = os.path.join(copy, "benchmarks", "configs", "seq-tiny.json")
    with open(path) as f:
        cfg = json.load(f)
    if lack in manifest.TRAIN_PARTS:
        # the name without its file
        os.remove(os.path.join(copy, "benchmarks", manifest.TRAIN_PARTS[lack],
                               cfg[lack] + ".py"))
        assert any(f"for its {lack}" in b for b in manifest.check(bench, copy))
        with pytest.raises(manifest.ManifestError, match="no file"):
            run.build_spec(bench, cell, args, "/nonexistent", copy)
    del cfg[lack]
    with open(path, "w") as f:
        json.dump(cfg, f)
    assert any("seq-tiny" in b and lack in b
               for b in manifest.check(bench, copy))
    with pytest.raises(manifest.ManifestError, match=lack):
        run.build_spec(bench, cell, args, "/nonexistent", copy)


def test_a_mix_of_an_unknown_kind_is_refused(copy):
    from benchmarks import child

    assert set(child.KINDS) == set(manifest.KINDS)
    with open(os.path.join(copy, "benchmarks", "traffic", "serve.json"), "w") as f:
        json.dump({"kind": "serve"}, f)
    bench = manifest.load_benchmark(copy)
    bench["workloads"].append({
        "name": "x.serve", "config": bench["configs"][0]["name"],
        "traffic": "serve", "chips": 1, "why": "a kind the harness has not"})
    assert any("kind 'serve'" in b for b in manifest.check(bench, copy))
    with pytest.raises(manifest.ManifestError, match="kind 'serve'"):
        run.build_spec(bench, bench["workloads"][-1], argparse.Namespace(
            tiny=True, seed=1, seconds=1.0, trace=0), "/nonexistent", copy)
