"""BENCHMARK.json and the data files it names: one loader, one checker.

Everything that belongs to one configuration, one traffic mix, one layer
metric or one cell is a file the harness finds by the name in
BENCHMARK.json, so a later PR adds cells by adding files:

    configs/<config>.json            sizes, source, reduced, assumed; for a
                                     cell that trains: template,
                                     algorithm_params, events, check
    events/<events>.py               the configuration's events from --seed
    checks/<check>.py                its output check, with its reference
    traffic/<traffic>.json           the mix's parameters
    traffic/<cell name>.json         optional per-cell numbers (the frozen
                                     offered rate), laid over the mix
    layer_metrics/<metric>.json      how one per-layer metric is read
"""

from __future__ import annotations

import json
import os
import re
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
#: the cell kinds child.py drives (a traffic file's `kind`)
KINDS = ("train", "batchpredict")
#: what a configuration of a `train` cell names, and the directory of each
TRAIN_PARTS = {"events": "events", "check": "checks"}


class ManifestError(ValueError):
    pass


def _read_json(path: str) -> Any:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise ManifestError(f"cannot read {path}: {e}") from e


def load_benchmark(root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def bench_dir(root: str = ROOT) -> str:
    return os.path.join(root, os.path.basename(BENCH_DIR))


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise ManifestError(
        f"no workload {name!r} in BENCHMARK.json; known: "
        + ", ".join(c["name"] for c in bench["workloads"]))


def load_config(bench: dict, name: str, root: str = ROOT) -> dict:
    for cfg in bench["configs"]:
        if cfg["name"] == name:
            doc = _read_json(os.path.join(root, cfg["file"]))
            doc.setdefault("name", name)
            return doc
    raise ManifestError(f"no configuration {name!r} in BENCHMARK.json")


def load_traffic(cell: dict, root: str = ROOT) -> dict:
    """The mix's parameters with the cell's own file laid over them."""
    tdir = os.path.join(bench_dir(root), "traffic")
    mix = _read_json(os.path.join(tdir, f"{cell['traffic']}.json"))
    own = os.path.join(tdir, f"{cell['name']}.json")
    if os.path.exists(own):
        mix = {**mix, **_read_json(own)}
    return mix


def train_parts(config: dict, root: str = ROOT) -> List[str]:
    """What a configuration lacks of what a `train` cell needs of it: a
    template, algorithm parameters, and an event generator and an output
    check that are files. An empty list means nothing."""
    who = f"config {config.get('name')}"
    bad = []
    if not isinstance(config.get("template"), str):
        bad.append(f"{who} names no template")
    if not isinstance(config.get("algorithm_params"), dict):
        bad.append(f"{who} has no algorithm_params object")
    for key, folder in TRAIN_PARTS.items():
        part = config.get(key)
        if not isinstance(part, str) or not part.isidentifier():
            bad.append(f"{who} names no {key} (a module name under "
                       f"{folder}/): {part!r}")
        elif not os.path.exists(os.path.join(bench_dir(root), folder,
                                             part + ".py")):
            bad.append(f"{who}: no file {folder}/{part}.py for its {key}")
    return bad


def metrics_of_cell(bench: dict, cell_name: str, group: str) -> List[dict]:
    """The metrics of `group` (end_to_end | per_layer) this cell reports."""
    out = []
    for m in bench[group]:
        cells = m.get("workloads")
        if cells is None or cell_name in cells:
            out.append(m)
    return out


def load_layer_reader(metric_name: str, root: str = ROOT) -> dict:
    return _read_json(os.path.join(
        bench_dir(root), "layer_metrics", f"{metric_name}.json"))


def check(bench: dict, root: str = ROOT) -> List[str]:
    """Every breach of the rules the driver holds BENCHMARK.json to that
    can be seen without a run. An empty list means none was found."""
    bad: List[str] = []

    def name_ok(kind: str, name: Any) -> None:
        if not isinstance(name, str) or not NAME_RE.match(name):
            bad.append(f"{kind} name {name!r} uses characters the driver "
                       "refuses")

    def line_ok(kind: str, text: Any) -> None:
        if not isinstance(text, str) or not 1 <= len(text) <= 200 \
                or "\n" in text or "\t" in text:
            bad.append(f"{kind} {text!r} is not one line of 1-200 characters")

    want_keys = {"command", "paths", "run_seconds", "configs", "workloads",
                 "end_to_end", "per_layer"}
    if set(bench) != want_keys:
        bad.append(f"top-level keys {sorted(bench)} != {sorted(want_keys)}")
        return bad
    if not isinstance(bench["run_seconds"], int) \
            or not 1 <= bench["run_seconds"] <= 51:
        bad.append("run_seconds must be a whole number from 1 to 51")
    paths = bench["paths"]
    for word in bench["command"]:
        line_ok("command word", word)
    config_names, files = set(), set()
    for cfg in bench["configs"]:
        if set(cfg) != {"name", "source", "file", "reduced", "why"}:
            bad.append(f"config {cfg.get('name')}: keys {sorted(cfg)}")
            continue
        name_ok("config", cfg["name"])
        line_ok("config source", cfg["source"])
        line_ok("config why", cfg["why"])
        for key in cfg["reduced"]:
            name_ok("reduced key", key)
        if len(cfg["reduced"]) > 16:
            bad.append(f"config {cfg['name']}: more than 16 reduced keys")
        if not any(cfg["file"].startswith(p + "/") for p in paths):
            bad.append(f"config file {cfg['file']} is not under paths")
        if cfg["file"] in files or cfg["name"] in config_names:
            bad.append(f"config {cfg['name']} or its file appears twice")
        files.add(cfg["file"])
        config_names.add(cfg["name"])
        if not os.path.exists(os.path.join(root, cfg["file"])):
            bad.append(f"config file {cfg['file']} does not exist")
    cell_names, pairs, used = set(), set(), set()
    for cell in bench["workloads"]:
        if set(cell) != {"name", "config", "traffic", "chips", "why"}:
            bad.append(f"workload {cell.get('name')}: keys {sorted(cell)}")
            continue
        name_ok("workload", cell["name"])
        name_ok("traffic", cell["traffic"])
        line_ok("workload why", cell["why"])
        if cell["chips"] not in (1, 4):
            bad.append(f"workload {cell['name']}: chips must be 1 or 4")
        if cell["config"] not in config_names:
            bad.append(f"workload {cell['name']}: unknown config")
        pair = (cell["config"], cell["traffic"])
        if pair in pairs or cell["name"] in cell_names:
            bad.append(f"workload {cell['name']} or its pair appears twice")
        pairs.add(pair)
        cell_names.add(cell["name"])
        used.add(cell["config"])
        tfile = os.path.join(bench_dir(root), "traffic",
                             f"{cell['traffic']}.json")
        if not os.path.exists(tfile):
            bad.append(f"workload {cell['name']}: no traffic file {tfile}")
            continue
        try:
            kind = load_traffic(cell, root).get("kind")
            if kind not in KINDS:
                bad.append(f"workload {cell['name']}: traffic kind {kind!r} "
                           f"is none of {KINDS}")
            elif kind == "train" and cell["config"] in config_names:
                bad.extend(train_parts(
                    load_config(bench, cell["config"], root), root))
        except ManifestError as e:     # a file named above that is not there
            bad.append(str(e))
    for unused in config_names - used:
        bad.append(f"config {unused} is used by no cell")
    four = sum(1 for c in bench["workloads"] if c.get("chips") == 4)
    if four > max(1, len(bench["workloads"]) // 4):
        bad.append("more than a quarter of the cells ask for four chips")

    metric_names = set()
    e2e: Dict[str, Optional[List[str]]] = {}
    for m in bench["end_to_end"]:
        allowed = {"name", "unit", "better", "bound", "source", "workloads"}
        if not set(m) <= allowed or not {"name", "unit", "better", "bound",
                                         "source"} <= set(m):
            bad.append(f"end_to_end {m.get('name')}: keys {sorted(m)}")
            continue
        name_ok("metric", m["name"])
        if not UNIT_RE.match(str(m["unit"])):
            bad.append(f"metric {m['name']}: unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            bad.append(f"metric {m['name']}: better {m['better']!r}")
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"metric {m['name']}: an end-to-end source is "
                       "host_clock or device_trace")
        if not 0.01 <= m["bound"] <= 0.1:
            bad.append(f"metric {m['name']}: bound {m['bound']} outside "
                       "1%..10%")
        if m["name"] in metric_names:
            bad.append(f"metric {m['name']} appears twice")
        metric_names.add(m["name"])
        e2e[m["name"]] = m.get("workloads")
        for c in m.get("workloads") or []:
            if c not in cell_names:
                bad.append(f"metric {m['name']}: unknown workload {c}")
    if "setup_s" not in e2e:
        bad.append("end_to_end lacks setup_s")
    elif e2e["setup_s"] is not None:
        bad.append("setup_s must be reported by every cell")

    def reports(cell: str, metric: str) -> bool:
        cells = e2e.get(metric, [])
        return metric in e2e and (cells is None or cell in cells)

    for cell in cell_names:
        if not any(reports(cell, m) for m in e2e if m != "setup_s"):
            bad.append(f"workload {cell} reports no end-to-end metric "
                       "besides setup_s")
    layered = set()
    for m in bench["per_layer"]:
        allowed = {"name", "unit", "better", "source", "layer", "moves",
                   "workloads"}
        if not set(m) <= allowed or not allowed - {"workloads"} <= set(m):
            bad.append(f"per_layer {m.get('name')}: keys {sorted(m)}")
            continue
        name_ok("metric", m["name"])
        line_ok("layer", m["layer"])
        if not UNIT_RE.match(str(m["unit"])):
            bad.append(f"metric {m['name']}: unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            bad.append(f"metric {m['name']}: better {m['better']!r}")
        if m["source"] not in SOURCES:
            bad.append(f"metric {m['name']}: source {m['source']!r}")
        if m["name"] in metric_names:
            bad.append(f"metric {m['name']} appears twice")
        metric_names.add(m["name"])
        if m["moves"] not in e2e:
            bad.append(f"metric {m['name']}: moves {m['moves']!r}, which "
                       "is no end-to-end metric")
            continue
        cells = m.get("workloads")
        if cells is None:
            cells = [c for c in cell_names if reports(c, m["moves"])]
        for c in cells:
            if c not in cell_names:
                bad.append(f"metric {m['name']}: unknown workload {c}")
            elif not reports(c, m["moves"]):
                bad.append(f"metric {m['name']}: workload {c} does not "
                           f"report {m['moves']}")
            layered.add(c)
        rfile = os.path.join(bench_dir(root), "layer_metrics",
                             f"{m['name']}.json")
        if not os.path.exists(rfile):
            bad.append(f"metric {m['name']}: no reader file {rfile}")
        else:
            reader = _read_json(rfile)
            for key in ("layer", "moves", "unit"):
                if reader.get(key) != m[key]:
                    bad.append(f"metric {m['name']}: {key} differs between "
                               "BENCHMARK.json and its reader file")
    for cell in cell_names - layered:
        bad.append(f"workload {cell} reports no per-layer metric")
    if len(json.dumps(bench)) > 64 * 1024:
        bad.append("BENCHMARK.json is over 64 KiB")
    return bad
