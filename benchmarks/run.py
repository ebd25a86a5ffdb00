#!/usr/bin/env python3
"""One cell, one run, one last line.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

This process never imports JAX: the chip belongs to the one child it
starts (child.py), which claims it with JAX_PLATFORMS=tpu, so that
without a chip there is no result rather than a CPU number under a
device metric's name. `--tiny` is the CPU rehearsal: toy sizes from the
configuration file's "tiny" section, `device` printed as cpu, no metric
that comes from a device trace.

The last line of stdout is the result: correct, attempted, failed,
metrics (the cell's end_to_end metrics with --trace 0, its per_layer
metrics with --trace 1), device and, last, `checks`: each number the
output check compared, with its limit. Earlier lines carry each job's
wall and each compared number on a `CHECK` line, which are also the last
lines on standard error.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import queue
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional

T0 = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import layer_readers, manifest  # noqa: E402


def say(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - T0:7.2f}s] {msg}",
          file=sys.stderr, flush=True)


class RunFailed(Exception):
    pass


# ---------------------------------------------------------------------------
# the child
# ---------------------------------------------------------------------------

def child_env(platform: str, work: str) -> dict:
    """The environment of the child: the checkout on the path, the
    platform pinned so that JAX raises instead of choosing another, and
    storage configured the way a user would (conf/pio-env.sh.template
    scheme; the sqlite event store and local-fs model store of the
    chip smoke), all under the run's work directory."""
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env["JAX_PLATFORMS"] = platform
    # the same hash order in every run: the seed, not the process,
    # decides the work
    env["PYTHONHASHSEED"] = "0"
    storage = os.path.join(work, "storage")
    env.update({
        "PIO_HOME": os.path.join(storage, "home"),
        "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "pio_meta",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "DB",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "pio_event",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "DB",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_NAME": "pio_model",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "FS",
        "PIO_STORAGE_SOURCES_DB_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_DB_PATH": os.path.join(storage, "pio.db"),
        "PIO_STORAGE_SOURCES_FS_TYPE": "localfs",
        "PIO_STORAGE_SOURCES_FS_PATH": os.path.join(storage, "models"),
    })
    return env


def child_argv(spec: dict) -> List[str]:
    return [sys.executable, os.path.join(HERE, "child.py"), json.dumps(spec)]


class Child:
    """The JAX child and its protocol lines."""

    def __init__(self, spec: dict, platform: str):
        os.makedirs(os.path.join(spec["work"], "storage"), exist_ok=True)
        self.proc = subprocess.Popen(
            child_argv(spec), env=child_env(platform, spec["work"]),
            cwd=ROOT, stdin=subprocess.DEVNULL, stdout=subprocess.PIPE,
            text=True, start_new_session=True)
        self.events: "queue.Queue[Optional[dict]]" = queue.Queue()
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self) -> None:
        for line in self.proc.stdout:
            if line.startswith("BENCH "):
                self.events.put(json.loads(line[6:]))
        self.events.put(None)

    def wait_for(self, kind: str, timeout: float, on_other=None) -> dict:
        deadline = time.monotonic() + timeout
        while True:
            left = deadline - time.monotonic()
            if left <= 0:
                raise RunFailed(f"no {kind!r} from the child in {timeout:g}s")
            try:
                ev = self.events.get(timeout=left)
            except queue.Empty:
                continue
            if ev is None:
                raise RunFailed(
                    f"the child ended (exit code {self.proc.wait()}) "
                    f"before {kind!r}")
            if ev["event"] == kind:
                return ev
            if on_other:
                on_other(ev)

    def close(self, timeout: float = 60.0) -> int:
        """Wait for the child to end; kill it and its group if it does
        not."""
        try:
            return self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            self.kill()
            return -9

    def kill(self) -> None:
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                self.proc.kill()
        self.proc.wait(timeout=30)


# ---------------------------------------------------------------------------
# the window, parent side
# ---------------------------------------------------------------------------

def print_job(ev: dict) -> None:
    if ev["event"] == "job":
        print(f"JOB {ev['index']} wall_s={ev['wall_s']:.6f}"
              + (" traced" if ev.get("traced") else ""), flush=True)


def run_jobs_cell(child: Child, spec: dict) -> dict:
    """train and batchpredict: the child runs whole jobs back to back."""
    child.wait_for("ready", 1150)
    setup_s = time.perf_counter() - T0
    say(f"set-up done in {setup_s:.2f} s; window of {spec['seconds']} s")
    # the window, the job in flight at its end, then the check: for a
    # train one more train, which compiles in a checkout's first run
    done = child.wait_for("done", spec["seconds"] + 600, on_other=print_job)
    with open(done["evidence"]) as f:
        evidence = json.load(f)
    evidence["setup_s"] = setup_s
    return evidence


# ---------------------------------------------------------------------------
# from evidence to the result line
# ---------------------------------------------------------------------------

def end_to_end(evidence: dict, kind: str) -> Dict[str, float]:
    """All the work over all the time of the window: from its opening to
    the end of the job in flight when it closed, bookkeeping between
    jobs included. A stalled job counts as what it took."""
    out = {"setup_s": evidence["setup_s"]}
    jobs = evidence.get("jobs", [])
    if kind == "train" and jobs:
        out["train_wall_s"] = evidence["window_s"] / len(jobs)
    if kind == "batchpredict" and jobs:
        out["batchpredict_rows_per_s"] = \
            sum(j["rows"] for j in jobs) / evidence["window_s"]
    return out


def check_lines(rows) -> List[str]:
    return [f"CHECK {name} value={value:.6g} limit={limit:.6g} "
            f"{'ok' if ok else 'NOT OK'}" for name, value, limit, ok in rows]


def result_line(bench: dict, cell: dict, evidence: dict, trace: bool,
                tiny: bool, root: str = ROOT) -> dict:
    kind = evidence["kind"]
    rows = evidence.get("correct_rows") or []
    for text in check_lines(rows):
        print(text, flush=True)
    correct = bool(rows) and all(r[3] for r in rows) \
        and not evidence.get("failed")
    metrics: Dict[str, dict] = {}
    if not trace:
        values = end_to_end(evidence, kind)
        for m in manifest.metrics_of_cell(bench, cell["name"], "end_to_end"):
            if values.get(m["name"]) is None:
                raise RunFailed(f"the run has no {m['name']}")
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        for m in manifest.metrics_of_cell(bench, cell["name"], "per_layer"):
            if tiny and m["source"] == "device_trace":
                continue      # no CPU number under a device metric's name
            reader = manifest.load_layer_reader(m["name"], root)
            value = layer_readers.read(evidence, reader)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = evidence["device"]
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"],
              "memory_peak_bytes": evidence["memory"].get(
                  "memory_peak_bytes", 0)}
    line = {"correct": correct, "attempted": evidence["attempted"],
            "failed": evidence["failed"], "metrics": metrics,
            "device": device}
    t = evidence.get("trace")
    if trace and t:
        device["busy_s"] = t["busy_s"]
        device["window_s"] = t["window_s"]
        line["breakdown"] = {"device_ops": t["device_ops_top"],
                             "idle_gaps": t["idle_gaps_top"]}
    # a value that is not finite has no JSON number: null (its CHECK
    # line says which it was)
    line["checks"] = {
        name: {"value": value if math.isfinite(value) else None,
               "limit": limit, "ok": ok}
        for name, value, limit, ok in rows}
    return line


def build_spec(bench: dict, cell: dict, args, work: str,
               root: str = ROOT) -> dict:
    config = manifest.load_config(bench, cell["config"], root)
    traffic = manifest.load_traffic(cell, root)
    if args.tiny:
        config = {**config, **config.get("tiny", {})}
        traffic = {**traffic, **traffic.get("tiny", {})}
    config.pop("tiny", None)
    traffic.pop("tiny", None)
    if traffic.get("kind") not in manifest.KINDS:
        raise manifest.ManifestError(
            f"traffic {cell['traffic']}: kind {traffic.get('kind')!r} is "
            f"none of {manifest.KINDS}")
    missing = manifest.train_parts(config, root) \
        if traffic["kind"] == "train" else []
    if missing:
        raise manifest.ManifestError("; ".join(missing))
    return {"workload": cell["name"], "config": config, "traffic": traffic,
            "seed": args.seed, "seconds": args.seconds,
            "trace": bool(args.trace) and not args.tiny,
            "tiny": bool(args.tiny), "chips": cell["chips"], "work": work}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal at toy sizes")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "predictionio_tpu")):
        print(f"benchmarks/run.py: no predictionio_tpu package beside "
              f"{HERE}; run it from a checkout", file=sys.stderr)
        return 2
    work = tempfile.mkdtemp(prefix="pio-bench-")
    child = None
    try:
        bench = manifest.load_benchmark()
        cell = manifest.find_cell(bench, args.workload)
        if args.seconds is None:
            args.seconds = float(bench["run_seconds"])
        spec = build_spec(bench, cell, args, work)
        kind = spec["traffic"]["kind"]
        child = Child(spec, "cpu" if args.tiny else "tpu")
        evidence = run_jobs_cell(child, spec)
        evidence["kind"] = kind
        rc = child.close()
        if rc != 0:
            raise RunFailed(f"the child exited with code {rc}")
        line = result_line(bench, cell, evidence, bool(args.trace),
                           args.tiny)
    except (RunFailed, manifest.ManifestError) as e:
        say(f"FAILED: {e}")
        return 1
    finally:
        if child is not None:
            child.kill()
        shutil.rmtree(work, ignore_errors=True)
    # the compared numbers once more, as the last lines of standard error
    for text in check_lines(evidence.get("correct_rows") or []):
        print(text, file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
