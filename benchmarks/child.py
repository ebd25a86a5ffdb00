#!/usr/bin/env python3
"""The one process of a run that imports JAX and holds the chip.

Started by run.py with a JSON spec on the command line. It drives the
program's normal entry points -- `workflow.train.run_train`,
`workflow.batch_predict.run_batch_predict` -- and talks to the parent in
lines on stdout that start with "BENCH ". Everything it learns (job
walls, metric deltas, memory, the reduced trace, the rows of the output
check) goes back as one "evidence" document; the parent turns that into
the result line.

The `train` kind knows no template, algorithm or model. The window, its
clock and what a job is are here, for every training configuration
alike; the configuration's file names what differs and the harness finds
each by name: its events (events/<name>.py), its algorithm parameters,
its output check with its reference (checks/<name>.py).
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import json
import os
import pickle
import shutil
import subprocess
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

APP = "bench"


def say(kind: str, **doc) -> None:
    """One protocol line to the parent."""
    sys.stdout.write("BENCH " + json.dumps({"event": kind, **doc}) + "\n")
    sys.stdout.flush()


def log(msg: str) -> None:
    print(f"[child +{time.time() - T0:7.2f}s] {msg}", file=sys.stderr,
          flush=True)


# ---------------------------------------------------------------------------
# the device, as JAX reports it
# ---------------------------------------------------------------------------

def claim_device(spec: dict) -> dict:
    """Import JAX on the platform the parent pinned; fail when it is not
    the one the cell needs (no chip, no result)."""
    from predictionio_tpu.utils.device import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "compile_cache_dir": cache_dir}
    want = "cpu" if spec["tiny"] else "tpu"
    if info["platform"] != want:
        raise SystemExit(f"JAX reports platform {info['platform']!r}, the "
                         f"run needs {want!r}")
    if not spec["tiny"] and info["count"] < spec["chips"]:
        raise SystemExit(f"the cell needs {spec['chips']} chip(s), JAX "
                         f"finds {info['count']}")
    return info


def memory_peaks() -> dict:
    """Allocator peaks of the fullest chip. This TPU runtime keeps two
    disjoint accounts: `peak_bytes_in_use` is live arrays only (what the
    deployment holds between programs), `peak_bytes_reserved` the HBM the
    loaded programs reserve for their temporaries, which on backends
    with one allocator is counted inside `peak_bytes_in_use`. The chip
    held at least the larger of the two at once and at most their sum;
    the result line's `memory_peak_bytes` is the larger, a floor of the
    true peak. Both accounts are per-layer metrics of their own."""
    import jax

    best: Dict[str, int] = {}
    for d in jax.local_devices():
        stats = d.memory_stats()
        if not stats:
            continue              # the CPU backend reports none
        row = {"peak_bytes_in_use": int(stats.get("peak_bytes_in_use", 0)),
               "peak_bytes_reserved": int(stats.get("peak_bytes_reserved",
                                                    0))}
        row["memory_peak_bytes"] = max(row.values())
        if row["memory_peak_bytes"] >= best.get("memory_peak_bytes", -1):
            best = row
    return best


# ---------------------------------------------------------------------------
# the program's counters and spans
# ---------------------------------------------------------------------------

def registry_snapshot(registries=None) -> dict:
    """{metric: [[labels, value-or-{sum,count}]]} of the program's
    registries, for deltas over the window."""
    from predictionio_tpu.obs.registry import (
        Counter, Gauge, Histogram, default_registry,
    )

    out: Dict[str, list] = {}
    for reg in registries or [default_registry()]:
        for m in reg.collect():
            rows = out.setdefault(m.name, [])
            if isinstance(m, Histogram):
                for labels, _ in m.samples():
                    rows.append([labels, {"sum": m.sum_(**labels),
                                          "count": m.count(**labels)}])
            elif isinstance(m, (Counter, Gauge)):
                for labels, value in m.samples():
                    rows.append([labels, value])
    return out


def span_totals() -> Dict[str, List[float]]:
    """{span: [seconds, count]} from pio_span_duration_seconds."""
    snap = registry_snapshot().get("pio_span_duration_seconds", [])
    return {labels.get("span", "?"): [v["sum"], v["count"]]
            for labels, v in snap}


def spans_between(s0: dict, s1: dict) -> Dict[str, float]:
    """Seconds each span gained between two span_totals() readings."""
    return {k: s1[k][0] - s0.get(k, [0.0, 0])[0] for k in s1
            if s1[k][1] != s0.get(k, [0.0, 0])[1]}


def span_note(spans: Dict[str, float]) -> str:
    return ", ".join(f"{k}={v:.3f}" for k, v in sorted(spans.items())
                     if v >= 0.05)


# ---------------------------------------------------------------------------
# tracing one job
# ---------------------------------------------------------------------------

class Tracer:
    """jax.profiler around one traced interval, Python tracer off (it
    would write an event per Python call of the program's host loop)."""

    def __init__(self, work: str, host_label: str):
        self.dir = os.path.join(work, "trace")
        self.host_label = host_label
        self.reduced: Optional[dict] = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> None:
        import jax

        from benchmarks.lib import trace_reduce

        jax.profiler.stop_trace()
        trace = trace_reduce.load(trace_reduce.find_xplane(self.dir))
        self.reduced = trace_reduce.reduce(trace, host_label=self.host_label)
        shutil.rmtree(self.dir, ignore_errors=True)


@contextlib.contextmanager
def job_annotation(i: int):
    import jax

    with jax.profiler.TraceAnnotation("bench_job", job=i):
        yield


# ---------------------------------------------------------------------------
# storage, app, engine variant: what a user sets up once
# ---------------------------------------------------------------------------

def pio(*args) -> None:
    """A `pio` command, in this process, through the CLI's own group."""
    from predictionio_tpu.cli.main import cli

    cli.main(args=list(args), standalone_mode=False)


def write_variant(work: str, cfg: dict, overlay: Optional[dict] = None,
                  name: str = "engine") -> str:
    """The template's engine.json with the app's name, and the
    configuration's `algorithm_params` (then `overlay`) laid over the
    first algorithm's parameters; every other parameter stays at the
    program's default."""
    engine_dir = os.path.join(work, name)
    pio("template", "get", cfg["template"], engine_dir)
    path = os.path.join(engine_dir, "engine.json")
    with open(path) as f:
        variant = json.load(f)
    variant["datasource"]["params"]["app_name"] = APP
    variant["algorithms"][0]["params"].update(
        {**cfg["algorithm_params"], **(overlay or {})})
    with open(path, "w") as f:
        json.dump(variant, f, indent=2)
    return path


def register_release(variant_path: str, model) -> Any:
    """A COMPLETED instance whose model is `model`, registered the way
    run_train registers one: the program's serialiser, model store,
    instance table and release registry. Returns the instance."""
    import datetime as dt

    from predictionio_tpu.cli.main import _load_engine_variant
    from predictionio_tpu.core.params import params_to_json
    from predictionio_tpu.data.event import UTC
    from predictionio_tpu.deploy.releases import record_release
    from predictionio_tpu.storage import Storage
    from predictionio_tpu.storage.base import EngineInstance, Model
    from predictionio_tpu.workflow.serialization import serialize_models

    _engine, ep, factory_path, variant_id, _ = \
        _load_engine_variant(variant_path)
    now = dt.datetime.now(tz=UTC)
    instance = EngineInstance(
        status="INIT", start_time=now, engine_id=factory_path,
        engine_version="1", engine_variant=variant_id,
        engine_factory=factory_path, batch="", runtime_conf={},
        data_source_params=json.dumps(
            params_to_json(ep.data_source_params), sort_keys=True),
        preparator_params=json.dumps(
            params_to_json(ep.preparator_params), sort_keys=True),
        algorithms_params=json.dumps(
            [{"name": n, "params": params_to_json(p)}
             for n, p in ep.algorithm_params_list], sort_keys=True),
        serving_params=json.dumps(
            params_to_json(ep.serving_params), sort_keys=True))
    instances = Storage.get_meta_data_engine_instances()
    instance.id = instances.insert(instance)
    blob = serialize_models([model])
    Storage.get_model_data_models().insert(Model(id=instance.id, models=blob))
    instance.status = "COMPLETED"
    instance.end_time = dt.datetime.now(tz=UTC)
    instances.update(instance)
    record_release(instance, train_seconds=0.0, blob=blob)
    return instance


def synthetic_model(cfg: dict, seed: int):
    """The configuration's factors from the seed as the program's own
    ALSModel. Returns (model, generated factors); the program only ever
    sees a serialised copy, the reference keeps the generated arrays."""
    from benchmarks.lib import datagen
    from predictionio_tpu.models.als import ALSModel

    gen = datagen.factors(cfg["n_users"], cfg["n_items"],
                          cfg["algorithm_params"]["rank"], seed)
    model = ALSModel(
        user_vocab=datagen.entity_ids(cfg["n_users"], "u"),
        item_vocab=datagen.entity_ids(cfg["n_items"], "i"),
        U=gen["U"], V=gen["V"])
    return model, gen


def served_rows(item_scores_rows):
    """Served itemScores lists -> (item index rows, score rows)."""
    idx = [[int(s["item"][1:]) for s in row] for row in item_scores_rows]
    scores = [[float(s["score"]) for s in row] for row in item_scores_rows]
    return idx, scores


# ---------------------------------------------------------------------------
# cell kind: train
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TrainRun:
    """What a configuration's check (checks/<name>.py) is given."""

    #: the configuration as run, with its `limits`
    config: dict
    seed: int
    #: what the event generator returned beside the columns
    truth: Any
    #: the window's last train, as registered
    instance: Any
    #: an instance -> its model, out of the model store
    load_model: Callable[[Any], Any]
    #: algorithm parameters -> the instance of one more train of the same
    #: events with them laid over, outside every clock
    train_again: Callable[[dict], Any]


def run_train_cell(spec: dict) -> dict:
    cfg, traffic, work = spec["config"], spec["traffic"], spec["work"]
    checker = importlib.import_module(f"benchmarks.checks.{cfg['check']}")
    pio("app", "new", APP)
    # the store fills (fill.py: sqlite, host only, a process of its own)
    # while JAX reaches the chip
    truth_path = os.path.join(work, "truth.pickle")
    filler = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "fill.py"),
         json.dumps({"config": cfg, "seed": spec["seed"]}), APP, truth_path],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, stdin=subprocess.DEVNULL)
    try:
        device = claim_device(spec)
        log(f"device claimed: {device['kind']} x{device['count']}")
        from predictionio_tpu.cli.main import _load_engine_variant
        from predictionio_tpu.data.ingest import clear_scan_cache
        from predictionio_tpu.storage import Storage
        from predictionio_tpu.workflow import run_train
        from predictionio_tpu.workflow.serialization import deserialize_models

        if filler.wait(timeout=900) != 0:
            raise SystemExit("the event store could not be filled (fill.py "
                             f"exited with code {filler.returncode})")
    finally:
        if filler.poll() is None:
            filler.kill()
            filler.wait()
    log("event store filled")
    engine, engine_params, factory_path, variant_id, _ = \
        _load_engine_variant(write_variant(work, cfg))

    def one_train(params=engine_params):
        # every train starts as a fresh `pio train` does: nothing of the
        # last one on the heap, and the store scanned anew
        gc.collect()
        clear_scan_cache()
        return run_train(engine, params, engine_factory=factory_path,
                         engine_variant=variant_id)

    def load_model(instance):
        blob = Storage.get_model_data_models().get(instance.id).models
        return deserialize_models(blob)[0]

    def train_again(overlay: dict):
        t0 = time.perf_counter()
        params = _load_engine_variant(
            write_variant(work, cfg, overlay, "engine_again"))[1]
        instance = one_train(params)
        log(f"train with {overlay} for the check: "
            f"{time.perf_counter() - t0:.2f} s")
        return instance

    for _ in range(int(traffic.get("warm_jobs", 1))):
        s0, t0 = span_totals(), time.perf_counter()
        one_train()                   # warms shapes and the store's pages
        log(f"warm train: {time.perf_counter() - t0:.2f} s; "
            + span_note(spans_between(s0, span_totals())))
    tracer = Tracer(work, "train_host") if spec["trace"] else None
    say("ready")
    before = registry_snapshot()
    jobs: List[dict] = []
    t_open = time.perf_counter()
    instance = None
    while time.perf_counter() - t_open < spec["seconds"]:
        traced = tracer is not None and len(jobs) == 1
        s0 = span_totals()
        if traced:
            tracer.start()
        t0 = time.perf_counter()
        with job_annotation(len(jobs)) if traced else contextlib.nullcontext():
            instance = one_train()
        wall = time.perf_counter() - t0
        if traced:
            tracer.stop()
        jobs.append({"wall_s": wall, "traced": traced,
                     "spans": spans_between(s0, span_totals())})
        log(f"train {len(jobs)}: {wall:.4f} s" + (" (traced)" if traced
                                                   else "")
            + "; " + span_note(jobs[-1]["spans"]))
        say("job", index=len(jobs), wall_s=wall, traced=traced)
    window_s = time.perf_counter() - t_open
    after = registry_snapshot()
    mem = memory_peaks()

    # the configuration's check of the last train's release, as the
    # registry holds it: after the window and outside every clock
    with open(truth_path, "rb") as f:     # written by this run's fill.py
        truth = pickle.load(f)
    run = TrainRun(config=cfg, seed=spec["seed"], truth=truth,
                   instance=instance, load_model=load_model,
                   train_again=train_again)
    t0 = time.perf_counter()
    rows = [list(r) for r in checker.check(run)]
    log(f"check: {time.perf_counter() - t0:.2f} s")
    return {"device": device, "memory": mem, "jobs": jobs,
            "window_s": window_s, "attempted": len(jobs),
            "failed": 0, "registry_before": before, "registry_after": after,
            "trace": tracer.reduced if tracer else None,
            "correct_rows": rows, "shapes": checker.shapes(run)}


# ---------------------------------------------------------------------------
# cell kind: batchpredict
# ---------------------------------------------------------------------------

def write_queries(path: str, cfg: dict, traffic: dict, seed: int):
    from benchmarks.lib import datagen

    users = datagen.query_users(cfg["n_users"], traffic["zipf_exponent"],
                                traffic["rows_per_job"], seed)
    ids = datagen.entity_ids(cfg["n_users"], "u")
    num = traffic["num"]
    with open(path, "w") as f:
        f.write("".join('{"user": "%s", "num": %d}\n' % (ids[u], num)
                        for u in users.tolist()))
    return users


def run_batchpredict_cell(spec: dict) -> dict:
    import numpy as np

    from benchmarks.lib import reference

    cfg, traffic, work = spec["config"], spec["traffic"], spec["work"]
    pio("app", "new", APP)
    variant_path = write_variant(work, cfg)
    # the factors are drawn (numpy, host only) while JAX reaches the chip
    made: list = []
    maker = threading.Thread(
        target=lambda: made.append(synthetic_model(cfg, spec["seed"])))
    maker.start()
    device = claim_device(spec)
    log(f"device claimed: {device['kind']} x{device['count']}")
    from predictionio_tpu.cli.main import _load_engine_variant
    from predictionio_tpu.workflow.batch_predict import run_batch_predict

    maker.join()
    if not made:
        raise SystemExit("the factors could not be made")
    model, gen = made[0]
    log("factors made")
    instance = register_release(variant_path, model)
    del model, made
    log("release registered")
    engine = _load_engine_variant(variant_path)[0]
    input_path = os.path.join(work, "queries.jsonl")
    users = write_queries(input_path, cfg, traffic, spec["seed"])
    output_path = os.path.join(work, "predictions.jsonl")

    def one_job():
        # the function behind `pio batchpredict`: restores the release
        # from the model store, scores, commits the sink
        gc.collect()
        return run_batch_predict(engine, instance, input_path, output_path,
                                 chunk_size=traffic["chunk_size"])

    for _ in range(int(traffic.get("warm_jobs", 1))):
        one_job()
    log("warm job done")
    tracer = Tracer(work, "batchpredict_host") if spec["trace"] else None
    say("ready")
    before = registry_snapshot()
    jobs: List[dict] = []
    t_open = time.perf_counter()
    failed = 0
    while time.perf_counter() - t_open < spec["seconds"]:
        traced = tracer is not None and len(jobs) == 1
        if traced:
            tracer.start()
        t0 = time.perf_counter()
        with job_annotation(len(jobs)) if traced else contextlib.nullcontext():
            report = one_job()
        wall = time.perf_counter() - t0
        if traced:
            tracer.stop()
        failed += report.invalid
        jobs.append({"wall_s": wall, "traced": traced,
                     "rows": report.written, "chunks": report.chunks,
                     "pad_waste": report.pad_waste,
                     "rows_per_s": report.written / wall})
        log(f"job {len(jobs)}: {wall:.4f} s, {report.written / wall:.1f} "
            "rows/s" + (" (traced)" if traced else ""))
        say("job", index=len(jobs), wall_s=wall, traced=traced)
    window_s = time.perf_counter() - t_open
    after = registry_snapshot()
    mem = memory_peaks()

    # a seeded sample of the last job's rows, against the reference
    rng = np.random.default_rng([spec["seed"], 0xC0])
    pick = np.sort(rng.choice(len(users), min(traffic["check_rows"],
                                              len(users)), replace=False))
    want = set(pick.tolist())
    served = {}
    with open(output_path) as f:
        for row, line in enumerate(f):
            if row in want:
                served[row] = json.loads(line)["prediction"]["itemScores"]
    idx, scores = served_rows([served.get(r, []) for r in pick.tolist()])
    u_rows = gen["U"][users[pick]]
    rows = reference.compare_topk(u_rows, gen["V"], idx, scores,
                                  traffic["num"], cfg["limits"])
    return {"device": device, "memory": mem, "jobs": jobs,
            "window_s": window_s,
            "attempted": sum(j["rows"] for j in jobs) + failed,
            "failed": failed, "registry_before": before,
            "registry_after": after,
            "trace": tracer.reduced if tracer else None,
            "correct_rows": rows, "shapes": {
                "n_items": cfg["n_items"],
                "rank": cfg["algorithm_params"]["rank"],
                "num": traffic["num"], "chunk_size": traffic["chunk_size"]}}


KINDS = {"train": run_train_cell, "batchpredict": run_batchpredict_cell}


def main(argv=None) -> int:
    spec = json.loads((argv or sys.argv)[1])
    evidence = KINDS[spec["traffic"]["kind"]](spec)
    path = os.path.join(spec["work"], "evidence.json")
    with open(path, "w") as f:
        json.dump(evidence, f)
    say("done", evidence=path)
    return 0


T0 = time.time()

if __name__ == "__main__":
    sys.exit(main())
