"""Offline batch scoring (workflow/batch_predict.py): pipelined, sharded,
columnar `pio batchpredict`.

Covers the PR-8 contracts: per-engine parity with the query server's
single-query answers, 2-shard merge == single-process run, crash-safe
temp-write + rename output (a kill mid-run leaves nothing partial at the
final path), malformed-row sidecar isolation, columnar parquet input and
output (both layouts), the arrow-lane fallback, and the metrics the run
emits."""

import json
import os

import numpy as np
import pytest

from predictionio_tpu.obs.registry import MetricsRegistry
from predictionio_tpu.storage import faults
from predictionio_tpu.workflow.batch_predict import run_batch_predict


def _synth_result(nu=40, ni=24, rank=4, seed=5):
    """Tiny deterministic trained recommendation engine (no storage)."""
    from predictionio_tpu.core.engine import TrainResult
    from predictionio_tpu.core.params import EngineParams
    from predictionio_tpu.engines.recommendation import (
        ALSAlgorithm, AlgorithmParams, RecommendationServing,
    )
    from predictionio_tpu.models.als import ALSModel

    rng = np.random.default_rng(seed)
    model = ALSModel(
        user_vocab=np.asarray([f"u{i}" for i in range(nu)], dtype=object),
        item_vocab=np.asarray([f"i{i}" for i in range(ni)], dtype=object),
        U=rng.normal(size=(nu, rank)).astype(np.float32),
        V=rng.normal(size=(ni, rank)).astype(np.float32))
    return TrainResult(
        models=[model], algorithms=[ALSAlgorithm(AlgorithmParams())],
        serving=RecommendationServing(), engine_params=EngineParams())


def _write_queries(path, n=60, nu=40):
    with open(path, "w") as f:
        for i in range(n):
            q = {"user": f"u{i % (nu + 3)}", "num": 3 + (i % 4)}
            if i % 7 == 0:
                q["black_list"] = [f"i{i % 5}"]
            f.write(json.dumps(q) + "\n")
    return n


def _read_jsonl(path):
    with open(path) as f:
        return [json.loads(ln) for ln in f if ln.strip()]


def _read_parquet_values(path):
    import pyarrow.parquet as pq

    table = pq.read_table(path)
    rows = []
    for q, p in zip(table.column("query").to_pylist(),
                    table.column("prediction").to_pylist()):
        rows.append({"query": json.loads(q),
                     "prediction": json.loads(p) if isinstance(p, str)
                     else p})
    return rows


# ---------------------------------------------------------------------------
# sharding
# ---------------------------------------------------------------------------

def test_sharded_merge_equals_single_process(tmp_path):
    """2-shard run (contiguous ranges + manifest merge) must produce the
    byte-identical file a single-process run writes, and GC its
    fragments/metas/manifest after the merge."""
    result = _synth_result()
    inp = tmp_path / "q.jsonl"
    n = _write_queries(inp)

    single = tmp_path / "single.jsonl"
    rep = run_batch_predict(None, None, str(inp), str(single),
                            chunk_size=16, loaded=(result, None))
    assert rep.written == rep.total_written == n and rep.merged

    merged = tmp_path / "merged.jsonl"
    r0 = run_batch_predict(None, None, str(inp), str(merged),
                           chunk_size=16, loaded=(result, None),
                           worker=(0, 2))
    assert not r0.merged and r0.worker == (0, 2)
    assert not merged.exists()           # half the shards done: no output
    r1 = run_batch_predict(None, None, str(inp), str(merged),
                           chunk_size=16, loaded=(result, None),
                           worker=(1, 2))
    assert r1.merged and r1.total_written == n
    assert r0.written + r1.written == n
    assert abs(r0.written - r1.written) <= 1     # balanced ranges
    assert merged.read_bytes() == single.read_bytes()
    leftovers = [p for p in os.listdir(tmp_path)
                 if ".part-" in p or ".meta-" in p or ".manifest" in p
                 or ".tmp-" in p]
    assert not leftovers, leftovers


def test_shard_identity_from_the_process_env(tmp_path, monkeypatch):
    """A batchpredict fleet is N processes with two env vars each: with
    no `worker=` a run takes its shard from PIO_PROCESS_ID /
    PIO_NUM_PROCESSES, and the last shard to finish merges the file a
    single process writes."""
    result = _synth_result()
    inp = tmp_path / "q.jsonl"
    n = _write_queries(inp)
    single = tmp_path / "single.jsonl"
    run_batch_predict(None, None, str(inp), str(single),
                      chunk_size=16, loaded=(result, None), worker=(0, 1))

    merged = tmp_path / "merged.jsonl"
    monkeypatch.setenv("PIO_NUM_PROCESSES", "2")
    reports = []
    for rank in ("1", "0"):
        monkeypatch.setenv("PIO_PROCESS_ID", rank)
        reports.append(run_batch_predict(
            None, None, str(inp), str(merged), chunk_size=16,
            loaded=(result, None)))
    assert [r.worker for r in reports] == [(1, 2), (0, 2)]
    assert not reports[0].merged and reports[1].merged
    assert reports[0].written + reports[1].written == n
    assert merged.read_bytes() == single.read_bytes()
    monkeypatch.setenv("PIO_PROCESS_ID", "2")
    with pytest.raises(ValueError, match="PIO_PROCESS_ID=2 outside"):
        run_batch_predict(None, None, str(inp), str(merged),
                          chunk_size=16, loaded=(result, None))


def test_sharded_parquet_values_equal_single(tmp_path):
    """Sharded parquet fragments merge into the same VALUES as a
    single-process parquet run (row-group layout may differ)."""
    result = _synth_result()
    inp = tmp_path / "q.jsonl"
    _write_queries(inp)

    single = tmp_path / "single.parquet"
    run_batch_predict(None, None, str(inp), str(single),
                      chunk_size=16, loaded=(result, None))
    merged = tmp_path / "merged.parquet"
    for rank in (0, 1):
        rep = run_batch_predict(None, None, str(inp), str(merged),
                                chunk_size=16, loaded=(result, None),
                                worker=(rank, 2))
    assert rep.merged
    assert _read_parquet_values(merged) == _read_parquet_values(single)


# ---------------------------------------------------------------------------
# crash safety
# ---------------------------------------------------------------------------

def test_kill_mid_run_leaves_no_partial_output(tmp_path):
    """An injected kill while chunks are being written must leave
    NOTHING visible at the final path (temp-write + atomic rename), and
    a clean re-run must succeed."""
    result = _synth_result()
    inp = tmp_path / "q.jsonl"
    n = _write_queries(inp)
    out = tmp_path / "out.jsonl"

    faults.set_kill_points(["batchpredict:chunk"])
    try:
        with pytest.raises(faults.CrashError):
            run_batch_predict(None, None, str(inp), str(out),
                              chunk_size=16, loaded=(result, None))
    finally:
        faults.set_kill_points([])
    assert not out.exists()
    assert not list(tmp_path.glob("out.jsonl.tmp-*"))   # temp cleaned up

    rep = run_batch_predict(None, None, str(inp), str(out),
                            chunk_size=16, loaded=(result, None))
    assert rep.written == n and out.exists()


def test_kill_mid_merge_leaves_no_partial_output(tmp_path):
    """A kill inside the shard MERGE (after the manifest is claimed)
    must still leave nothing at the final path; the next run of any
    shard rolls the crashed merge forward from the surviving fragments
    — no manual manifest surgery required."""
    result = _synth_result()
    inp = tmp_path / "q.jsonl"
    n = _write_queries(inp)
    out = tmp_path / "out.jsonl"

    run_batch_predict(None, None, str(inp), str(out), chunk_size=16,
                      loaded=(result, None), worker=(0, 2))
    faults.set_kill_points(["batchpredict:merge"])
    try:
        with pytest.raises(faults.CrashError):
            run_batch_predict(None, None, str(inp), str(out),
                              chunk_size=16, loaded=(result, None),
                              worker=(1, 2))
    finally:
        faults.set_kill_points([])
    assert not out.exists()
    assert os.path.exists(f"{out}.manifest.json")   # the stale claim
    rep = run_batch_predict(None, None, str(inp), str(out), chunk_size=16,
                            loaded=(result, None), worker=(1, 2))
    assert rep.merged and rep.total_written == n and out.exists()
    assert not os.path.exists(f"{out}.manifest.json")   # GC'd post-merge


def test_stale_manifest_after_commit_does_not_wedge(tmp_path, monkeypatch):
    """A merger crashing AFTER its commit but BEFORE GC leaves the
    manifest + all fragments behind next to a committed output. The
    next fleet over the same path must neither be wedged by the stale
    claim nor merge the stale fragments: stale metas fail the input
    fingerprint check, each shard clears its own old markers, and the
    last shard re-runs the merge over the fresh fragments."""
    result = _synth_result()
    inp = tmp_path / "q.jsonl"
    _write_queries(inp)
    out = tmp_path / "out.jsonl"

    # fleet 1 completes its merge but "crashes" before GC: suppress the
    # marker unlinks so manifest/parts/metas all survive the commit
    real_unlink = os.unlink

    def keep_markers(path, *args, **kwargs):
        p = str(path)
        if ".part-" in p or ".meta-" in p or ".manifest" in p:
            return
        return real_unlink(path, *args, **kwargs)

    monkeypatch.setattr(os, "unlink", keep_markers)
    for rank in (0, 1):
        run_batch_predict(None, None, str(inp), str(out), chunk_size=16,
                          loaded=(result, None), worker=(rank, 2))
    monkeypatch.undo()
    assert out.exists() and os.path.exists(f"{out}.manifest.json")

    # fleet 2 scores a DIFFERENT query file content to the same path:
    # the final output must reflect fleet 2, not the stale fragments
    n2 = _write_queries(inp, n=50)
    single = tmp_path / "single.jsonl"
    run_batch_predict(None, None, str(inp), str(single), chunk_size=16,
                      loaded=(result, None))
    for rank in (0, 1):
        rep = run_batch_predict(None, None, str(inp), str(out),
                                chunk_size=16, loaded=(result, None),
                                worker=(rank, 2))
    assert rep.merged and rep.total_written == n2
    assert out.read_bytes() == single.read_bytes()
    leftovers = [p for p in os.listdir(tmp_path)
                 if ".part-" in p or ".meta-" in p or ".manifest" in p]
    assert not leftovers, leftovers


# ---------------------------------------------------------------------------
# malformed input
# ---------------------------------------------------------------------------

def test_malformed_rows_skip_to_sidecar(tmp_path):
    """Bad JSON and queries that don't fit the engine's query class
    never abort the run: each lands in the `.errors.jsonl` sidecar and
    `pio_batchpredict_invalid_queries_total`; valid rows still score."""
    result = _synth_result()
    inp = tmp_path / "q.jsonl"
    inp.write_text("\n".join([
        json.dumps({"user": "u1", "num": 3}),
        "this is { not json",
        json.dumps({"wrong_field": 1}),          # doesn't fit Query
        "",                                      # blank: ignored, not error
        json.dumps({"user": "u2", "num": 2}),
    ]) + "\n")
    out = tmp_path / "out.jsonl"
    registry = MetricsRegistry()
    rep = run_batch_predict(None, None, str(inp), str(out), chunk_size=8,
                            loaded=(result, None), registry=registry)
    assert rep.written == 2 and rep.invalid == 2
    assert rep.errors_path == str(out) + ".errors.jsonl"
    lines = _read_jsonl(out)
    assert [ln["query"]["user"] for ln in lines] == ["u1", "u2"]
    errors = _read_jsonl(rep.errors_path)
    assert [e["row"] for e in errors] == [1, 2]
    assert "invalid JSON" in errors[0]["error"]
    assert "does not fit" in errors[1]["error"]
    assert registry.counter(
        "pio_batchpredict_invalid_queries_total", "").value() == 2


def test_clean_run_writes_no_sidecar(tmp_path):
    result = _synth_result()
    inp = tmp_path / "q.jsonl"
    _write_queries(inp, n=5)
    out = tmp_path / "out.jsonl"
    rep = run_batch_predict(None, None, str(inp), str(out),
                            loaded=(result, None))
    assert rep.invalid == 0 and rep.errors_path is None
    assert not os.path.exists(str(out) + ".errors.jsonl")


def test_clean_run_removes_stale_sidecar(tmp_path):
    """A clean re-run over the same output path must remove the sidecar
    a previous (dirty) run left there — otherwise stale errors
    masquerade as the fresh run's."""
    result = _synth_result()
    inp = tmp_path / "q.jsonl"
    inp.write_text(json.dumps({"user": "u1", "num": 3}) + "\n"
                   + "not json\n")
    out = tmp_path / "out.jsonl"
    rep = run_batch_predict(None, None, str(inp), str(out),
                            loaded=(result, None))
    sidecar = str(out) + ".errors.jsonl"
    assert rep.invalid == 1 and os.path.exists(sidecar)

    inp.write_text(json.dumps({"user": "u1", "num": 3}) + "\n")
    rep = run_batch_predict(None, None, str(inp), str(out),
                            loaded=(result, None))
    assert rep.invalid == 0 and rep.errors_path is None
    assert not os.path.exists(sidecar)


# ---------------------------------------------------------------------------
# columnar input/output
# ---------------------------------------------------------------------------

def test_parquet_input_layouts_match_jsonl(tmp_path):
    """Both accepted parquet query layouts — a `query` JSON column and
    one column per query field — score byte-identically to the same
    queries fed as JSON-lines."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from predictionio_tpu.data.columnar import queries_to_table

    result = _synth_result()
    queries = [{"num": 2 + i % 3, "user": f"u{i}"} for i in range(20)]
    inp_jsonl = tmp_path / "q.jsonl"
    inp_jsonl.write_text(
        "".join(json.dumps(q, sort_keys=True) + "\n" for q in queries))
    ref = tmp_path / "ref.jsonl"
    run_batch_predict(None, None, str(inp_jsonl), str(ref),
                      chunk_size=8, loaded=(result, None))

    qcol = tmp_path / "qcol.parquet"
    pq.write_table(queries_to_table(queries), qcol)
    out1 = tmp_path / "out1.jsonl"
    run_batch_predict(None, None, str(qcol), str(out1),
                      chunk_size=8, loaded=(result, None))
    assert out1.read_bytes() == ref.read_bytes()

    fields = tmp_path / "fields.parquet"
    pq.write_table(pa.table({
        "user": [q["user"] for q in queries],
        "num": [q["num"] for q in queries]}), fields)
    out2 = tmp_path / "out2.jsonl"
    run_batch_predict(None, None, str(fields), str(out2),
                      chunk_size=8, loaded=(result, None))
    assert out2.read_bytes() == ref.read_bytes()


def test_sharded_parquet_input_equals_single(tmp_path):
    """Sharded runs over a MULTI-ROW-GROUP parquet input (each shard
    prunes to the row groups overlapping its range) merge to exactly the
    single-process output."""
    import pyarrow.parquet as pq

    from predictionio_tpu.data.columnar import queries_to_table

    result = _synth_result()
    queries = [{"num": 2 + i % 3, "user": f"u{i % 43}"} for i in range(60)]
    inp = tmp_path / "q.parquet"
    pq.write_table(queries_to_table(queries), inp, row_group_size=7)
    assert pq.ParquetFile(inp).metadata.num_row_groups > 1

    single = tmp_path / "single.jsonl"
    run_batch_predict(None, None, str(inp), str(single),
                      chunk_size=16, loaded=(result, None))
    merged = tmp_path / "merged.jsonl"
    for rank in (0, 1, 2):
        rep = run_batch_predict(None, None, str(inp), str(merged),
                                chunk_size=16, loaded=(result, None),
                                worker=(rank, 3))
    assert rep.merged and merged.read_bytes() == single.read_bytes()


def test_output_format_precedence_extension_beats_config():
    """A recognized extension outranks the configured default (a
    server.json outputFormat must never mislabel preds.parquet), and an
    explicit per-invocation override outranks both."""
    from predictionio_tpu.workflow.batch_predict import _format_of

    assert _format_of("preds.parquet", None, "jsonl") == "parquet"
    assert _format_of("preds.jsonl", None, "parquet") == "jsonl"
    assert _format_of("preds.out", None, "parquet") == "parquet"
    assert _format_of("preds.out", None, None) == "jsonl"
    assert _format_of("preds.parquet", "jsonl", None) == "jsonl"


def test_parquet_query_echo_is_canonical(tmp_path):
    """The parquet query column carries canonical sort_keys JSON —
    identical bytes to the jsonl lane — however the input spelled the
    object (key order, whitespace)."""
    import pyarrow.parquet as pq

    result = _synth_result()
    inp = tmp_path / "q.jsonl"
    inp.write_text('{"num": 3,   "user": "u1"}\n{"user":"u2","num":2}\n')
    out = tmp_path / "out.parquet"
    run_batch_predict(None, None, str(inp), str(out),
                      loaded=(result, None))
    qs = pq.read_table(out).column("query").to_pylist()
    assert qs == ['{"num": 3, "user": "u1"}', '{"num": 2, "user": "u2"}']


def test_parquet_output_structured_and_value_identical(tmp_path):
    """Parquet output from the arrow lane carries REAL wire-typed
    columns (list<struct<item,score>> under a struct, not JSON strings)
    and exactly the values of the JSON-lines run."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    result = _synth_result()
    inp = tmp_path / "q.jsonl"
    _write_queries(inp)
    ref = tmp_path / "ref.jsonl"
    run_batch_predict(None, None, str(inp), str(ref), chunk_size=16,
                      loaded=(result, None))
    out = tmp_path / "out.parquet"
    rep = run_batch_predict(None, None, str(inp), str(out), chunk_size=16,
                            loaded=(result, None))
    assert rep.written == len(_read_jsonl(ref))
    schema = pq.read_table(out).schema
    assert schema.field("prediction").type == pa.struct([
        ("itemScores", pa.list_(pa.struct([("item", pa.string()),
                                           ("score", pa.float64())])))])
    assert _read_parquet_values(out) == _read_jsonl(ref)


def test_arrow_lane_failure_falls_back_to_generic(tmp_path, monkeypatch):
    """A broken arrow hook must not fail the run or change the output:
    the chunk retries on the generic path, values identical."""
    from predictionio_tpu.engines.recommendation import ALSAlgorithm

    result = _synth_result()
    inp = tmp_path / "q.jsonl"
    n = _write_queries(inp)
    ref = tmp_path / "ref.jsonl"
    run_batch_predict(None, None, str(inp), str(ref), chunk_size=16,
                      loaded=(result, None))

    def boom(self, model, queries):
        raise RuntimeError("arrow lane down")

    monkeypatch.setattr(ALSAlgorithm, "batch_predict_arrow", boom)
    out = tmp_path / "out.parquet"
    rep = run_batch_predict(None, None, str(inp), str(out), chunk_size=16,
                            loaded=(result, None))
    assert rep.written == n and rep.invalid == 0
    assert _read_parquet_values(out) == _read_jsonl(ref)


def test_serving_override_disables_fast_lanes(tmp_path):
    """Engines with a custom Serving keep the generic per-row path — an
    overridden serve() must be honored, so the dataclass-free lanes are
    ineligible."""
    from predictionio_tpu.core.base import Serving as BaseServing
    from predictionio_tpu.engines.recommendation import PredictedResult

    result = _synth_result()

    class TopOne(BaseServing):
        def serve(self, query, predictions):
            return PredictedResult(
                item_scores=predictions[0].item_scores[:1])

    result.serving = TopOne()
    inp = tmp_path / "q.jsonl"
    inp.write_text(json.dumps({"user": "u1", "num": 5}) + "\n")
    out = tmp_path / "out.jsonl"
    run_batch_predict(None, None, str(inp), str(out),
                      loaded=(result, None))
    (line,) = _read_jsonl(out)
    assert len(line["prediction"]["itemScores"]) == 1


# ---------------------------------------------------------------------------
# metrics + pipeline accounting
# ---------------------------------------------------------------------------

def test_metrics_and_pad_waste_accounting(tmp_path):
    """13 queries at chunk 8 -> chunks [8, 5]; the short chunk pads up
    its power-of-two bucket (8), so 3 throwaway rows are charged to
    `pio_batchpredict_pad_waste_rows_total` and the report."""
    result = _synth_result()
    inp = tmp_path / "q.jsonl"
    _write_queries(inp, n=13)
    out = tmp_path / "out.jsonl"
    registry = MetricsRegistry()
    rep = run_batch_predict(None, None, str(inp), str(out), chunk_size=8,
                            loaded=(result, None), registry=registry)
    assert rep.written == 13 and rep.chunks == 2
    assert rep.pad_waste == 3
    assert registry.counter(
        "pio_batchpredict_pad_waste_rows_total", "").value() == 3
    assert registry.counter(
        "pio_batchpredict_queries_total", "").value() == 13
    assert registry.gauge(
        "pio_batchpredict_rows_per_second", "").value() > 0
    assert rep.rows_per_second > 0 and rep.seconds > 0


# ---------------------------------------------------------------------------
# per-engine parity with the query server
# ---------------------------------------------------------------------------

@pytest.fixture()
def storage_backend(tmp_path):
    from predictionio_tpu.data.eventstore import clear_cache
    from predictionio_tpu.storage import Storage

    Storage.configure({
        "sources": {"DB": {"TYPE": "sqlite",
                           "PATH": str(tmp_path / "bp.db")}},
        "repositories": {
            "METADATA": {"NAME": "pio", "SOURCE": "DB"},
            "EVENTDATA": {"NAME": "pio", "SOURCE": "DB"},
            "MODELDATA": {"NAME": "pio", "SOURCE": "DB"},
        },
    })
    clear_cache()
    yield Storage
    Storage.reset()
    clear_cache()


def _make_app(backend, name):
    from predictionio_tpu.storage import App

    app_id = backend.get_meta_data_apps().insert(App(id=0, name=name))
    backend.get_events().init_channel(app_id)
    return app_id


def _setup_recommendation(backend):
    from predictionio_tpu.data import DataMap, Event
    from predictionio_tpu.engines.recommendation import (
        default_engine_params, engine,
    )
    from predictionio_tpu.workflow import run_train

    app_id = _make_app(backend, "BpRec")
    rng = np.random.default_rng(7)
    events = []
    for u in range(15):
        for it in range(10):
            if (u % 2) == (it % 2) and rng.random() < 0.7:
                events.append(Event(
                    event="rate", entity_type="user", entity_id=f"u{u}",
                    target_entity_type="item", target_entity_id=f"i{it}",
                    properties=DataMap(
                        {"rating": float(rng.integers(1, 6))})))
    backend.get_events().insert_batch(events, app_id)
    eng = engine()
    ep = default_engine_params("BpRec", rank=4, num_iterations=4)
    instance = run_train(
        eng, ep,
        engine_factory="predictionio_tpu.engines.recommendation:engine")
    queries = [{"user": "u0", "num": 3}, {"user": "u1", "num": 5},
               {"user": "ghost", "num": 3},
               {"user": "u2", "num": 4, "black_list": ["i0", "i2"]},
               {"user": "u3", "num": 2, "white_list": ["i1", "i3", "i5"]}]
    return eng, instance, queries


def _setup_classification(backend):
    from predictionio_tpu.data import DataMap, Event
    from predictionio_tpu.engines.classification import (
        default_engine_params, engine,
    )
    from predictionio_tpu.workflow import run_train

    app_id = _make_app(backend, "BpCls")
    rng = np.random.default_rng(5)
    events = []
    for i in range(80):
        a0, a1 = float(rng.integers(0, 8)), float(rng.integers(0, 8))
        events.append(Event(
            event="$set", entity_type="user", entity_id=f"u{i}",
            properties=DataMap({"plan": 1.0 if a0 > a1 else 0.0,
                                "attr0": a0, "attr1": a1,
                                "attr2": float(rng.integers(0, 4))})))
    backend.get_events().insert_batch(events, app_id)
    eng = engine()
    ep = default_engine_params("BpCls", algorithm="naive")
    instance = run_train(
        eng, ep,
        engine_factory="predictionio_tpu.engines.classification:engine")
    queries = [{"attr0": 7.0, "attr1": 0.0, "attr2": 1.0},
               {"attr0": 0.0, "attr1": 7.0, "attr2": 1.0},
               {"attr0": 3.0, "attr1": 3.0, "attr2": 2.0}]
    return eng, instance, queries


def _setup_similarproduct(backend):
    from predictionio_tpu.data import DataMap, Event
    from predictionio_tpu.engines.similarproduct import (
        default_engine_params, engine,
    )
    from predictionio_tpu.workflow import run_train

    app_id = _make_app(backend, "BpSim")
    rng = np.random.default_rng(3)
    events = []
    for it in range(12):
        events.append(Event(
            event="$set", entity_type="item", entity_id=f"i{it}",
            properties=DataMap({"categories": [
                "even" if it % 2 == 0 else "odd"]})))
    for u in range(16):
        for it in range(12):
            if it % 2 == (u % 2) and rng.random() < 0.8:
                events.append(Event(
                    event="view", entity_type="user", entity_id=f"u{u}",
                    target_entity_type="item", target_entity_id=f"i{it}"))
    backend.get_events().insert_batch(events, app_id)
    eng = engine()
    ep = default_engine_params("BpSim", algorithms=("als",))
    instance = run_train(
        eng, ep,
        engine_factory="predictionio_tpu.engines.similarproduct:engine")
    queries = [{"items": ["i0"], "num": 4},
               {"items": ["i1", "i3"], "num": 3},
               {"items": ["i0"], "num": 4, "categories": ["odd"]},
               {"items": ["i2"], "num": 3, "black_list": ["i4"]},
               {"items": ["nope"], "num": 3}]
    return eng, instance, queries


def _assert_same_answers(got, expected):
    """Structural equality with floats compared at float32 precision:
    the server's single-query path runs a batch-of-1 matmul where
    batchpredict runs a batch-of-chunk, so BLAS accumulation order may
    differ in the last float32 bits — items, order and shapes must still
    agree exactly."""
    import math

    def eq(a, b, path):
        if isinstance(a, float) or isinstance(b, float):
            assert math.isclose(float(a), float(b),
                                rel_tol=1e-5, abs_tol=1e-6), (path, a, b)
        elif isinstance(a, dict):
            assert isinstance(b, dict) and a.keys() == b.keys(), (
                path, a, b)
            for k in a:
                eq(a[k], b[k], f"{path}.{k}")
        elif isinstance(a, list):
            assert isinstance(b, list) and len(a) == len(b), (path, a, b)
            for i, (x, y) in enumerate(zip(a, b)):
                eq(x, y, f"{path}[{i}]")
        else:
            assert a == b, (path, a, b)

    assert len(got) == len(expected)
    for i, (g, e) in enumerate(zip(got, expected)):
        eq(g, e, f"row{i}")


@pytest.mark.parametrize("setup", [
    _setup_recommendation, _setup_classification, _setup_similarproduct,
], ids=["recommendation", "classification", "similarproduct"])
def test_parity_with_query_server(storage_backend, tmp_path, setup):
    """For every engine with a batch_predict path: batchpredict over a
    query file must answer exactly what the query server answers for the
    same queries one at a time on the same trained instance (same items,
    same order, scores at float32 precision)."""
    from predictionio_tpu.core.params import params_from_json
    from predictionio_tpu.server.query_server import (
        _query_class, _to_jsonable, create_query_server,
    )
    from predictionio_tpu.workflow.train import load_for_deploy

    eng, instance, queries = setup(storage_backend)
    result, ctx = load_for_deploy(eng, instance)
    server = create_query_server(eng, result, instance, ctx)
    qc = _query_class(result)
    expected = [
        {"query": q, "prediction": _to_jsonable(
            server._predict(params_from_json(q, qc) if qc else q))}
        for q in queries]

    inp, out = tmp_path / "queries.jsonl", tmp_path / "preds.jsonl"
    inp.write_text("".join(json.dumps(q) + "\n" for q in queries))
    rep = run_batch_predict(eng, instance, str(inp), str(out),
                            chunk_size=4)
    assert rep.written == len(queries) and rep.invalid == 0
    _assert_same_answers(_read_jsonl(out), expected)

    # parquet output of the same run carries byte-identical values to
    # the jsonl run (same batch shapes -> exact, not just approximate)
    outp = tmp_path / "preds.parquet"
    run_batch_predict(eng, instance, str(inp), str(outp), chunk_size=4)
    assert _read_parquet_values(outp) == _read_jsonl(out)


def test_pipelined_false_matches_pipelined_true(tmp_path):
    """`pipelined=False` (the measurement baseline: same stages, one
    thread) writes the byte-identical file."""
    result = _synth_result()
    inp = tmp_path / "q.jsonl"
    _write_queries(inp)
    a, b = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    run_batch_predict(None, None, str(inp), str(a), chunk_size=16,
                      loaded=(result, None), pipelined=True)
    run_batch_predict(None, None, str(inp), str(b), chunk_size=16,
                      loaded=(result, None), pipelined=False)
    assert a.read_bytes() == b.read_bytes()
