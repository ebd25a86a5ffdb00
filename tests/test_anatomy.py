"""Cost & capacity attribution plane (obs/anatomy, obs/capacity):
histogram exemplars end to end (capture -> exposition -> snapshot merge
-> tsdb persistence), SLO breach evidence + trace pinning, per-request
stage anatomy under a concurrent burst, the device-memory ledger, and
the tail-anatomy report math behind `pio analyze`."""

import json
import re
import time
from types import SimpleNamespace

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from predictionio_tpu.obs import anatomy, jax_stats, tracing
from predictionio_tpu.obs import trace_context as tc
from predictionio_tpu.obs.anatomy import (
    SERVING_COST_STAGES, SERVING_WALL_STAGES, STAGE_HISTOGRAM,
    composition, regression_diff, stage_stats,
)
from predictionio_tpu.obs.capacity import (
    capacity_document, model_capacity, unit_capacity,
)
from predictionio_tpu.obs.registry import MetricsRegistry, render_prometheus
from predictionio_tpu.obs.slo import SLOEngine, SLOObjective, SLOSpec, \
    SLOWindow
from predictionio_tpu.obs.tsdb import TSDB, TSDBReader, merge_exemplar_slots
from test_obs_registry import parse_exposition

pytestmark = pytest.mark.anyio

EXEMPLAR_LINE = re.compile(
    r'^# exemplar ([a-zA-Z_:][a-zA-Z0-9_:]*_bucket)\{[^{}]*le="[^"]+"[^{}]*\}'
    r' trace_id="([^"]+)" (\S+) (\S+)$')


@pytest.fixture(autouse=True)
def _clean_recorder():
    tc.recorder().clear()
    yield
    tc.recorder().clear()


def _observe_traced(hist, value, request_id, **labels):
    """One observation under a live trace; returns the trace id the
    exemplar provider should have stamped."""
    tokens, trace = tracing.start_trace(request_id)
    try:
        hist.observe(value, **labels)
    finally:
        tracing.reset_trace(tokens)
    return trace.trace_id


# ---------------------------------------------------------------------------
# exemplar capture + algebra
# ---------------------------------------------------------------------------

def test_exemplar_capture_requires_trace_and_anatomy(monkeypatch):
    r = MetricsRegistry()
    h = r.histogram("pio_ex_seconds", buckets=(0.1, 1.0))
    h.observe(0.05)                      # no live trace -> no exemplar
    assert h.exemplars() == [None, None, None]

    tid = _observe_traced(h, 0.5, "req-1")
    ex = h.exemplars()
    assert ex[0] is None and ex[2] is None
    assert ex[1][0] == tid and ex[1][1] == 0.5

    # the PIO_ANATOMY kill switch stops exemplar capture too
    monkeypatch.setenv(anatomy.ANATOMY_ENV, "0")
    _observe_traced(h, 5.0, "req-2")
    assert h.exemplars()[2] is None


def test_exemplar_newest_wins_and_exposition_stays_parseable():
    r = MetricsRegistry()
    h = r.histogram("pio_ex_seconds", buckets=(0.1, 1.0),
                    labelnames=("op",))
    _observe_traced(h, 0.5, "older", op="a")
    tid = _observe_traced(h, 0.6, "newer", op="a")
    assert h.exemplars(op="a")[1][0] == tid

    text = render_prometheus([r])
    # 0.0.4-style parsers (and this repo's own) must still parse every
    # sample line: exemplars ride as comments
    samples, _ = parse_exposition(text)
    assert samples['pio_ex_seconds_bucket{op="a",le="1"}'] == 2
    matches = [EXEMPLAR_LINE.match(ln) for ln in text.splitlines()
               if ln.startswith("# exemplar ")]
    assert matches and all(m is not None for m in matches)
    assert any(m.group(2) == tid and float(m.group(3)) == 0.6
               for m in matches)


def test_exemplar_snapshot_merge_algebra():
    src = MetricsRegistry()
    h = src.histogram("pio_ex_seconds", buckets=(0.1, 1.0))
    tid = _observe_traced(h, 0.5, "round-trip")
    snap = src.to_snapshot()
    assert snap["pio_ex_seconds"]["series"][0]["exemplars"][1][0] == tid

    # round-trip: merge into an empty registry carries the slots exactly
    dst = MetricsRegistry()
    dst.merge_snapshot(snap)
    merged = dst.get("pio_ex_seconds")
    assert merged.exemplars()[1][0] == tid
    assert merged.count() == 1

    # fleet merge keeps the NEWEST exemplar per bucket (counts still add)
    newer = json.loads(json.dumps(snap))
    newer["pio_ex_seconds"]["series"][0]["exemplars"][1] = \
        ["winner", 0.7, time.time() + 100]
    dst.merge_snapshot(newer)
    assert merged.exemplars()[1][0] == "winner"
    older = json.loads(json.dumps(snap))
    older["pio_ex_seconds"]["series"][0]["exemplars"][1] = \
        ["loser", 0.8, 1.0]
    dst.merge_snapshot(older)
    assert merged.exemplars()[1][0] == "winner"
    assert merged.count() == 3

    # merging a snapshot WITHOUT exemplars is the identity on the slots
    plain = json.loads(json.dumps(snap))
    del plain["pio_ex_seconds"]["series"][0]["exemplars"]
    dst.merge_snapshot(plain)
    assert merged.exemplars()[1][0] == "winner"

    # slot-count mismatch is corruption, not mergeable data
    bad = json.loads(json.dumps(snap))
    bad["pio_ex_seconds"]["series"][0]["exemplars"] = [None, None]
    with pytest.raises(ValueError):
        dst.merge_snapshot(bad)


def test_exemplars_above_threshold_newest_first():
    r = MetricsRegistry()
    h = r.histogram("pio_ex_seconds", buckets=(0.1, 0.25, 1.0))
    _observe_traced(h, 0.05, "fast")
    slow1 = _observe_traced(h, 0.5, "slow-1")
    slow2 = _observe_traced(h, 3.0, "slow-2")
    above = h.exemplars_above(0.25)
    assert [e[0] for e in above] == [slow2, slow1]
    assert all(e[1] > 0.25 for e in above)
    assert h.exemplars_above(5.0) == []


# ---------------------------------------------------------------------------
# SLO breach evidence: exemplars attached + traces pinned
# ---------------------------------------------------------------------------

def test_slo_breach_attaches_exemplars_and_pins_traces():
    reg = MetricsRegistry()
    h = reg.histogram("pio_query_duration_seconds", "q",
                      labelnames=("engine_variant",),
                      buckets=(0.1, 0.25, 1.0))
    # the culprit request rode the ring once, then got buried
    rec = tc.recorder()
    tokens, trace = tracing.start_trace("culprit")
    h.observe(0.9, engine_variant="default")
    tracing.reset_trace(tokens)
    rec.record_span(trace_id=trace.trace_id, span_id="s1",
                    parent_span_id=None, name="POST /queries.json",
                    duration_s=0.9)

    vals = {"bad": 0.0, "total": 0.0}
    spec = SLOSpec(
        objectives=[SLOObjective("lat", "latency", threshold_s=0.25,
                                 budget=0.1)],
        windows=[SLOWindow(10.0, 1.0)], eval_interval_s=5.0)
    eng = SLOEngine(reg, spec, sources={
        "latency": lambda obj: (vals["bad"], vals["total"])})
    t = 0.0
    while t <= 30.0 and not eng.breached():
        vals["total"] += 50
        vals["bad"] += 50
        eng.tick(now=t)
        t += 5.0
    assert eng.breached()

    event = next(e for e in reversed(rec.events())
                 if e["kind"] == "slo_breach")
    assert event["exemplars"] == [trace.trace_id]
    # the evidence is pinned: bury the ring and the trace still resolves
    assert trace.trace_id in rec.pinned_ids()
    for i in range(tc.DEFAULT_TRACE_CAPACITY + 8):
        rec.record_span(trace_id=f"noise-{i}", span_id="s",
                        parent_span_id=None, name="noise", duration_s=0.0)
    found = rec.traces(trace_id=trace.trace_id)
    assert found and found[0]["name"] == "POST /queries.json"


# ---------------------------------------------------------------------------
# flight recorder: configurable rings + pinning bounds
# ---------------------------------------------------------------------------

def test_ring_capacity_env_beats_server_json(monkeypatch, tmp_path):
    conf = tmp_path / "server.json"
    conf.write_text(json.dumps(
        {"trace": {"traceCapacity": 7, "eventCapacity": 5}}))
    monkeypatch.setenv("PIO_SERVER_CONF", str(conf))
    fr = tc.FlightRecorder()
    for i in range(20):
        fr.record_span(trace_id=f"t{i}", span_id="s", parent_span_id=None,
                       name="n", duration_s=0.0)
        fr.record_event("k")
    assert len(fr.traces()) == 7
    assert len(fr.events()) == 5

    monkeypatch.setenv(tc.TRACE_CAPACITY_ENV, "3")
    monkeypatch.setenv(tc.TRACE_EVENT_CAPACITY_ENV, "2")
    fr = tc.FlightRecorder()
    for i in range(20):
        fr.record_span(trace_id=f"t{i}", span_id="s", parent_span_id=None,
                       name="n", duration_s=0.0)
        fr.record_event("k")
    assert len(fr.traces()) == 3
    assert len(fr.events()) == 2

    # malformed knobs fall back to the default, never crash construction
    monkeypatch.setenv(tc.TRACE_CAPACITY_ENV, "not-a-number")
    monkeypatch.setenv(tc.TRACE_EVENT_CAPACITY_ENV, "-4")
    fr = tc.FlightRecorder()
    assert fr._traces.maxlen == tc.DEFAULT_TRACE_CAPACITY
    assert fr._events.maxlen == tc.DEFAULT_EVENT_CAPACITY


def test_pin_survives_eviction_and_stays_bounded():
    fr = tc.FlightRecorder(capacity=4)
    fr.record_span(trace_id="keep", span_id="s0", parent_span_id=None,
                   name="slow", duration_s=1.0)
    fr.pin("keep")
    fr.pin(None)                              # no-op, never raises
    for i in range(10):
        fr.record_span(trace_id=f"noise-{i}", span_id="s",
                       parent_span_id=None, name="n", duration_s=0.0)
    assert all(t["traceId"] != "keep" for t in fr.traces())  # ring evicted
    assert [t["name"] for t in fr.traces(trace_id="keep")] == ["slow"]
    # spans of a pinned trace recorded AFTER the pin are retained too
    fr.record_span(trace_id="keep", span_id="s1", parent_span_id=None,
                   name="later", duration_s=0.5)
    for i in range(10):
        fr.record_span(trace_id=f"more-{i}", span_id="s",
                       parent_span_id=None, name="n", duration_s=0.0)
    assert {t["name"] for t in fr.traces(trace_id="keep")} == \
        {"slow", "later"}
    # FIFO-bounded pin table
    for i in range(tc.DEFAULT_PIN_CAPACITY + 10):
        fr.pin(f"pin-{i}")
    assert len(fr.pinned_ids()) == tc.DEFAULT_PIN_CAPACITY
    assert "keep" not in fr.pinned_ids()


# ---------------------------------------------------------------------------
# capacity ledger
# ---------------------------------------------------------------------------

def test_live_buffer_walk_is_ttl_memoized():
    import jax.numpy as jnp

    pinned = jnp.ones((64, 64), jnp.float32)    # keep a live array around
    jax_stats.live_buffer_stats(ttl_s=0.0)      # force a fresh walk
    walks0 = jax_stats.live_buffer_walks()
    a = jax_stats.live_buffer_stats(ttl_s=60.0)
    b = jax_stats.live_buffer_stats(ttl_s=60.0)
    assert jax_stats.live_buffer_walks() == walks0   # cache hits, no walk
    assert a == b and a[0] >= pinned.nbytes
    assert jax_stats.live_buffer_stats(ttl_s=0.0)
    assert jax_stats.live_buffer_walks() == walks0 + 1
    assert jax_stats.device_watermark_bytes() >= a[0]


def test_unit_capacity_agrees_with_scorer_factor_bytes():
    class FakeScorer:
        _rotation = np.zeros((6, 6), np.float32)

        def status(self):
            return {"factorBytes": 4096, "exactBytes": 128,
                    "mode": "int8"}

    factors = np.zeros((100, 8), np.float32)
    model = SimpleNamespace(_resident=(None, factors),
                            _scorer_cache=(None, None, FakeScorer()))
    unit = SimpleNamespace(
        result=SimpleNamespace(models=[model]),
        instance=SimpleNamespace(id="ei-1"), release_version=3)

    entry = model_capacity(model)
    assert entry["modelFactorBytes"] == factors.nbytes
    assert entry["scorerFactorBytes"] == 4096
    assert entry["shortlistBytes"] == FakeScorer._rotation.nbytes
    assert entry["residentBytes"] == (factors.nbytes + 4096
                                      + FakeScorer._rotation.nbytes)

    cap = unit_capacity(unit, "active")
    assert cap["role"] == "active" and cap["release"] == 3
    # the cross-check contract: scorerBytes IS the sum of the scorers'
    # factorBytes, the number /deploy/status.json echoes
    assert cap["scorerBytes"] == 4096
    assert cap["residentBytes"] == entry["residentBytes"]

    # a bare unit (no scorer cache, nothing resident) reports zeros,
    # never raises
    bare = unit_capacity(SimpleNamespace(), "standby")
    assert bare["residentBytes"] == 0 and bare["models"] == []


async def test_capacity_endpoint_reports_units():
    from test_query_batcher import make_server

    server = make_server()
    c = TestClient(TestServer(server.app))
    await c.start_server()
    try:
        resp = await c.post("/queries.json", json={"user": "u1", "num": 3})
        assert resp.status == 200
        resp = await c.get("/capacity.json")
        assert resp.status == 200
        doc = await resp.json()
    finally:
        await c.close()
    assert set(doc["process"]) >= {"deviceBytes", "deviceArrays",
                                   "deviceWatermarkBytes", "hostRssBytes"}
    roles = [u["role"] for u in doc["units"]]
    assert roles == ["active"]
    unit = doc["units"][0]
    assert unit["residentBytes"] == \
        sum(m["residentBytes"] for m in unit["models"])
    # the gauges ride the same roll-up
    assert server.registry.get("pio_capacity_device_bytes") is not None
    samples = server.registry.get(
        "pio_capacity_unit_resident_bytes").samples()
    assert [labels["role"] for labels, _v in samples] == ["active"]
    assert samples[0][1] == unit["residentBytes"]
    # a unit-less document (event server shape) still answers
    assert capacity_document(None)["units"] == []


# ---------------------------------------------------------------------------
# per-request anatomy under a concurrent burst
# ---------------------------------------------------------------------------

async def test_stage_sums_approximate_wall_under_burst():
    import asyncio

    from test_query_batcher import make_server

    server = make_server()
    c = TestClient(TestServer(server.app))
    await c.start_server()
    n_clients, per_client = 6, 4
    try:
        async def one(i):
            resp = await c.post("/queries.json",
                                json={"user": f"u{i % 40}", "num": 5})
            assert resp.status == 200

        await asyncio.gather(*[one(i) for i in range(n_clients)])  # warm
        await asyncio.gather(
            *[one(i) for i in range(n_clients * per_client)])
    finally:
        await c.close()

    total = n_clients + n_clients * per_client
    stage_hist = server.registry.get(STAGE_HISTOGRAM)
    assert stage_hist is not None
    # every request passes through every wall stage exactly once
    for stage in SERVING_WALL_STAGES + SERVING_COST_STAGES:
        assert stage_hist.count(path="serving", stage=stage) == total, stage
    # and the elapsed stages sum to ~the measured request wall (cost
    # stages are amortized shares, deliberately outside the identity)
    wall = server.registry.get("pio_query_duration_seconds").total_sum()
    stages = sum(stage_hist.sum_(path="serving", stage=s)
                 for s in SERVING_WALL_STAGES)
    assert stages <= wall * 1.5 + 0.05, (stages, wall)
    assert stages >= wall * 0.25 - 0.05, (stages, wall)


@pytest.mark.parametrize("switch", ["PIO_TRACING", "PIO_ANATOMY"])
async def test_query_server_answers_with_a_plane_switched_off(
        monkeypatch, switch):
    """Either kill switch leaves serving whole: concurrent queries
    answer through the batcher, the request metrics still count, and
    the plane that is off records nothing."""
    import asyncio

    from test_query_batcher import make_server

    monkeypatch.setenv(switch, "0")
    server = make_server()
    c = TestClient(TestServer(server.app))
    await c.start_server()
    try:
        out = await asyncio.gather(*[
            c.post("/queries.json", json={"user": f"u{i % 9}", "num": 3})
            for i in range(8)])
        for resp in out:
            assert resp.status == 200
            assert len((await resp.json())["itemScores"]) == 3
    finally:
        await c.close()
    assert server.registry.get(
        "pio_query_duration_seconds").total_count() == 8
    stage_hist = server.registry.get(STAGE_HISTOGRAM)
    stages = stage_hist.total_count() if stage_hist is not None else 0
    if switch == "PIO_ANATOMY":
        assert stages == 0
        assert tc.recorder().traces()          # tracing stays on
    else:
        assert tc.recorder().traces() == []


def test_ingest_anatomy_observes_every_submit():
    from predictionio_tpu.data.write_buffer import WriteBuffer
    from test_faults import ev

    class MemStore:
        def insert_batch(self, events, app_id, channel_id=None):
            return [f"id-{i}" for i in range(len(events))]

        def insert_batch_idempotent(self, events, app_id,
                                    channel_id=None):
            return self.insert_batch(events, app_id, channel_id)

    store = MemStore()
    reg = MetricsRegistry()
    buf = WriteBuffer(store_fn=lambda: store, linger_s=0.02, registry=reg)
    futures = [buf.submit([ev(i)], 7) for i in range(20)]
    for f in futures:
        f.result(timeout=10)
    buf.stop()
    hist = reg.get(STAGE_HISTOGRAM)
    assert hist is not None
    # one flush_wait + one commit observation per submit, coalescing
    # notwithstanding
    assert hist.count(path="ingest", stage="flush_wait") == 20
    assert hist.count(path="ingest", stage="commit") == 20
    assert hist.sum_(path="ingest", stage="commit") > 0.0


async def test_slow_query_exemplar_resolves_to_pinned_trace():
    """The acceptance walk: a forced-slow query lands an exemplar in
    /metrics whose trace id resolves via the flight recorder to a trace
    whose anatomy spans name the dominating stage."""
    import time as _time

    from predictionio_tpu.engines.recommendation import (
        ALSAlgorithm, AlgorithmParams, RecommendationServing,
    )
    from test_query_batcher import make_als_model, make_server

    class SlowServing(RecommendationServing):
        def serve(self, query, predictions):
            _time.sleep(0.06)
            return super().serve(query, predictions)

    server = make_server(algorithms=[ALSAlgorithm(AlgorithmParams())],
                         models=[make_als_model()], serving=SlowServing())
    c = TestClient(TestServer(server.app))
    await c.start_server()
    try:
        resp = await c.post("/queries.json", json={"user": "u1", "num": 3})
        assert resp.status == 200
        resp = await c.get("/metrics")
        text = await resp.text()
    finally:
        await c.close()

    parse_exposition(text)                 # exemplars never break parsing
    tids = [m.group(2) for m in
            (EXEMPLAR_LINE.match(ln) for ln in text.splitlines())
            if m is not None
            and m.group(1) == "pio_query_duration_seconds_bucket"
            and float(m.group(3)) >= 0.06]
    assert tids, "slow query left no exemplar in /metrics"
    tid = tids[-1]
    records = tc.recorder().traces(trace_id=tid)
    assert records, "exemplar trace id did not resolve in the recorder"
    spans = records[-1]["spans"]
    anatomy_spans = {k: v for k, v in spans.items()
                     if k.startswith(anatomy.TRACE_STAGE_PREFIX)}
    assert anatomy_spans, spans
    # the forced sleep makes `serve` the dominating wall stage
    wall_spans = {s: anatomy_spans.get(anatomy.TRACE_STAGE_PREFIX + s, 0.0)
                  for s in SERVING_WALL_STAGES}
    assert max(wall_spans, key=wall_spans.get) == "serve", wall_spans
    # pinning it keeps the evidence past the ring, like the SLO engine
    tc.recorder().pin(tid)
    for i in range(tc.DEFAULT_TRACE_CAPACITY + 8):
        tc.recorder().record_span(trace_id=f"noise-{i}", span_id="s",
                                  parent_span_id=None, name="n",
                                  duration_s=0.0)
    assert tc.recorder().traces(trace_id=tid)


# ---------------------------------------------------------------------------
# tsdb exemplar carriage
# ---------------------------------------------------------------------------

def _hist_snap(values, exemplars=None):
    """A cumulative registry snapshot with one histogram series (and
    explicit exemplar slots, timestamps controlled by the test)."""
    reg = MetricsRegistry()
    h = reg.histogram("pio_t_seconds", "lat", buckets=(0.1, 0.2, 0.4))
    for v in values:
        h.observe(v)
    snap = reg.to_snapshot()
    if exemplars is not None:
        snap["pio_t_seconds"]["series"][0]["exemplars"] = exemplars
    return snap


def test_merge_exemplar_slots_semantics():
    a = [["A", 0.05, 100.0], None, None, None]
    b = [["B", 0.06, 200.0], None, ["C", 0.3, 150.0], None]
    merged = merge_exemplar_slots([list(e) if e else None for e in a], b)
    assert merged[0][0] == "B" and merged[2][0] == "C"
    # src older than dst loses
    again = merge_exemplar_slots(merged, [["D", 0.04, 50.0], None, None,
                                          None])
    assert again[0][0] == "B"
    # persisted data is never worth raising over: mismatched slot counts
    # keep the destination untouched
    assert merge_exemplar_slots(merged, [None, None]) == merged
    assert merge_exemplar_slots([], b)[0][0] == "B"
    assert merge_exemplar_slots(merged, None) == merged


def test_tsdb_exemplars_survive_roll_and_compaction(tmp_path):
    d = str(tmp_path / "db")
    db = TSDB(d, compact_min_segments=2)
    db.append_snapshot(
        _hist_snap([0.05], [["A", 0.05, 100.0], None, None, None]),
        ts_ms=1000)
    db.append_snapshot(
        _hist_snap([0.05, 0.3],
                   [["B", 0.06, 200.0], None, ["C", 0.3, 150.0], None]),
        ts_ms=2000)
    db.roll()
    db.append_snapshot(
        _hist_snap([0.05, 0.3, 0.3],
                   [["B", 0.06, 200.0], None, ["C", 0.31, 300.0], None]),
        ts_ms=3000)
    db.close()

    def slots(dirpath):
        (info,) = TSDBReader([dirpath]).series("pio_t_seconds")
        return info.exemplars

    got = slots(d)
    assert got[0][:2] == ["B", 0.06]          # newest-per-bucket across
    assert got[2][:2] == ["C", 0.31]          # records AND segments
    assert got[1] is None and got[3] is None

    db2 = TSDB(d, compact_min_segments=2)
    assert db2.compact(now_ms=10_000) >= 2
    db2.close()
    assert slots(d) == got                    # compaction re-emits them


# ---------------------------------------------------------------------------
# pio analyze report math
# ---------------------------------------------------------------------------

class FakeReader:
    """histogram_window stub: stage -> (layout, counts, total, sum)."""

    def __init__(self, windows):
        self.windows = windows

    def histogram_window(self, name, labels=None, since_ms=None,
                         until_ms=None):
        assert name == STAGE_HISTOGRAM
        return self.windows.get(labels["stage"])


LAYOUT = (0.005, 0.05, 0.5)


def _window(counts, sum_s):
    return (LAYOUT, list(counts), sum(counts), sum_s)


def test_stage_stats_and_composition():
    reader = FakeReader({
        "queue_wait": _window([90, 10, 0, 0], 0.3),
        "device": _window([0, 80, 20, 0], 4.0),
        "serve": _window([100, 0, 0, 0], 0.1),
        "pad_share": _window([100, 0, 0, 0], 0.05),
    })
    stats = stage_stats(reader, "serving")
    assert set(stats) == {"queue_wait", "device", "serve", "pad_share"}
    assert stats["device"]["count"] == 100
    assert stats["device"]["mean"] == pytest.approx(0.04)
    assert stats["device"]["p99"] > stats["device"]["p50"] > 0

    comp = composition(stats, "serving", which="mean")
    # cost stages are excluded from the wall identity
    assert "pad_share" not in comp
    assert sum(comp.values()) == pytest.approx(1.0)
    assert max(comp, key=comp.get) == "device"
    assert composition({}, "serving") == {}


def test_regression_diff_names_the_planted_stage():
    before = FakeReader({
        "queue_wait": _window([95, 5, 0, 0], 0.2),
        "device": _window([0, 100, 0, 0], 2.0),
        "serve": _window([100, 0, 0, 0], 0.1),
    })
    after = FakeReader({
        # planted regression: queue_wait mean exploded 2ms -> 100ms
        "queue_wait": _window([0, 20, 80, 0], 10.0),
        "device": _window([0, 100, 0, 0], 2.1),
        "serve": _window([100, 0, 0, 0], 0.1),
    })
    b = stage_stats(before, "serving")
    a = stage_stats(after, "serving")
    diff = regression_diff(b, a)
    assert diff["stage"] == "queue_wait"
    assert diff["deltaMeanS"] == pytest.approx(0.098)
    assert diff["beforeMeanS"] == pytest.approx(0.002)
    assert diff["afterMeanS"] == pytest.approx(0.1)
    assert set(diff["deltas"]) == {"queue_wait", "device", "serve"}
    assert regression_diff({}, {}) is None
    assert regression_diff(b, {"novel": {"mean": 1.0}}) is None
