"""The workload simulator (predictionio_tpu/loadtest/): synthetic
population, declarative scenarios, the shared open-loop harness, the
exactly-once audit, the invariant engine — and one smoke-scale storm
through a real LocalFleet.

Covers the ISSUE's acceptance paths:
  * samplers are deterministic under seed with EXACT distribution
    assertions (Zipf frequencies vs the analytic pmf, arrival counts
    vs the integrated intensity);
  * scenario validation is strict and path-labelled — unknown keys,
    unknown incident kinds, out-of-range times, bad mixes all REJECT;
  * drive_open_loop accounts every offered item (acked / failed /
    dropped), paces by schedule, weights batches, and times out
    without hanging;
  * audit_exactly_once catches planted missing / duplicate / extra
    ids, including a duplicate leaked ACROSS partitions (the routing
    bug row counts cannot see);
  * the invariant engine's verdicts;
  * a smoke-scale storm against a live fleet: mixed lanes + mid-run
    retrain, zero dropped acks, exactly-once by audit, registry
    converged.  The full-scale chaos storm is @slow (bench's chaos
    leg runs it judged).
"""

import concurrent.futures
import json
import threading
import time

import numpy as np
import pytest

from predictionio_tpu.data.event import Event
from predictionio_tpu.loadtest.harness import (
    LatencyLedger, OpenLoopResult, drive_open_loop,
)
from predictionio_tpu.loadtest.invariants import InvariantEngine
from predictionio_tpu.loadtest.population import (
    Population, ZipfSampler, arrival_offsets, diurnal_rate,
)
from predictionio_tpu.loadtest.scenario import (
    Scenario, ScenarioError, example_scenario, example_tenant_scenario,
)
from predictionio_tpu.storage.audit import audit_exactly_once


# ---------------------------------------------------------------------------
# samplers: deterministic under seed, exact distributions
# ---------------------------------------------------------------------------

def test_zipf_sampler_deterministic_under_seed():
    a = ZipfSampler(500, alpha=1.1, seed=42)
    b = ZipfSampler(500, alpha=1.1, seed=42)
    assert np.array_equal(a.sample(2048), b.sample(2048))
    # a different seed is a different sequence
    c = ZipfSampler(500, alpha=1.1, seed=43)
    assert not np.array_equal(a.sample(2048), c.sample(2048))


def test_zipf_sampler_matches_analytic_pmf():
    """Empirical head frequencies within 5 sigma of the EXACT pmf."""
    n, draws = 50, 40_000
    s = ZipfSampler(n, alpha=1.1, seed=7)
    pmf = [s.probability(r) for r in range(n)]
    assert abs(sum(pmf) - 1.0) < 1e-9
    assert all(pmf[r] > pmf[r + 1] for r in range(n - 1))
    out = s.sample(draws)
    assert out.min() >= 0 and out.max() < n
    counts = np.bincount(out, minlength=n)
    for rank in (0, 1, 2, 5):
        p = pmf[rank]
        sigma = (draws * p * (1 - p)) ** 0.5
        assert abs(counts[rank] - draws * p) <= 5 * sigma, (
            rank, counts[rank], draws * p)


def test_zipf_sampler_rejects_empty_catalog():
    with pytest.raises(ValueError):
        ZipfSampler(0)


def test_diurnal_rate_shape():
    base, period = 100.0, 40.0
    assert diurnal_rate(0.0, base, 0.5, period) == pytest.approx(base)
    # peak at a quarter period (sin max), trough clamped at zero
    assert diurnal_rate(period / 4, base, 0.5, period) \
        == pytest.approx(base * 1.5)
    assert diurnal_rate(3 * period / 4, base, 1.0, period) \
        == pytest.approx(0.0)


def test_arrival_offsets_deterministic_sorted_and_bounded():
    a = arrival_offsets(6.0, 150.0, 0.5, 6.0, seed=11)
    b = arrival_offsets(6.0, 150.0, 0.5, 6.0, seed=11)
    assert np.array_equal(a, b)
    assert np.all(np.diff(a) >= 0)
    assert a.min() >= 0.0 and a.max() < 6.0
    assert len(arrival_offsets(0.0, 100.0)) == 0
    assert len(arrival_offsets(5.0, 0.0)) == 0


def test_arrival_offsets_count_matches_integrated_rate():
    """Flat curve: the count is Poisson(rate * duration) — assert
    within 6 sigma of the exact mean."""
    rate, duration = 300.0, 5.0
    n = len(arrival_offsets(duration, rate, amplitude=0.0, seed=3))
    expected = rate * duration
    assert abs(n - expected) <= 6 * expected ** 0.5, (n, expected)


def test_population_deterministic_payloads_and_lazy_sessions():
    a = Population(10_000, 500, seed=9)
    b = Population(10_000, 500, seed=9)
    assert a.active_users == 0
    def payload(pop, i):
        uid = pop.next_user()
        d = pop.event_for(uid, i * 0.1).to_dict()
        d.pop("creationTime", None)    # wall-clock, not seeded
        return uid, d

    seq_a = [payload(a, i) for i in range(64)]
    seq_b = [payload(b, i) for i in range(64)]
    assert seq_a == seq_b          # identical payloads under one seed
    # memory is O(active users), not O(population)
    assert 0 < a.active_users <= 64


def test_population_event_times_monotone_per_user():
    pop = Population(100, 50, seed=1)
    uid = pop.next_user()
    times = [pop.event_for(uid, t).event_time
             for t in (0.5, 0.2, 0.2, 3.0)]   # at_s even goes BACKWARDS
    assert all(t1 > t0 for t0, t1 in zip(times, times[1:]))


def test_population_feedback_closes_the_served_loop():
    pop = Population(100, 50, seed=2)
    uid = pop.next_user()
    # nothing served yet -> nothing to react to
    assert pop.feedback_for(uid, 1.0) is None
    pop.record_recommendations(uid, ["i3", "i7"])
    ev = pop.feedback_for(uid, 2.0)
    assert ev is not None
    assert ev.target_entity_id in ("i3", "i7")
    assert ev.properties["feedback"] is True
    assert ev.properties["rating"] == 5.0


# ---------------------------------------------------------------------------
# scenario validation: strict, path-labelled
# ---------------------------------------------------------------------------

def test_scenario_example_round_trips():
    sc = Scenario.from_dict(example_scenario())
    assert sc.name == "example-chaos"
    assert sc.mix_events + sc.mix_queries + sc.mix_feedback \
        == pytest.approx(1.0)
    assert [i.kind for i in sc.incidents] == ["kill_replica", "retrain"]
    # to_dict -> from_dict is stable
    again = Scenario.from_dict(sc.to_dict())
    assert again.to_dict() == sc.to_dict()


def test_scenario_load_from_file(tmp_path):
    p = tmp_path / "storm.json"
    p.write_text(json.dumps(example_scenario()))
    assert Scenario.load(str(p)).name == "example-chaos"
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    with pytest.raises(ScenarioError, match="not valid JSON"):
        Scenario.load(str(bad))


@pytest.mark.parametrize("patch,path_hint", [
    ({"bogusKey": 1}, "bogusKey"),
    ({"population": "many"}, r"\$\.population"),
    ({"population": 0}, r"\$\.population"),
    ({"amplitude": 1.5}, r"\$\.amplitude"),
    ({"backend": "oracle"}, r"\$\.backend"),
    ({"mix": {"events": 0.5, "queries": 0.5, "feedback": 0.5}}, r"\$\.mix"),
    ({"mix": {"events": 0.5, "queries": 0.5, "surprise": 0.0}}, "surprise"),
    ({"incidents": [{"kind": "meteor", "atS": 1.0}]}, "kind"),
    ({"incidents": [{"kind": "retrain", "atS": 999.0}]}, "past the"),
    ({"incidents": [{"kind": "retrain", "atS": 1.0,
                     "restartAfterS": 2.0}]}, "only kill_replica"),
    ({"incidents": [{"kind": "kill_replica", "atS": 1.0,
                     "target": 9}]}, "does not exist"),
    ({"incidents": [{"kind": "kill_replica", "atS": 1.0,
                     "blast": True}]}, "unknown key"),
])
def test_scenario_rejections_name_the_path(patch, path_hint):
    doc = dict(example_scenario())
    doc.update(patch)
    with pytest.raises(ScenarioError, match=path_hint):
        Scenario.from_dict(doc)


def test_tenant_scenario_round_trips():
    sc = Scenario.from_dict(example_tenant_scenario())
    assert [t.name for t in sc.tenants] == ["alpha", "beta", "gamma"]
    assert sc.tenants[1].rate_scale == pytest.approx(0.5)
    assert sc.tenants[2].item_alpha == pytest.approx(0.9)
    assert sc.incidents[0].tenant == "beta"
    again = Scenario.from_dict(sc.to_dict())
    assert again.to_dict() == sc.to_dict()
    # tenant-less scenarios keep the key out of their dict entirely
    assert "tenants" not in Scenario.from_dict(example_scenario()).to_dict()


@pytest.mark.parametrize("patch,path_hint", [
    ({"tenants": [{"name": "a"}, {"name": "a"}]}, "unique"),
    ({"tenants": [{"name": ""}]}, r"\$\.tenants\[0\]\.name"),
    ({"tenants": [{"name": "a/b"}]}, r"\$\.tenants\[0\]\.name"),
    ({"tenants": [{"name": "a", "rateScale": 0}]}, "rateScale"),
    ({"tenants": [{"name": "a", "surprise": 1}]}, "unknown key"),
    ({"tenants": [{"name": "a"}],
      "incidents": [{"kind": "burn_slo", "atS": 1.0,
                     "tenant": "ghost"}]}, "not in"),
    ({"incidents": [{"kind": "retrain", "atS": 1.0,
                     "tenant": "a"}]}, "only burn_slo"),
])
def test_tenant_scenario_rejections_name_the_path(patch, path_hint):
    doc = dict(example_scenario())
    doc.update(patch)
    with pytest.raises(ScenarioError, match=path_hint):
        Scenario.from_dict(doc)


# ---------------------------------------------------------------------------
# the open-loop harness
# ---------------------------------------------------------------------------

def _done(value=None):
    f = concurrent.futures.Future()
    f.set_result(value)
    return f


def test_latency_ledger_percentile_is_the_bench_estimator():
    led = LatencyLedger()
    for s in (0.4, 0.1, 0.3, 0.2):
        led.record(s)
    # sorted-index estimator: sorted[int(q/100 * n)], clamped
    assert led.percentile_ms(50) == pytest.approx(300.0)
    assert led.percentile_ms(0) == pytest.approx(100.0)
    assert led.percentile_ms(99) == pytest.approx(400.0)
    assert led.mean_ms() == pytest.approx(250.0)
    assert LatencyLedger().percentile_ms(99) == 0.0


def test_drive_open_loop_accounts_everything():
    acked_items = []
    res = drive_open_loop(
        list(range(10)), lambda i: _done(i),
        max_outstanding=4, timeout_s=10.0,
        on_ack=lambda item, fut: acked_items.append(item))
    assert (res.offered, res.acked, res.failed) == (10, 10, 0)
    assert res.dropped == 0 and not res.timed_out
    assert sorted(acked_items) == list(range(10))
    assert len(res.ledger) == 10
    d = res.as_dict()
    assert d["dropped"] == 0 and d["ack_p99_ms"] >= 0.0


def test_drive_open_loop_weights_batches_as_events():
    batches = [["a"] * 5, ["b"] * 3]
    res = drive_open_loop(batches, lambda b: _done(b),
                          max_outstanding=2, timeout_s=5.0, weight=len)
    assert res.offered == 8 and res.acked == 8


def test_drive_open_loop_counts_failures_not_drops():
    def submit(i):
        if i % 2:
            f = concurrent.futures.Future()
            f.set_exception(RuntimeError("boom"))
            return f
        return _done(i)

    res = drive_open_loop(list(range(6)), submit,
                          max_outstanding=8, timeout_s=5.0)
    assert (res.acked, res.failed, res.dropped) == (3, 3, 0)
    # a submit() that raises is a failure too, not a hang
    def explode(_i):
        raise RuntimeError("no")
    res = drive_open_loop([1, 2], explode, max_outstanding=2, timeout_s=5.0)
    assert (res.offered, res.failed, res.dropped) == (2, 2, 0)


def test_drive_open_loop_paces_by_schedule():
    t0 = time.perf_counter()
    res = drive_open_loop(["x", "y"], lambda i: _done(i),
                          max_outstanding=4, timeout_s=5.0,
                          schedule=[0.0, 0.35])
    assert time.perf_counter() - t0 >= 0.35
    assert res.acked == 2


def test_drive_open_loop_window_backpressures():
    """max_outstanding=1 with deferred acks: everything still lands."""
    pool = concurrent.futures.ThreadPoolExecutor(2)
    try:
        res = drive_open_loop(
            list(range(8)),
            lambda i: pool.submit(time.sleep, 0.01),
            max_outstanding=1, timeout_s=10.0)
        assert (res.acked, res.dropped) == (8, 0)
    finally:
        pool.shutdown()


def test_drive_open_loop_times_out_and_reports_drops():
    res = drive_open_loop(
        [1, 2, 3], lambda i: concurrent.futures.Future(),  # never resolves
        max_outstanding=8, timeout_s=0.4)
    assert res.timed_out
    assert res.dropped == 3 and res.acked == 0


# ---------------------------------------------------------------------------
# the exactly-once audit
# ---------------------------------------------------------------------------

def _ev(i, eid=None):
    return Event(event="rate", entity_type="user", entity_id=f"u{i}",
                 target_entity_type="item", target_entity_id=f"i{i}",
                 properties={"rating": 3.0}, event_id=eid)


@pytest.fixture
def plain_store():
    from predictionio_tpu.storage.sqlite_backend import (
        SqliteClient, SqliteEvents,
    )
    client = SqliteClient(":memory:")
    store = SqliteEvents(client)
    store.init_channel(1)
    yield store
    client.close()


def test_audit_clean_parity(plain_store):
    ids = plain_store.insert_batch([_ev(i) for i in range(12)], 1)
    rep = audit_exactly_once(plain_store, 1, ids)
    assert rep.ok
    assert (rep.expected, rep.found) == (12, 12)
    assert rep.partitions == {-1: 12}
    assert "exactly-once OK" in rep.summary()
    assert rep.as_dict()["ok"] is True


def test_audit_catches_missing_and_extra(plain_store):
    ids = plain_store.insert_batch([_ev(i) for i in range(4)], 1)
    # acked-but-absent: the emitter believes in an id the store lost
    rep = audit_exactly_once(plain_store, 1, ids + ["ghost-1"])
    assert not rep.ok and rep.missing == ["ghost-1"] and not rep.extras
    # present-but-never-acked: a write the emitter never made
    plain_store.insert(_ev(99, eid="stowaway-1"), 1)
    rep = audit_exactly_once(plain_store, 1, ids)
    assert not rep.ok and rep.extras == ["stowaway-1"]
    assert "VIOLATED" in rep.summary()


def test_audit_catches_cross_partition_duplicate(tmp_path):
    """The routing bug row counts can't see: one acked event present in
    TWO partitions. Per-partition scans catch it."""
    from predictionio_tpu.storage.partitioned import (
        PartitionedEvents, SqlitePartitions,
    )
    store = PartitionedEvents(
        SqlitePartitions(str(tmp_path / "pio.db")), initial_count=2)
    try:
        store.init_channel(1)
        ids = store.insert_batch([_ev(i) for i in range(10)], 1)
        rep = audit_exactly_once(store, 1, ids)
        assert rep.ok
        assert sorted(rep.partitions) == [0, 1]
        assert sum(rep.partitions.values()) == 10
        # plant the same id in BOTH partitions, ledger acks it once
        store.partition_store(0).insert(_ev(77, eid="dup-77"), 1)
        store.partition_store(1).insert(_ev(77, eid="dup-77"), 1)
        rep = audit_exactly_once(store, 1, ids + ["dup-77"])
        assert not rep.ok and rep.duplicates == ["dup-77"]
    finally:
        store.close()


# ---------------------------------------------------------------------------
# the invariant engine
# ---------------------------------------------------------------------------

class _Rel:
    def __init__(self, version, status):
        self.version, self.status = version, status


class _Rels:
    def __init__(self, rels):
        self._rels = rels

    def get_all(self):
        return self._rels


class _Cycle:
    def __init__(self, outcome):
        self.outcome = outcome


def test_invariant_engine_verdicts():
    eng = InvariantEngine()
    clean = OpenLoopResult(offered=5, acked=5, failed=0, wall_s=1.0,
                           ledger=LatencyLedger())
    leaky = OpenLoopResult(offered=5, acked=3, failed=0, wall_s=1.0,
                           ledger=LatencyLedger())
    assert eng.check_open_loop("no_dropped_acks", clean)
    assert eng.check_registry_converged(
        _Rels([_Rel(1, "RETIRED"), _Rel(2, "LIVE")]))
    assert eng.check_retrain_promoted([_Cycle("promoted")])
    assert eng.check_latency("ack_p99_bound", 12.0, 100.0)
    assert eng.check_freshness(10, 0.5, 30.0)
    assert eng.ok and not eng.failures()

    assert not eng.check_open_loop("no_dropped_acks", leaky)
    assert not eng.check_registry_converged(
        _Rels([_Rel(1, "LIVE"), _Rel(2, "LIVE")]))
    assert not eng.check_retrain_promoted([_Cycle("rolled_back")])
    assert not eng.check_latency("ack_p99_bound", 500.0, 100.0)
    assert not eng.check_freshness(0, None, 30.0)
    assert not eng.ok
    assert {r.name for r in eng.failures()} == {
        "no_dropped_acks", "registry_one_live",
        "retrain_promoted_mid_run", "ack_p99_bound", "freshness_foldin"}
    # every verdict is on the report, ok and violated alike
    assert len(eng.report()) == 10


# ---------------------------------------------------------------------------
# the storm, smoke scale: real fleet, mixed lanes, mid-run retrain
# ---------------------------------------------------------------------------

def _storm(tmp_path, doc, **run_kw):
    from predictionio_tpu.loadtest.fleet import LocalFleet
    from predictionio_tpu.loadtest.simulator import run_storm

    sc = Scenario.from_dict(doc)
    fleet = LocalFleet(str(tmp_path / "fleet"), replicas=sc.replicas,
                       partitions=sc.partitions, backend=sc.backend)
    try:
        fleet.start()
        return run_storm(sc, fleet, **run_kw)
    finally:
        fleet.stop()


def test_storm_smoke_mixed_lanes_retrain(tmp_path):
    report = _storm(tmp_path, {
        "name": "smoke", "population": 120, "items": 40,
        "durationS": 3.0, "seed": 5, "baseRate": 30.0, "amplitude": 0.4,
        "mix": {"events": 0.6, "queries": 0.3, "feedback": 0.1},
        "replicas": 2, "partitions": 2, "backend": "sqlite",
        "maxOutstanding": 64,
        "incidents": [{"kind": "retrain", "atS": 1.0}],
    }, check_freshness=False)
    assert report["ok"], report["invariants"]
    lanes = report["lanes"]
    assert lanes["events"]["acked"] > 0
    assert lanes["queries"]["acked"] > 0
    assert all(l["dropped"] == 0 for l in lanes.values())
    assert report["audit"]["ok"], report["audit"]["summary"]
    assert any(c["outcome"] == "promoted" for c in report["cycles"])
    assert report["active_users"] > 0


def test_storm_chaos_smoke_parquet(tmp_path):
    """The chaos storm shrunken into tier-1: on the parquet backend a
    replica is killed and restarted and a compaction crashes mid-storm,
    with zero dropped acks and exactly-once by audit."""
    from predictionio_tpu.obs.loadtest_stats import loadtest_incidents
    from predictionio_tpu.obs.registry import MetricsRegistry

    registry = MetricsRegistry()
    report = _storm(tmp_path, {
        "name": "chaos-smoke", "population": 100, "items": 40,
        "durationS": 5.0, "seed": 11, "baseRate": 25.0, "amplitude": 0.3,
        "mix": {"events": 0.7, "queries": 0.25, "feedback": 0.05},
        "replicas": 2, "partitions": 2, "backend": "parquet",
        "maxOutstanding": 64,
        "incidents": [
            {"kind": "kill_replica", "atS": 1.2, "target": 1,
             "restartAfterS": 1.5},
            {"kind": "kill_compaction", "atS": 2.8},
        ],
    }, check_freshness=False, registry=registry)
    assert report["ok"], report["invariants"]
    fired = loadtest_incidents(registry)
    assert fired.value(kind="kill_replica") == 1
    assert fired.value(kind="kill_compaction") == 1
    lanes = report["lanes"]
    assert lanes["events"]["acked"] > 0 and lanes["queries"]["acked"] > 0
    assert all(lane["dropped"] == 0 for lane in lanes.values())
    assert report["audit"]["ok"], report["audit"]["summary"]
    assert report["audit"]["expected"] > 0


@pytest.mark.slow
def test_storm_full_chaos(tmp_path):
    """Full chaos at test scale: replica kill + restart, compaction
    crash, SLO burn and quality degradation all mid-storm — zero
    dropped acks and exactly-once by audit. Excluded from tier-1
    (-m 'not slow'); `test_storm_chaos_smoke_parquet` is its shrunken
    tier-1 form."""
    report = _storm(tmp_path, {
        "name": "chaos", "population": 2_000, "items": 300,
        "durationS": 10.0, "seed": 13, "baseRate": 80.0,
        "amplitude": 0.5,
        "mix": {"events": 0.7, "queries": 0.25, "feedback": 0.05},
        "replicas": 2, "partitions": 2, "backend": "parquet",
        "maxOutstanding": 128,
        "incidents": [
            {"kind": "kill_replica", "atS": 2.5, "target": 1,
             "restartAfterS": 3.0},
            {"kind": "kill_compaction", "atS": 5.5},
            {"kind": "burn_slo", "atS": 4.0, "durationS": 2.0},
            {"kind": "degrade_quality", "atS": 6.0, "durationS": 2.0},
        ],
    }, check_freshness=False)
    assert report["ok"], report["invariants"]
    assert report["audit"]["ok"], report["audit"]["summary"]


# ---------------------------------------------------------------------------
# the multi-tenant storm: consolidated host, per-tenant lanes, SLO burn
# ---------------------------------------------------------------------------

def test_tenant_storm_burn_sheds_one_tenant_only(tmp_path):
    """The blast-radius verdict e2e: three tenants with independent
    Zipf mixes behind ONE MultiTenantFleet host; an incident burns
    beta's error budget mid-run. Admission must 429 beta (rejections
    counted host-side) while alpha and gamma drop nothing, take zero
    rejections, and hold their p99 — one noisy tenant, zero
    neighbour damage."""
    from predictionio_tpu.loadtest.fleet import MultiTenantFleet
    from predictionio_tpu.loadtest.simulator import run_tenant_storm

    sc = Scenario.from_dict({
        "name": "mt-smoke",
        "durationS": 4.0,
        "seed": 11,
        "baseRate": 25.0,
        "amplitude": 0.3,
        "maxOutstanding": 32,
        "tenants": [
            {"name": "alpha", "population": 300, "items": 80,
             "rateScale": 1.0},
            {"name": "beta", "population": 100, "items": 40,
             "rateScale": 0.6, "itemAlpha": 1.4},
            {"name": "gamma", "population": 500, "items": 120,
             "rateScale": 0.4, "itemAlpha": 0.9},
        ],
        "incidents": [
            {"kind": "burn_slo", "atS": 0.5, "tenant": "beta",
             "durationS": 2.5},
        ],
    })
    fleet = MultiTenantFleet(str(tmp_path / "mtfleet"), sc.tenants)
    try:
        fleet.start()
        report = run_tenant_storm(sc, fleet,
                                  query_p99_bound_ms=5000.0)
    finally:
        fleet.stop()
    assert report["ok"], report["invariants"]
    tenants = report["tenants"]
    assert set(tenants) == {"alpha", "beta", "gamma"}
    # the burned tenant was shed by ADMISSION (host-side 429 count),
    # and nothing anywhere was silently dropped
    assert tenants["beta"]["rejections"] > 0
    assert all(t["dropped"] == 0 for t in tenants.values())
    assert tenants["alpha"]["rejections"] == 0
    assert tenants["gamma"]["rejections"] == 0
    assert tenants["alpha"]["acked"] > 0
    assert tenants["gamma"]["acked"] > 0
    names = {inv["name"] for inv in report["invariants"]}
    assert {"tenant_shed:beta", "tenant_p99:alpha",
            "tenant_p99:gamma"} <= names
    assert "tenant_p99:beta" not in names     # burned: p99 not judged
