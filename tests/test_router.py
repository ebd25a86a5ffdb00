"""The serving-fleet router tier (server/router.py) and the SLO-driven
autoscaler (deploy/fleet.py).

Covers the ISSUE's acceptance paths:
  * WeightedSplitter exactness — the canary error-diffusion discipline
    over N arms (±1 of the exact share over any window), eligibility
    restriction for retries, state/restore round-trip, junk rejection;
  * TrafficSplitter restart fix — the single-arm accumulator persists
    and restores, so a restarted server resumes the mid-stream split;
  * the router proxies with an EXACT spread, forwards ONE trace id
    router -> replica, retries a failed replica on its siblings (no
    user-visible 5xx while any replica serves), ejects after
    consecutive failures and re-admits on recovery, and answers 503 +
    pio_router_dropped_total only when nothing is routable;
  * splitter accumulators survive a ROUTER restart through the durable
    telemetry store (the restart path, end to end through a real
    TelemetryRecorder);
  * fleet-consistent deploy/rollback: sequenced in rank order, aborted
    on first failure, already-cut replicas unwound;
  * drain = zero-drop scale-down: weight to zero first, in-flight runs
    to completion;
  * FleetController: pure decide() (sustain windows, cooldown, bounds,
    burn outranks idle), committed actions with kill points at every
    boundary and recover() converging (chaos harness), and the full
    autoscale e2e — load grows the fleet, idleness shrinks it, ZERO
    dropped queries across both transitions, scaling decisions in the
    flight recorder under one trace id per action.
"""

import asyncio
import json
import time

import pytest
from aiohttp import web
from aiohttp.test_utils import TestClient, TestServer

from predictionio_tpu.deploy.canary import TrafficSplitter
from predictionio_tpu.deploy.fleet import (
    FleetController, FleetSignals, FleetState, decide,
)
from predictionio_tpu.obs.registry import MetricsRegistry
from predictionio_tpu.obs.telemetry import TelemetryRecorder
from predictionio_tpu.obs.trace_context import (
    TRACE_HEADER, TraceContext, recorder,
)
from predictionio_tpu.server.router import Router, WeightedSplitter
from predictionio_tpu.storage.faults import CrashError, set_kill_points
from predictionio_tpu.utils.server_config import (
    FleetConfig, RouterConfig, TelemetryConfig,
)

pytestmark = pytest.mark.anyio


@pytest.fixture(autouse=True)
def _clean_slate():
    recorder().clear()
    set_kill_points([])
    yield
    set_kill_points([])
    recorder().clear()


def _rcfg(**kw):
    kw.setdefault("health_interval_s", 0.05)
    kw.setdefault("health_fail_after", 2)
    kw.setdefault("proxy_retries", 1)
    kw.setdefault("drain_timeout_s", 5.0)
    return RouterConfig(**kw)


class StubReplica:
    """A controllable in-process replica: the readiness surfaces a
    deployed query server exposes, plus switches for every failure
    mode the router must survive."""

    def __init__(self):
        self.breached = False
        self.fail_queries = False
        self.fail_probes = False
        self.fail_deploy = False
        self.hold_s = 0.0
        self.trace_headers = []
        self.deploys = []
        self.rollbacks = []
        self.served = 0
        self.server = None

    def make_app(self):
        app = web.Application()

        async def queries(request):
            self.trace_headers.append(request.headers.get(TRACE_HEADER))
            if self.fail_queries:
                return web.json_response({"message": "boom"}, status=500)
            if self.hold_s:
                await asyncio.sleep(self.hold_s)
            self.served += 1
            return web.json_response({"itemScores": []})

        async def slo(request):
            if self.fail_probes:
                return web.Response(status=503)
            return web.json_response({"breached": self.breached})

        async def status(request):
            if self.fail_probes:
                return web.Response(status=503)
            return web.json_response({"active": {"releaseVersion": 1}})

        async def deploy(request):
            self.deploys.append(await request.json())
            if self.fail_deploy:
                return web.json_response({"message": "bad"}, status=500)
            return web.json_response({"message": "Deployed"})

        async def rollback(request):
            self.rollbacks.append(await request.json())
            return web.json_response({"message": "Rolled back"})

        app.router.add_post("/queries.json", queries)
        app.router.add_get("/slo.json", slo)
        app.router.add_get("/deploy/status.json", status)
        app.router.add_post("/deploy.json", deploy)
        app.router.add_post("/rollback.json", rollback)
        return app

    async def start(self):
        self.server = TestServer(self.make_app())
        await self.server.start_server()
        return f"http://{self.server.host}:{self.server.port}"

    async def close(self):
        if self.server is not None:
            await self.server.close()


async def _stubs(n):
    stubs = [StubReplica() for _ in range(n)]
    urls = [await s.start() for s in stubs]
    return stubs, urls


async def _start_router(router):
    client = TestClient(TestServer(router.app))
    await client.start_server()
    for rank in list(router.replicas):
        assert await router.wait_replica_healthy(rank, timeout_s=10)
    return client


async def _close(client, stubs):
    await client.close()
    for s in stubs:
        await s.close()


async def _wait_for(predicate, timeout_s=10.0, interval_s=0.02):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return True
        await asyncio.sleep(interval_s)
    return predicate()


# ---------------------------------------------------------------------------
# WeightedSplitter (the canary diffusion discipline over N arms)
# ---------------------------------------------------------------------------

def test_weighted_splitter_exact_spread():
    s = WeightedSplitter({0: 1.0, 1: 1.0, 2: 1.0})
    counts = {0: 0, 1: 0, 2: 0}
    for _ in range(300):
        counts[s.route()] += 1
    assert counts == {0: 100, 1: 100, 2: 100}
    s = WeightedSplitter({0: 0.9, 1: 0.1})
    counts = {0: 0, 1: 0}
    for _ in range(1000):
        counts[s.route()] += 1
    assert abs(counts[0] - 900) <= 1 and abs(counts[1] - 100) <= 1


def test_weighted_splitter_window_exactness_any_prefix():
    """±1 of the exact share over ANY window, not just in the limit."""
    s = WeightedSplitter({0: 1.0, 1: 1.0, 2: 1.0, 3: 1.0})
    counts = {a: 0 for a in range(4)}
    for n in range(1, 401):
        counts[s.route()] += 1
        for arm, c in counts.items():
            assert abs(c - n / 4) <= 1, (n, counts)


def test_weighted_splitter_eligibility_and_zero_weight():
    s = WeightedSplitter({0: 1.0, 1: 1.0, 2: 0.0})
    # zero-weight arms never win; eligibility restricts without
    # disturbing the others' credit
    assert all(s.route() in (0, 1) for _ in range(10))
    assert all(s.route(eligible={1}) == 1 for _ in range(5))
    assert s.route(eligible=set()) is None
    assert WeightedSplitter().route() is None
    # a scale event keeps surviving arms' credit
    acc_before = s.state()[0]
    s.set_weights({0: 1.0, 3: 1.0})
    assert s.state()[0] == acc_before and 3 in s.state()


def test_weighted_splitter_state_restore_roundtrip_and_junk():
    s = WeightedSplitter({0: 1.0, 1: 1.0, 2: 1.0})
    for _ in range(7):
        s.route()
    saved = s.state()
    fresh = WeightedSplitter({0: 1.0, 1: 1.0, 2: 1.0})
    fresh.restore(saved)
    assert fresh.state() == saved
    seq_a = [s.route() for _ in range(30)]
    seq_b = [fresh.route() for _ in range(30)]
    assert seq_a == seq_b          # the restored split resumes EXACTLY
    # junk is ignored, never trusted
    fresh.restore({0: "nan-ish", 1: float("nan"), 2: 99.0, "x": 1})
    st = fresh.state()
    assert st[2] != 99.0 and all(abs(v) < 4 for v in st.values())


def test_traffic_splitter_state_restore():
    """The single-arm restart fix: a restored accumulator resumes the
    exact mid-stream split (no ~1/fraction-query skew)."""
    s = TrafficSplitter(0.25)
    routes = [s.route() for _ in range(10)]
    resumed = TrafficSplitter(0.25)
    resumed.restore(s.state())
    expected = [s.route() for _ in range(40)]
    assert [resumed.route() for _ in range(40)] == expected
    assert sum(routes) + sum(expected) == round(50 * 0.25)
    # junk snapshots are ignored
    t = TrafficSplitter(0.5)
    for bad in (None, "x", float("nan"), -0.2, 1.5):
        t.restore(bad)
        assert t.state() == 0.0


# ---------------------------------------------------------------------------
# the router: proxy, spread, trace, health, retries
# ---------------------------------------------------------------------------

async def test_router_proxies_with_exact_spread():
    stubs, urls = await _stubs(3)
    router = Router(_rcfg(), replica_urls=urls)
    client = await _start_router(router)
    try:
        for _ in range(30):
            async with client.post("/queries.json",
                                   json={"user": "u"}) as resp:
                assert resp.status == 200
                assert "itemScores" in await resp.json()
        assert [s.served for s in stubs] == [10, 10, 10]
        for rank in range(3):
            assert router._requests.value(replica=str(rank),
                                          status="200") == 10.0
        # the fleet status surface sees all three in rotation
        async with client.get("/fleet/status.json") as resp:
            doc = await resp.json()
        assert [r["healthy"] for r in doc["replicas"]] == [True] * 3
    finally:
        await _close(client, stubs)


async def test_router_forwards_one_trace_id():
    """Router -> replica is one lineage: the replica receives the SAME
    trace id the caller handed the router."""
    stubs, urls = await _stubs(1)
    router = Router(_rcfg(), replica_urls=urls)
    client = await _start_router(router)
    try:
        ctx = TraceContext.root()
        async with client.post("/queries.json", json={},
                               headers={TRACE_HEADER: ctx.encode()}):
            pass
        forwarded = TraceContext.decode(stubs[0].trace_headers[-1])
        assert forwarded is not None
        assert forwarded.trace_id == ctx.trace_id
    finally:
        await _close(client, stubs)


async def test_router_retries_failures_ejects_and_readmits():
    stubs, urls = await _stubs(2)
    router = Router(_rcfg(health_interval_s=0.2), replica_urls=urls)
    client = await _start_router(router)
    try:
        # replica 0 breaks wholesale: queries 500, probes 503 (probes
        # must fail too, else the health loop re-admits it instantly)
        stubs[0].fail_queries = True
        stubs[0].fail_probes = True
        # every query answers 200 — failures retry on the sibling
        for _ in range(8):
            async with client.post("/queries.json", json={}) as resp:
                assert resp.status == 200
        assert sum(v for _, v in router._retries.samples()) > 0
        assert sum(v for _, v in router._dropped.samples()) == 0
        # consecutive proxy failures ejected replica 0 from rotation
        assert router.replicas[0].healthy is False
        assert stubs[1].served == 8
        # recovery: the health loop re-admits it, and it serves again
        stubs[0].fail_queries = False
        stubs[0].fail_probes = False
        assert await _wait_for(lambda: router.replicas[0].healthy)
        before = stubs[0].served
        for _ in range(4):
            async with client.post("/queries.json", json={}) as resp:
                assert resp.status == 200
        assert stubs[0].served > before
    finally:
        await _close(client, stubs)


async def test_router_answers_503_only_when_nothing_routable():
    stubs, urls = await _stubs(2)
    router = Router(_rcfg(), replica_urls=urls)
    client = await _start_router(router)
    try:
        stubs[0].fail_queries = stubs[1].fail_queries = True
        async with client.post("/queries.json", json={}) as resp:
            assert resp.status == 503
            assert "no replica" in (await resp.json())["message"]
        assert sum(v for _, v in router._dropped.samples()) == 1
    finally:
        await _close(client, stubs)


async def test_router_probe_backoff_for_dead_replica():
    """A dead replica is probed at interval, 2x, 4x ... capped — NOT
    hammered at health_interval_s forever. Over a 1.2s window at a
    0.05s interval a non-backed-off loop would fail ~24 probes; the
    exponential schedule (0.05 + 0.1 + 0.2 + 0.4 + 0.8 ...) fits ~5."""
    stubs, urls = await _stubs(2)
    router = Router(_rcfg(health_interval_s=0.05,
                          health_backoff_cap_s=5.0), replica_urls=urls)
    client = await _start_router(router)
    try:
        stubs[0].fail_probes = True
        stubs[0].fail_queries = True
        assert await _wait_for(lambda: not router.replicas[0].healthy)
        before = router._health_total.value(replica="0", outcome="fail")
        await asyncio.sleep(1.2)
        burned = router._health_total.value(
            replica="0", outcome="fail") - before
        assert burned <= 8, f"{burned} probes in 1.2s is no backoff"
        # the healthy sibling keeps its regular cadence
        assert router.replicas[1].next_probe_at == 0.0
    finally:
        await _close(client, stubs)


async def test_router_backoff_readmission_bounded_by_cap():
    """One successful probe resets the schedule, and the cap — not the
    downtime — bounds how stale the probe schedule can get: a replica
    that was down long enough for uncapped backoff to reach multi-
    second gaps must still be re-admitted within ~cap after it heals."""
    stubs, urls = await _stubs(2)
    router = Router(_rcfg(health_interval_s=0.05,
                          health_backoff_cap_s=0.2), replica_urls=urls)
    client = await _start_router(router)
    try:
        stubs[0].fail_probes = True
        assert await _wait_for(lambda: not router.replicas[0].healthy)
        # accumulate failures: uncapped, the next probe gap would be
        # 0.05 * 2^(fails-1) >> 1s by now; the cap holds it at 0.2s
        await asyncio.sleep(1.5)
        assert router.replicas[0].fails >= 4
        stubs[0].fail_probes = False
        t0 = time.monotonic()
        assert await _wait_for(lambda: router.replicas[0].healthy,
                               timeout_s=5.0)
        assert time.monotonic() - t0 <= 1.0, (
            "re-admission took longer than the probe backoff cap allows")
        assert router.replicas[0].fails == 0
        assert router.replicas[0].next_probe_at == 0.0
    finally:
        await _close(client, stubs)


async def test_router_startup_fails_loudly_when_spawned_replica_exits(
        monkeypatch):
    """A spawned replica that exits before its first healthy probe (on
    one chip: the second `pio deploy` cannot claim the device) must stop
    the router with a reason — not sit in the probe back-off forever."""
    from predictionio_tpu.server import router as router_mod
    from predictionio_tpu.server.router import ReplicaHandle

    stubs, urls = await _stubs(1)

    class Proc:
        def __init__(self, rc):
            self.rc = rc

        def poll(self):
            return self.rc

        def terminate(self):
            pass

        def wait(self, timeout=None):
            return self.rc

    stopped = []
    monkeypatch.setattr(router_mod, "_raise_shutdown",
                        lambda: stopped.append(True))

    def spawn(rank):
        # replica 0 serves (its process is alive); replica 1 died at
        # start-up with exit code 1
        if rank == 0:
            return ReplicaHandle(rank=0, url=urls[0], proc=Proc(None))
        return ReplicaHandle(rank=1, url="http://127.0.0.1:9",
                             proc=Proc(1))

    router = Router(_rcfg(replicas=2), spawn=spawn)
    client = TestClient(TestServer(router.app))
    await client.start_server()
    try:
        assert await _wait_for(lambda: bool(stopped))
        assert "replica 1" in router.fatal
        assert "exited with code 1" in router.fatal
    finally:
        await _close(client, stubs)


async def test_router_drain_is_zero_drop():
    """Scale-down discipline: weight to zero FIRST, the in-flight query
    runs to completion, THEN the replica detaches."""
    stubs, urls = await _stubs(2)
    for s in stubs:
        s.hold_s = 0.3
    router = Router(_rcfg(), replica_urls=urls)
    client = await _start_router(router)
    try:
        async def slow_query():
            async with client.post("/queries.json", json={}) as resp:
                return resp.status

        # two concurrent queries: the diffusion puts one on each arm,
        # so replica 1 holds one in flight when the drain starts
        tasks = [asyncio.ensure_future(slow_query()) for _ in range(2)]
        assert await _wait_for(lambda: router.replicas[1].inflight > 0,
                               timeout_s=2.0)
        drained = await router.drain(1)
        assert drained is True
        assert [await t for t in tasks] == [200, 200]
        assert 1 not in router.replicas
        for s in stubs:
            s.hold_s = 0.0
        # the survivor keeps serving; nothing was dropped
        async with client.post("/queries.json", json={}) as resp:
            assert resp.status == 200
        assert sum(v for _, v in router._dropped.samples()) == 0
    finally:
        await _close(client, stubs)


async def test_sequenced_deploy_aborts_and_unwinds():
    """The fleet-consistent cutover: rank order, first failure aborts
    the remainder AND rolls the already-cut replicas back — the fleet
    never diverges past one rank."""
    stubs, urls = await _stubs(3)
    stubs[1].fail_deploy = True
    router = Router(_rcfg(), replica_urls=urls)
    client = await _start_router(router)
    try:
        async with client.post("/deploy.json",
                               json={"version": "2"}) as resp:
            assert resp.status == 502
            doc = await resp.json()
        assert doc["aborted"] is True and doc["failedReplica"] == 1
        assert doc["unwound"] == [0]
        assert len(stubs[0].deploys) == 1 and len(stubs[0].rollbacks) == 1
        assert len(stubs[1].deploys) == 1
        assert stubs[2].deploys == []          # never reached
        cutovers = [e for e in recorder().events()
                    if e["kind"] == "router_cutover"]
        assert cutovers and cutovers[-1]["outcome"] == "aborted"
        # a healthy fleet cuts over in full, in rank order
        stubs[1].fail_deploy = False
        async with client.post("/deploy.json",
                               json={"version": "2"}) as resp:
            assert resp.status == 200
            doc = await resp.json()
        assert doc["aborted"] is False
        assert [r["replica"] for r in doc["results"]] == [0, 1, 2]
        # sequenced rollback fans out the same way
        async with client.post("/rollback.json", json={}) as resp:
            assert resp.status == 200
        assert all(len(s.rollbacks) >= 1 for s in stubs)
    finally:
        await _close(client, stubs)


async def test_splitter_state_survives_router_restart(tmp_path):
    """The restart path end to end: accumulators publish through a real
    TelemetryRecorder, a NEW router over the same store resumes the
    EXACT mid-stream split — the combined spread across the restart
    stays within ±1 of the exact share."""
    tcfg = TelemetryConfig(dir=str(tmp_path / "telemetry"),
                           interval_s=60.0)
    stubs, urls = await _stubs(3)
    reg1 = MetricsRegistry()
    rec1 = TelemetryRecorder("router", tcfg, registries=[reg1])
    router1 = Router(_rcfg(), registry=reg1, telemetry=rec1,
                     replica_urls=urls)
    client1 = await _start_router(router1)
    for _ in range(7):                      # 7 % 3 != 0: mid-stream
        async with client1.post("/queries.json", json={}) as resp:
            assert resp.status == 200
    saved = router1.splitter.state()
    phase1 = [s.served for s in stubs]
    reference = WeightedSplitter({0: 1.0, 1: 1.0, 2: 1.0})
    reference.restore(saved)
    await client1.close()                   # stop() drains a final scrape

    reg2 = MetricsRegistry()
    rec2 = TelemetryRecorder("router", tcfg, registries=[reg2])
    router2 = Router(_rcfg(), registry=reg2, telemetry=rec2,
                     replica_urls=urls)
    client2 = await _start_router(router2)
    try:
        assert router2.splitter.state() == pytest.approx(saved)
        for _ in range(23):
            async with client2.post("/queries.json", json={}) as resp:
                assert resp.status == 200
        expected = {a: 0 for a in range(3)}
        for _ in range(23):
            expected[reference.route()] += 1
        for rank, stub in enumerate(stubs):
            # 30 queries over 3 replicas across a restart: exact ±1
            assert abs(stub.served - 10) <= 1, [s.served for s in stubs]
            # and router2's post-restart routing matches an in-process
            # splitter resumed from the same snapshot EXACTLY
            assert stub.served - phase1[rank] == expected[rank]
    finally:
        await _close(client2, stubs)


# ---------------------------------------------------------------------------
# FleetController: decide, committed actions, chaos, recovery
# ---------------------------------------------------------------------------

def _fcfg(**kw):
    kw.setdefault("min_replicas", 1)
    kw.setdefault("max_replicas", 4)
    kw.setdefault("burn_sustain_s", 10.0)
    kw.setdefault("idle_qps", 0.5)
    kw.setdefault("idle_sustain_s", 60.0)
    kw.setdefault("cooldown_s", 30.0)
    return FleetConfig(**kw)


class FakeActuator:
    def __init__(self, replicas=1, fail=False):
        self.replicas = replicas
        self.fail = fail
        self.ups = 0
        self.downs = 0

    def count(self):
        return self.replicas

    def scale_up(self):
        if self.fail:
            raise RuntimeError("spawn blew up")
        self.replicas += 1
        self.ups += 1
        return self.replicas - 1

    def scale_down(self):
        self.replicas -= 1
        self.downs += 1
        return True


def test_decide_burn_sustain_and_bounds():
    cfg = _fcfg()
    state = FleetState()
    burn = FleetSignals(burning=True, qps=50.0, healthy=2)
    assert decide(cfg, state, burn, 0, 2) == (None, "steady")
    assert decide(cfg, state, burn, 9_000, 2)[0] is None
    action, reason = decide(cfg, state, burn, 11_000, 2)
    assert action == "scale_up" and "burned" in reason
    # at max_replicas a sustained burn cannot scale further
    assert decide(cfg, state, burn, 11_000, 4) == \
        (None, "burning but at max_replicas")
    # a gap in the burn resets the sustain clock
    state = FleetState()
    decide(cfg, state, burn, 0, 2)
    decide(cfg, state, FleetSignals(burning=False, qps=50.0), 5_000, 2)
    assert decide(cfg, state, burn, 10_000, 2)[0] is None


def test_decide_idle_cooldown_and_priority():
    cfg = _fcfg()
    state = FleetState()
    idle = FleetSignals(burning=False, qps=0.0, healthy=2)
    decide(cfg, state, idle, 0, 2)
    action, reason = decide(cfg, state, idle, 61_000, 2)
    assert action == "scale_down" and "qps" in reason
    assert decide(cfg, state, idle, 61_000, 1) == \
        (None, "idle but at min_replicas")
    # cooldown suppresses everything
    state = FleetState(cooldown_until_ms=100_000)
    assert decide(cfg, state, idle, 99_999, 2) == (None, "cooldown")
    # burning + idle-looking = broken replica, not spare capacity
    state = FleetState()
    both = FleetSignals(burning=True, qps=0.0, healthy=2)
    decide(cfg, state, both, 0, 2)
    action, _ = decide(cfg, state, both, 61_000, 2)
    assert action == "scale_up"


def _controller(tmp_path, actuator, clock, **kw):
    return FleetController(_fcfg(**kw), actuator=actuator,
                           state_dir=str(tmp_path / "fleet"),
                           registry=MetricsRegistry(),
                           clock_ms=clock)


def test_fleet_scale_up_commits_archives_and_traces(tmp_path):
    clock = {"ms": 0}
    act = FakeActuator(replicas=1)
    ctl = _controller(tmp_path, act, lambda: clock["ms"])
    burn = FleetSignals(burning=True, qps=9.0, healthy=1)
    assert ctl.tick(burn) is None           # sustain clock starts
    clock["ms"] = 11_000
    doc = ctl.tick(burn)
    assert doc.outcome == "done" and act.replicas == 2
    # archived, not active; cooldown opened; sustain clocks reset
    assert ctl.store.load_action() is None
    state = ctl.store.load_state()
    assert state.cooldown_until_ms == 11_000 + 30_000
    assert state.burn_since_ms == 0 and state.last_action == "scale_up"
    with open(tmp_path / "fleet" / "history"
              / f"{doc.action_id}.json") as f:
        assert json.load(f)["outcome"] == "done"
    # one trace id per action, start -> done in the flight recorder
    events = [e for e in recorder().events()
              if e["kind"] == "fleet_scale"
              and e.get("actionId") == doc.action_id]
    assert [e["status"] for e in events] == ["start", "done"]
    trace_id = doc.trace.split(":")[0]
    assert all(e["traceId"] == trace_id for e in events)
    # inside the cooldown nothing re-fires even though it still burns
    clock["ms"] = 20_000
    assert ctl.tick(burn) is None


def test_fleet_failed_actuation_is_committed_failed(tmp_path):
    clock = {"ms": 0}
    act = FakeActuator(replicas=1, fail=True)
    ctl = _controller(tmp_path, act, lambda: clock["ms"])
    burn = FleetSignals(burning=True, qps=9.0, healthy=1)
    ctl.tick(burn)
    clock["ms"] = 11_000
    doc = ctl.tick(burn)
    assert doc.outcome == "failed" and "spawn blew up" in doc.detail
    assert ctl.store.load_action() is None      # archived, not wedged
    assert act.replicas == 1


@pytest.mark.parametrize("point,expect_ups", [
    ("fleet:action:created", 1),      # actuation never ran: re-actuate
    ("fleet:scale_up:enter", 1),      # ditto
    ("fleet:scale_up:done", 0),       # capacity reached: just commit
    ("fleet:scale_up:committed", 0),  # ditto
])
def test_fleet_kill_points_recover_converges(tmp_path, point, expect_ups):
    """The chaos contract: kill the controller at any boundary, a new
    process over the same state dir converges to EXACTLY one applied
    scale-up — no double-spawn, no wedged action."""
    clock = {"ms": 0}
    act = FakeActuator(replicas=1)
    ctl = _controller(tmp_path, act, lambda: clock["ms"])
    burn = FleetSignals(burning=True, qps=9.0, healthy=1)
    ctl.tick(burn)
    clock["ms"] = 11_000
    set_kill_points([point])
    with pytest.raises(CrashError):
        ctl.tick(burn)
    pending = ctl.store.load_action()
    assert pending is not None and pending.outcome == ""

    # "restart": a fresh controller over the same durable state
    act2 = FakeActuator(replicas=act.replicas)
    ctl2 = _controller(tmp_path, act2, lambda: clock["ms"])
    out = ctl2.recover()
    assert out in ("resumed", "committed")
    assert act2.ups == expect_ups
    assert act2.replicas == 2                  # exactly one net spawn
    assert ctl2.store.load_action() is None
    done = ctl2.store.load_state()
    assert done.last_outcome == "done"
    # the next tick sees a clean slate (cooldown holds, nothing pending)
    assert ctl2.tick(burn) is None


def test_fleet_tick_recovers_pending_before_new_work(tmp_path):
    clock = {"ms": 0}
    act = FakeActuator(replicas=1)
    ctl = _controller(tmp_path, act, lambda: clock["ms"])
    burn = FleetSignals(burning=True, qps=9.0, healthy=1)
    ctl.tick(burn)
    clock["ms"] = 11_000
    set_kill_points(["fleet:scale_up:enter"])
    with pytest.raises(CrashError):
        ctl.tick(burn)
    # the same controller's next tick converges instead of stacking a
    # second action on top of the crashed one
    assert ctl.tick(burn) is None
    assert ctl.store.load_action() is None
    assert act.replicas == 2 and act.ups == 1


# ---------------------------------------------------------------------------
# autoscale e2e: load grows the fleet, idleness shrinks it, zero drops
# ---------------------------------------------------------------------------

async def test_autoscale_e2e_zero_drops(tmp_path):
    stubs, urls = await _stubs(2)
    cfg = _rcfg(replicas=1)
    fleet = FleetController(
        FleetConfig(min_replicas=1, max_replicas=2,
                    burn_sustain_s=0.15, idle_qps=10_000.0,
                    idle_sustain_s=0.15, cooldown_s=0.3),
        state_dir=str(tmp_path / "fleet"))
    router = Router(cfg, spawn=lambda rank: urls[rank], stop=lambda h: None,
                    fleet=fleet, replica_urls=urls[:1])
    client = await _start_router(router)
    statuses = []
    stop = asyncio.Event()

    async def driver():
        while not stop.is_set():
            try:
                async with client.post("/queries.json", json={}) as resp:
                    statuses.append(resp.status)
            except Exception as e:     # a dropped connection IS a drop
                statuses.append(repr(e))
            await asyncio.sleep(0.005)

    task = asyncio.ensure_future(driver())
    try:
        # sustained SLO burn grows the fleet 1 -> 2
        stubs[0].breached = True
        assert await _wait_for(lambda: router.active_count() == 2,
                               timeout_s=15.0), fleet.status()
        # burn clears; sustained idleness (qps under the generous bar)
        # shrinks it back 2 -> 1 after the cooldown
        stubs[0].breached = False
        assert await _wait_for(lambda: router.active_count() == 1,
                               timeout_s=15.0), fleet.status()
        # traffic flowed THROUGH both transitions: zero drops, no 5xx
        stop.set()
        await task
        assert statuses and set(statuses) == {200}
        assert sum(v for _, v in router._dropped.samples()) == 0
        # active_count() flips the moment the drain STARTS (the
        # draining flag excludes the victim); the drain coroutine —
        # and the controller's done-event + archive — finish shortly
        # after. Wait for both archives so the event assertions below
        # don't race drain completion on a loaded box.
        assert await _wait_for(
            lambda: len(list((tmp_path / "fleet" / "history")
                             .glob("*.json"))) == 2, timeout_s=10.0)
        # both scale decisions are flight-recorder events, one trace id
        # per action from decide to commit
        events = [e for e in recorder().events()
                  if e["kind"] == "fleet_scale"]
        by_action = {}
        for e in events:
            by_action.setdefault(e["actionId"], []).append(e)
        outcomes = {es[0]["action"]: [e["status"] for e in es]
                    for es in by_action.values()}
        assert outcomes.get("scale_up") == ["start", "done"]
        assert outcomes.get("scale_down") == ["start", "done"]
        for es in by_action.values():
            assert len({e["traceId"] for e in es}) == 1
        # the durable history holds both archived actions
        history = list((tmp_path / "fleet" / "history").glob("*.json"))
        assert len(history) == 2
    finally:
        stop.set()
        if not task.done():
            await task
        await _close(client, stubs)
