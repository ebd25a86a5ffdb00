"""Serving-fleet router tier: one front door over N query-server replicas.

``pio deploy`` serves one process on one host; this module is the thin
tier that turns N of those processes into ONE serving surface
(ROADMAP item 2's replication axis):

* **Spread** — queries fan out over the replicas through
  :class:`WeightedSplitter`, the canary ``TrafficSplitter``'s
  error-diffusion discipline generalized to N arms: every arm
  accumulates ``weight/total`` credit per query and the largest
  accumulator wins, so over any window each replica serves exactly
  ``round(N * share)`` (±1) queries — no RNG, deterministic tests, and
  a restarted router resumes the EXACT mid-stream split because the
  accumulators persist through the durable telemetry store
  (``pio_router_splitter_acc``).
* **Health** — every replica is probed at ``/slo.json`` +
  ``/deploy/status.json`` (the readiness surfaces a deployed query
  server already exposes); ``health_fail_after`` consecutive failures
  eject it from rotation, the first healthy probe re-admits it. A
  failed proxy attempt retries on OTHER replicas (``proxy_retries``)
  before surfacing — a replica mid-restart must not fail user queries.
* **Fleet cutovers** — ``POST /deploy.json`` / ``/rollback.json`` on
  the router sequence the release-registry cutover one replica at a
  time in rank order, aborting (and rolling back the already-cut
  replicas) on the first failure: the router is the ONE place a fleet
  deploy is ordered, so replicas can never diverge past one rank.
* **One trace id** — the proxy forwards the request's trace context in
  ``X-Pio-Trace`` (obs/middleware.py adopts it on the replica), so
  router → replica → device is one lineage in the flight recorder; the
  replicas the router spawns inherit it via
  ``parallel/distributed.worker_env``.
* **Autoscaling** — when a ``deploy/fleet.FleetController`` is
  attached, the router feeds it burn/QPS signals off the health probes
  and executes its scale decisions: grow spawns + waits healthy,
  shrink DRAINS the victim (weight zero, in-flight runs to completion)
  before stopping it — zero dropped queries across a scale-down is the
  contract, tested.

Every knob is ``PIO_ROUTER_*`` / server.json ``router`` (see
``utils.server_config.RouterConfig``); metrics are the ``pio_router_*``
family (OBSERVABILITY.md).
"""

from __future__ import annotations

import asyncio
import dataclasses
import logging
import subprocess
import time
from typing import Callable, Dict, Optional

import aiohttp
from aiohttp import web

from predictionio_tpu.obs.middleware import (
    add_metrics_routes, observability_middleware,
)
from predictionio_tpu.obs.registry import MetricsRegistry, default_registry
from predictionio_tpu.obs.trace_context import TRACE_HEADER, record_event
from predictionio_tpu.obs.tracing import capture_context, carried
from predictionio_tpu.utils.server_config import RouterConfig

logger = logging.getLogger("pio.router")

#: default port a router listens on (replicas live at base_port + rank)
DEFAULT_ROUTER_PORT = 8100

#: how long a scale-up waits for the new replica's first healthy probe
SPAWN_HEALTHY_TIMEOUT_S = 60.0

#: per-probe and per-proxy HTTP timeouts — probes must be fast enough
#: that a hung replica cannot stall the whole health sweep
PROBE_TIMEOUT_S = 5.0
PROXY_TIMEOUT_S = 30.0


class WeightedSplitter:
    """The canary error-diffusion splitter generalized to N arms.

    Each :meth:`route` call adds ``weight/total`` credit to every arm
    and picks the arm with the most accumulated credit (ties break on
    the lowest arm id), then debits the winner by 1 — stride
    scheduling, so over any window of N routes each arm serves within
    ±1 of its exact share, deterministically. Arms with zero weight
    (draining or ejected replicas) accrue nothing and can never win.

    The accumulators are the ONLY state; :meth:`state` / :meth:`restore`
    round-trip them through the telemetry store so a restarted router
    resumes the split mid-stream instead of re-seeding at zero (the
    process-local-counter skew the single-arm ``TrafficSplitter`` had).
    """

    def __init__(self, weights: Optional[Dict[int, float]] = None):
        self._weights: Dict[int, float] = {}
        self._acc: Dict[int, float] = {}
        if weights:
            self.set_weights(weights)

    def set_weights(self, weights: Dict[int, float]) -> None:
        """Replace the arm set; surviving arms keep their accumulated
        credit (a scale event must not reshuffle the in-progress
        diffusion of the arms that stay)."""
        self._weights = {int(a): max(0.0, float(w))
                         for a, w in weights.items()}
        self._acc = {a: self._acc.get(a, 0.0) for a in self._weights}

    def route(self, eligible=None) -> Optional[int]:
        """The arm this query goes to, or None when no arm is routable.
        ``eligible`` restricts the draw (retry excluding the arm that
        just failed) without disturbing the other arms' credit."""
        arms = [a for a, w in self._weights.items()
                if w > 0 and (eligible is None or a in eligible)]
        if not arms:
            return None
        total = sum(self._weights[a] for a in arms)
        best = None
        for arm in sorted(arms):
            self._acc[arm] += self._weights[arm] / total
            if best is None or self._acc[arm] > self._acc[best]:
                best = arm
        self._acc[best] -= 1.0
        return best

    def state(self) -> Dict[int, float]:
        return dict(self._acc)

    def restore(self, accs: Dict[int, float]) -> None:
        """Re-seed surviving arms' accumulators from a persisted
        :meth:`state`; junk values (non-numeric, |acc| >= arm count + 1)
        are ignored — a corrupt snapshot must not be worse than the
        cold start it replaces."""
        bound = len(self._acc) + 1.0
        for arm, acc in accs.items():
            try:
                arm = int(arm)
                acc = float(acc)
            except (TypeError, ValueError):
                continue
            if arm in self._acc and abs(acc) < bound:
                self._acc[arm] = acc


@dataclasses.dataclass
class ReplicaHandle:
    """One replica's liveness state as the router sees it."""

    rank: int
    url: str
    proc: object = None             # Popen when the router spawned it
    healthy: bool = False
    fails: int = 0
    draining: bool = False
    inflight: int = 0
    slo: Optional[dict] = None
    deploy_status: Optional[dict] = None
    #: monotonic instant before which the health loop skips this
    #: replica — exponential probe backoff for a dead port (a killed
    #: replica must not be hammered at health_interval_s forever)
    next_probe_at: float = 0.0

    def to_json(self) -> dict:
        active = (self.deploy_status or {}).get("active") or {}
        return {
            "rank": self.rank,
            "url": self.url,
            "healthy": self.healthy,
            "draining": self.draining,
            "inflight": self.inflight,
            "consecutiveFailures": self.fails,
            "sloBreached": bool((self.slo or {}).get("breached")),
            "releaseVersion": active.get("releaseVersion"),
        }


class Router:
    """The router tier (module docstring). ``spawn(rank) -> url |
    ReplicaHandle`` and ``stop(handle)`` are the replica lifecycle
    seams: ``pio router`` injects a ``pio deploy`` subprocess spawner
    (cli/main.py), tests inject in-process stub servers, and a router
    can also front pre-existing replicas via ``replica_urls``."""

    def __init__(self, config: Optional[RouterConfig] = None,
                 registry: Optional[MetricsRegistry] = None,
                 telemetry=None,
                 spawn: Optional[Callable] = None,
                 stop: Optional[Callable] = None,
                 fleet=None,
                 replica_urls=()):
        self.cfg = config or RouterConfig.from_env()
        self.registry = registry or MetricsRegistry()
        self._telemetry = telemetry
        self._spawn = spawn
        self._stop = stop
        self.fleet = fleet
        self.replicas: Dict[int, ReplicaHandle] = {}
        self.splitter = WeightedSplitter()
        self._session: Optional[aiohttp.ClientSession] = None
        self._health_task: Optional[asyncio.Task] = None
        self._fleet_task: Optional[asyncio.Task] = None
        self._startup_task: Optional[asyncio.Task] = None
        #: why the router gave up at start-up (`run_router` exits
        #: non-zero with it); None while the fleet is fine
        self.fatal: Optional[str] = None
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._qps_sample = (time.monotonic(), 0.0)

        r = self.registry
        self._requests = r.counter(
            "pio_router_requests_total",
            "Queries proxied by replica and upstream HTTP status",
            labelnames=("replica", "status"))
        self._proxy_hist = r.histogram(
            "pio_router_proxy_duration_seconds",
            "Router-to-replica proxy wall time (queue + replica + wire)",
            labelnames=("replica",))
        self._retries = r.counter(
            "pio_router_retries_total",
            "Proxy attempts retried on another replica after a failure")
        self._dropped = r.counter(
            "pio_router_dropped_total",
            "Queries failed with no routable replica left to try")
        self._healthy_g = r.gauge(
            "pio_router_replica_healthy",
            "1 while the replica is in rotation, 0 while ejected",
            labelnames=("replica",))
        self._replicas_g = r.gauge(
            "pio_router_replicas",
            "Replicas currently attached (healthy or not)")
        self._acc_g = r.gauge(
            "pio_router_splitter_acc",
            "Error-diffusion accumulator per replica — persisted "
            "through the telemetry store so a restarted router resumes "
            "the exact mid-stream split",
            labelnames=("replica",))
        self._health_total = r.counter(
            "pio_router_health_checks_total",
            "Replica health probes by outcome",
            labelnames=("replica", "outcome"))
        self._deploys = r.counter(
            "pio_router_deploys_total",
            "Fleet-sequenced cutovers by action and outcome",
            labelnames=("action", "outcome"))

        for i, url in enumerate(replica_urls):
            self._attach(ReplicaHandle(rank=i, url=str(url).rstrip("/")))

        self.app = web.Application(middlewares=[
            observability_middleware(self.registry, "router")])
        self.app.on_startup.append(self._on_startup)
        self.app.on_cleanup.append(self._on_cleanup)
        self._routes()

    # -- membership ----------------------------------------------------------
    def _attach(self, handle: ReplicaHandle) -> ReplicaHandle:
        self.replicas[handle.rank] = handle
        self._rebuild_weights()
        return handle

    def _rebuild_weights(self) -> None:
        self.splitter.set_weights({
            rank: 0.0 if h.draining else 1.0
            for rank, h in self.replicas.items()})
        self._replicas_g.set(float(len(self.replicas)))
        for rank, h in self.replicas.items():
            self._healthy_g.set(
                1.0 if h.healthy and not h.draining else 0.0,
                replica=str(rank))
        self._publish_acc()

    def _publish_acc(self) -> None:
        for rank, acc in self.splitter.state().items():
            self._acc_g.set(acc, replica=str(rank))

    def active_count(self) -> int:
        return sum(1 for h in self.replicas.values() if not h.draining)

    def _restore_splitter(self) -> None:
        """Re-seed the diffusion accumulators from the durable
        telemetry store (the restart-skew fix): last persisted
        ``pio_router_splitter_acc`` point per replica wins."""
        if not self.cfg.persist_splitter or self._telemetry is None:
            return
        try:
            accs: Dict[int, float] = {}
            for info in self._telemetry.reader().series(
                    "pio_router_splitter_acc"):
                rep = info.labels.get("replica")
                if rep is None or not info.points:
                    continue
                accs[int(rep)] = float(info.points[-1][1])
            if accs:
                self.splitter.restore(accs)
                self._publish_acc()
                logger.info("splitter state restored for %d replica(s)",
                            len(accs))
        except Exception:
            logger.exception("splitter state restore failed; "
                             "starting from zero accumulators")

    # -- lifecycle -----------------------------------------------------------
    async def _on_startup(self, app) -> None:
        self._loop = asyncio.get_running_loop()
        self._session = aiohttp.ClientSession()
        self._restore_splitter()
        if self._spawn is not None and not self.replicas:
            for rank in range(self.cfg.replicas):
                await self.grow(wait_healthy=False)
            self._startup_task = self._loop.create_task(
                self._startup_watch())
        self._health_task = self._loop.create_task(self._health_loop())
        if self.fleet is not None:
            self._fleet_task = self._loop.create_task(self._fleet_loop())

    async def _on_cleanup(self, app) -> None:
        for task in (self._health_task, self._fleet_task,
                     self._startup_task):
            if task is not None:
                task.cancel()
                try:
                    await task
                except (asyncio.CancelledError, Exception):
                    pass
        for handle in list(self.replicas.values()):
            if handle.proc is not None:
                await self._terminate(handle)
        if self._session is not None:
            await self._session.close()
        if self._telemetry is not None:
            await asyncio.get_running_loop().run_in_executor(
                None, self._telemetry.stop)

    def _routes(self) -> None:
        r = self.app.router
        r.add_get("/", self.handle_root)
        r.add_post("/queries.json", self.handle_query)
        # multi-tenant replicas (server/multitenant.py): the tenant
        # path segment rides through to the replica's own gate, so
        # admission/residency decisions stay at the replica where the
        # tenant's SLO engine and budgeter live
        r.add_post("/t/{tenant}/queries.json", self.handle_tenant_query)
        r.add_get("/slo.json", self.handle_slo)
        r.add_get("/fleet/status.json", self.handle_fleet_status)
        r.add_post("/deploy.json", self.handle_deploy)
        r.add_post("/rollback.json", self.handle_rollback)
        add_metrics_routes(self.app, self.registry, default_registry())
        from predictionio_tpu.obs.telemetry import (
            add_history_routes, history_reader_factory,
        )

        add_history_routes(self.app,
                           history_reader_factory(self._telemetry))

    # -- spawn / drain (the fleet controller's actuation surface) ------------
    async def grow(self, wait_healthy: bool = True) -> int:
        """Attach one more replica via the spawner; returns its rank.
        ``wait_healthy`` blocks until its first healthy probe (the
        scale-up contract: capacity exists before the action commits)."""
        if self._spawn is None:
            raise RuntimeError("router has no replica spawner")
        rank = max(self.replicas) + 1 if self.replicas else 0
        spawned = self._spawn(rank)
        if isinstance(spawned, ReplicaHandle):
            spawned.rank = rank
            handle = spawned
        else:
            handle = ReplicaHandle(rank=rank, url=str(spawned).rstrip("/"))
        handle.url = handle.url.rstrip("/")
        self._attach(handle)
        logger.info("replica %d attached at %s", rank, handle.url)
        if wait_healthy:
            ok = await self.wait_replica_healthy(rank)
            if not ok:
                raise RuntimeError(
                    f"replica {rank} ({handle.url}) never became healthy "
                    f"within {SPAWN_HEALTHY_TIMEOUT_S:g}s")
        return rank

    async def _startup_watch(self) -> None:
        """The initial fleet must come up or the router gives up loudly:
        a spawned replica that EXITS before its first healthy probe (it
        could not load its model, or could not claim its device — one
        chip serves one process), or that is still not healthy after
        `SPAWN_HEALTHY_TIMEOUT_S`, is a start-up failure, not something
        to probe with back-off forever. Replicas that die after having
        been healthy stay the health loop's business (eject, re-admit)."""
        deadline = time.monotonic() + SPAWN_HEALTHY_TIMEOUT_S
        waiting = [h for h in self.replicas.values() if h.proc is not None]
        while waiting:
            waiting = [h for h in waiting if not h.healthy]
            late = time.monotonic() > deadline
            for handle in waiting:
                rc = handle.proc.poll()
                if rc is not None:
                    self.fatal = (
                        f"replica {handle.rank} ({handle.url}) exited "
                        f"with code {rc} before it ever became healthy "
                        "(its own output above says why)")
                elif late:
                    self.fatal = (
                        f"replica {handle.rank} ({handle.url}) is not "
                        f"healthy after {SPAWN_HEALTHY_TIMEOUT_S:g}s")
                if self.fatal is not None:
                    logger.error("router start-up failed: %s; stopping "
                                 "the fleet", self.fatal)
                    self._loop.call_soon(_raise_shutdown)
                    return
            await asyncio.sleep(0.2)

    async def wait_replica_healthy(
            self, rank: int,
            timeout_s: float = SPAWN_HEALTHY_TIMEOUT_S) -> bool:
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            handle = self.replicas.get(rank)
            if handle is None:
                return False
            if await self._probe(handle):
                return True
            await asyncio.sleep(
                min(0.1, max(0.01, self.cfg.health_interval_s / 4)))
        return False

    async def drain(self, rank: int,
                    timeout_s: Optional[float] = None) -> bool:
        """Scale-down one replica with the zero-drop discipline: weight
        to zero FIRST (no new queries), in-flight queries run to
        completion (bounded by ``drain_timeout_s``), then stop. Returns
        True when the drain completed with nothing in flight."""
        handle = self.replicas.get(rank)
        if handle is None:
            return True
        handle.draining = True
        self._rebuild_weights()
        deadline = time.monotonic() + (
            self.cfg.drain_timeout_s if timeout_s is None else timeout_s)
        while handle.inflight > 0 and time.monotonic() < deadline:
            await asyncio.sleep(0.02)
        drained = handle.inflight == 0
        if not drained:
            logger.warning("replica %d drain timed out with %d in flight",
                           rank, handle.inflight)
        await self._terminate(handle)
        self.replicas.pop(rank, None)
        self._healthy_g.set(0.0, replica=str(rank))
        self._rebuild_weights()
        logger.info("replica %d drained and detached (%s)", rank,
                    "clean" if drained else "timeout")
        return drained

    async def _terminate(self, handle: ReplicaHandle) -> None:
        if self._stop is not None:
            try:
                out = self._stop(handle)
                if asyncio.iscoroutine(out):
                    await out
            except Exception:
                logger.exception("replica %d stop hook failed",
                                 handle.rank)
        elif handle.proc is not None:
            wait = asyncio.get_running_loop().run_in_executor
            try:
                handle.proc.terminate()
                try:
                    await wait(None, handle.proc.wait, 10)
                except subprocess.TimeoutExpired:
                    # a replica holding a TPU can take longer than this
                    # to tear its runtime down; it must not outlive us
                    logger.warning("replica %d ignored SIGTERM for 10s; "
                                   "killing it", handle.rank)
                    handle.proc.kill()
                    await wait(None, handle.proc.wait, 10)
            except Exception:
                logger.exception("replica %d terminate failed",
                                 handle.rank)
        handle.proc = None

    # -- health --------------------------------------------------------------
    async def _probe(self, handle: ReplicaHandle) -> bool:
        """One readiness probe: both surfaces a deployed query server
        exposes must answer — /slo.json (burn state feeds the fleet
        controller) and /deploy/status.json (a replica mid-cutover is
        not ready)."""
        try:
            timeout = aiohttp.ClientTimeout(total=PROBE_TIMEOUT_S)
            async with self._session.get(f"{handle.url}/slo.json",
                                         timeout=timeout) as resp:
                if resp.status != 200:
                    raise aiohttp.ClientError(f"slo {resp.status}")
                slo = await resp.json()
            async with self._session.get(
                    f"{handle.url}/deploy/status.json",
                    timeout=timeout) as resp:
                if resp.status != 200:
                    raise aiohttp.ClientError(f"status {resp.status}")
                status = await resp.json()
        except (aiohttp.ClientError, asyncio.TimeoutError, ValueError,
                OSError):
            handle.fails += 1
            self._health_total.inc(replica=str(handle.rank),
                                   outcome="fail")
            # exponential backoff: a replica that keeps failing gets
            # probed at interval, 2x, 4x ... capped — a dead port is
            # not hammered at health_interval_s, and one successful
            # probe resets the schedule (re-admission stays bounded by
            # the cap, not by how long the replica was down)
            backoff = min(
                self.cfg.health_backoff_cap_s,
                self.cfg.health_interval_s * (2 ** max(0, handle.fails - 1)))
            handle.next_probe_at = time.monotonic() + backoff
            if handle.healthy \
                    and handle.fails >= self.cfg.health_fail_after:
                handle.healthy = False
                self._rebuild_weights()
                logger.warning("replica %d ejected after %d failed "
                               "probes (probe backoff up to %.1fs)",
                               handle.rank, handle.fails,
                               self.cfg.health_backoff_cap_s)
            return False
        handle.slo = slo if isinstance(slo, dict) else None
        handle.deploy_status = status if isinstance(status, dict) else None
        handle.fails = 0
        handle.next_probe_at = 0.0
        self._health_total.inc(replica=str(handle.rank), outcome="ok")
        if not handle.healthy:
            handle.healthy = True
            self._rebuild_weights()
        return True

    async def _health_loop(self) -> None:
        while True:
            await asyncio.sleep(self.cfg.health_interval_s)
            now = time.monotonic()
            for handle in list(self.replicas.values()):
                if handle.draining or now < handle.next_probe_at:
                    continue
                try:
                    await self._probe(handle)
                except Exception:
                    logger.exception("health probe for replica %d blew "
                                     "up", handle.rank)

    # -- the fleet controller loop -------------------------------------------
    def fleet_signals(self):
        """One observation for the autoscaler: fleet QPS from the
        router's own request counter delta, burn from the replicas'
        last /slo.json probes."""
        from predictionio_tpu.deploy.fleet import FleetSignals

        now = time.monotonic()
        total = sum(v for _, v in self._requests.samples())
        last_t, last_total = self._qps_sample
        self._qps_sample = (now, total)
        dt = max(1e-6, now - last_t)
        burning = any(bool((h.slo or {}).get("breached"))
                      for h in self.replicas.values()
                      if h.healthy and not h.draining)
        return FleetSignals(
            burning=burning,
            qps=max(0.0, total - last_total) / dt,
            healthy=sum(1 for h in self.replicas.values()
                        if h.healthy and not h.draining))

    async def _fleet_loop(self) -> None:
        self.fleet.bind(FleetRouterActuator(self, self._loop))
        await self._loop.run_in_executor(None, self.fleet.recover)
        while True:
            await asyncio.sleep(self.cfg.health_interval_s)
            signals = self.fleet_signals()
            ctx = capture_context()
            try:
                # the tick blocks on spawn/drain — keep it off the loop
                # (the proxy hot path must keep serving THROUGH a scale
                # action; that concurrency is the zero-drop test)
                await self._loop.run_in_executor(
                    None, lambda: self._fleet_tick(ctx, signals))
            except Exception:
                logger.exception("fleet controller tick failed")

    def _fleet_tick(self, ctx, signals) -> None:
        with carried(ctx, "fleet_tick", record=False):
            self.fleet.tick(signals)

    # -- handlers ------------------------------------------------------------
    async def handle_root(self, request) -> web.Response:
        return web.json_response({
            "service": "router",
            "replicas": [h.to_json()
                         for _, h in sorted(self.replicas.items())],
        })

    async def handle_query(self, request) -> web.Response:
        return await self._proxy_query(request, "/queries.json")

    async def handle_tenant_query(self, request) -> web.Response:
        tenant = request.match_info["tenant"]
        return await self._proxy_query(
            request, f"/t/{tenant}/queries.json")

    async def _proxy_query(self, request, path: str) -> web.Response:
        body = await request.read()
        headers = {"Content-Type": "application/json"}
        ctx = capture_context()
        if ctx is not None:
            # one trace id spans router -> replica -> device: the
            # replica's middleware adopts this as its parent
            headers[TRACE_HEADER] = ctx.encode()
        tried: set = set()
        attempts = 1 + self.cfg.proxy_retries
        last_error = "no routable replica"
        for attempt in range(attempts):
            eligible = {rank for rank, h in self.replicas.items()
                        if h.healthy and not h.draining
                        and rank not in tried}
            rank = self.splitter.route(eligible=eligible)
            if rank is None:
                break
            if attempt > 0:
                self._retries.inc()
            self._publish_acc()
            handle = self.replicas[rank]
            tried.add(rank)
            handle.inflight += 1
            t0 = time.perf_counter()
            try:
                timeout = aiohttp.ClientTimeout(total=PROXY_TIMEOUT_S)
                async with self._session.post(
                        f"{handle.url}{path}", data=body,
                        headers=headers, params=request.query,
                        timeout=timeout) as resp:
                    payload = await resp.read()
                    if resp.status >= 500:
                        raise aiohttp.ClientError(
                            f"replica {rank} answered {resp.status}")
                    self._requests.inc(replica=str(rank),
                                       status=str(resp.status))
                    return web.Response(
                        body=payload, status=resp.status,
                        content_type="application/json")
            except (aiohttp.ClientError, asyncio.TimeoutError,
                    OSError) as e:
                last_error = str(e) or type(e).__name__
                self._requests.inc(replica=str(rank), status="error")
                handle.fails += 1
                if handle.healthy \
                        and handle.fails >= self.cfg.health_fail_after:
                    handle.healthy = False
                    self._rebuild_weights()
                    logger.warning("replica %d ejected on proxy "
                                   "failures: %s", rank, last_error)
            finally:
                handle.inflight -= 1
                self._proxy_hist.observe(time.perf_counter() - t0,
                                         replica=str(rank))
        self._dropped.inc()
        return web.json_response(
            {"message": f"no replica could serve the query: {last_error}"},
            status=503)

    async def handle_slo(self, request) -> web.Response:
        """The fleet burn view: breached when ANY in-rotation replica
        reports a burn (the scale-up trigger reads the same signal)."""
        docs = {str(rank): h.slo
                for rank, h in sorted(self.replicas.items())
                if h.slo is not None}
        breached = any(bool((d or {}).get("breached"))
                       for d in docs.values())
        return web.json_response({"breached": breached,
                                  "replicas": docs})

    async def handle_fleet_status(self, request) -> web.Response:
        doc = {
            "replicas": [h.to_json()
                         for _, h in sorted(self.replicas.items())],
            "splitter": {str(a): acc
                         for a, acc in self.splitter.state().items()},
            "config": {
                "replicas": self.cfg.replicas,
                "healthIntervalS": self.cfg.health_interval_s,
                "healthFailAfter": self.cfg.health_fail_after,
                "proxyRetries": self.cfg.proxy_retries,
                "drainTimeoutS": self.cfg.drain_timeout_s,
            },
        }
        if self.fleet is not None:
            doc["autoscaler"] = self.fleet.status()
        return web.json_response(doc)

    async def handle_deploy(self, request) -> web.Response:
        try:
            body = await request.json()
        except ValueError:
            body = {}
        return await self._sequenced("/deploy.json", body, "deploy",
                                     request)

    async def handle_rollback(self, request) -> web.Response:
        try:
            body = await request.json()
        except ValueError:
            body = {}
        return await self._sequenced("/rollback.json", body, "rollback",
                                     request)

    async def _sequenced(self, path: str, body: dict, action: str,
                         request) -> web.Response:
        """The fleet-consistent cutover: one replica at a time in rank
        order; the first failure aborts the remainder and rolls the
        already-cut replicas back, so the fleet can never diverge past
        one rank. Recorded as a flight-recorder event under the
        request's trace id."""
        ranks = [rank for rank, h in sorted(self.replicas.items())
                 if not h.draining]
        results = []
        done = []
        for rank in ranks:
            handle = self.replicas.get(rank)
            if handle is None:
                continue
            try:
                timeout = aiohttp.ClientTimeout(total=PROXY_TIMEOUT_S)
                async with self._session.post(
                        f"{handle.url}{path}", json=body,
                        params=request.query, timeout=timeout) as resp:
                    doc = await resp.json()
                    results.append({"replica": rank,
                                    "status": resp.status,
                                    "response": doc})
                    if resp.status >= 400:
                        raise aiohttp.ClientError(
                            f"replica {rank} answered {resp.status}")
            except (aiohttp.ClientError, asyncio.TimeoutError, ValueError,
                    OSError) as e:
                if not results or results[-1].get("replica") != rank:
                    results.append({"replica": rank, "status": "error",
                                    "error": str(e) or type(e).__name__})
                unwound = []
                if action == "deploy" and done:
                    unwound = await self._unwind(done, request)
                self._deploys.inc(action=action, outcome="aborted")
                record_event("router_cutover", {
                    "action": action, "outcome": "aborted",
                    "failedReplica": rank, "completed": done,
                    "unwound": unwound})
                return web.json_response(
                    {"action": action, "aborted": True,
                     "failedReplica": rank, "results": results,
                     "unwound": unwound}, status=502)
            done.append(rank)
        self._deploys.inc(action=action, outcome="ok")
        record_event("router_cutover", {"action": action,
                                        "outcome": "ok",
                                        "replicas": done})
        return web.json_response({"action": action, "aborted": False,
                                  "results": results})

    async def _unwind(self, ranks, request) -> list:
        """Best-effort rollback of replicas a failed sequenced deploy
        already cut over — convergence, not a guarantee (a replica that
        cannot answer its rollback stays divergent and its health probe
        keeps it visible)."""
        unwound = []
        for rank in ranks:
            handle = self.replicas.get(rank)
            if handle is None:
                continue
            try:
                timeout = aiohttp.ClientTimeout(total=PROXY_TIMEOUT_S)
                async with self._session.post(
                        f"{handle.url}/rollback.json", json={},
                        params=request.query, timeout=timeout) as resp:
                    if resp.status < 400:
                        unwound.append(rank)
            except (aiohttp.ClientError, asyncio.TimeoutError,
                    OSError):
                logger.exception("unwind rollback failed for replica "
                                 "%d", rank)
        return unwound


class FleetRouterActuator:
    """The fleet controller's synchronous view of the router: the
    controller ticks on an executor thread (scale actions block on
    spawn/drain), so each actuation round-trips into the router's
    event loop and waits for the result."""

    def __init__(self, router: Router, loop: asyncio.AbstractEventLoop):
        self._router = router
        self._loop = loop

    def count(self) -> int:
        return self._router.active_count()

    def scale_up(self) -> int:
        fut = asyncio.run_coroutine_threadsafe(
            self._router.grow(wait_healthy=True), self._loop)
        return fut.result(timeout=SPAWN_HEALTHY_TIMEOUT_S + 30)

    def scale_down(self) -> bool:
        active = sorted(rank for rank, h in self._router.replicas.items()
                        if not h.draining)
        if not active:
            return True
        victim = active[-1]     # newest replica drains first (LIFO)
        fut = asyncio.run_coroutine_threadsafe(
            self._router.drain(victim), self._loop)
        return fut.result(
            timeout=self._router.cfg.drain_timeout_s + 30)


def _raise_shutdown():
    raise web.GracefulExit()


def run_router(config: Optional[RouterConfig] = None,
               ip: str = "localhost",
               spawn: Optional[Callable] = None,
               stop: Optional[Callable] = None,
               replica_urls=(),
               registry: Optional[MetricsRegistry] = None,
               fleet=None) -> None:
    """Serve the router until stopped (the ``pio router`` entry):
    resolves the knob chain, arms durable telemetry (service
    ``router``) so the splitter accumulators and ``pio_router_*``
    history survive restarts, and attaches the autoscaler when
    server.json/env enable it."""
    from predictionio_tpu.utils.server_config import (
        fleet_config, router_config,
    )

    cfg = config or router_config()
    registry = registry or MetricsRegistry()
    from predictionio_tpu.obs.telemetry import build_recorder
    from predictionio_tpu.utils.server_config import telemetry_config

    telemetry = build_recorder(
        "router", telemetry_config(), instance=str(cfg.port),
        registries=[registry, default_registry()])
    if fleet is None:
        fcfg = fleet_config()
        if fcfg.enabled:
            from predictionio_tpu.deploy.fleet import FleetController

            fleet = FleetController(fcfg, registry=registry)
    router = Router(cfg, registry=registry, telemetry=telemetry,
                    spawn=spawn, stop=stop, fleet=fleet,
                    replica_urls=replica_urls)
    logger.info("Router listening on %s:%s over %d replica(s)", ip,
                cfg.port, max(len(router.replicas), cfg.replicas))
    web.run_app(router.app, host=ip, port=cfg.port, print=None)
    if router.fatal is not None:
        raise SystemExit(f"[ERROR] Router start-up failed: {router.fatal}")
