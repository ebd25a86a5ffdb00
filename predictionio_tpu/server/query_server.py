"""Query server — deployed engine REST serving on port 8000.

Parity with the reference CreateServer/PredictionServer
(core/.../workflow/CreateServer.scala:104-706):

  GET  /               -> engine/instance info + serving stats   (:460-482)
  POST /queries.json   -> the prediction hot path                (:484-605)
  GET  /reload         -> WARM-swap to latest COMPLETED instance (:642-652)
  POST /stop           -> graceful shutdown (key auth)           (:635-641)
  GET  /plugins.json   -> engine server plugin registry

Deploy-lifecycle surface (deploy/ subsystem; no reference counterpart —
the reference's /reload is a cold load-latest with no way back):

  GET  /releases.json       -> release manifests for this variant
  GET  /deploy/status.json  -> active release + canary window state
  POST /deploy.json         -> warm deploy a release (key auth); body
                               {"releaseId"|"version"|"engineInstanceId",
                                "canaryFraction"?, "shadow"?, ...}
  POST /rollback.json       -> roll back (key auth): abort an active
                               canary, else restore the standby release

Everything a query touches — TrainResult, the vectorized-capability
flag, the micro-batcher — is bundled into one :class:`deploy.ServingUnit`
and swapped as a single reference assignment, so an in-flight batch keeps
the release it was routed to and no request can observe a half-swapped
(result, vectorized) pair. Before a unit takes traffic it is driven
through the ops/bucketing shape ladder (deploy/warm.py), so the first
post-cutover batch pays zero XLA compiles.

The hot path (:508 runs algorithms serially and says "TODO: Parallelize";
SURVEY.md P7): here the model's factor matrices stay resident as device
arrays inside the model objects, queries run through jitted scoring, and the
serial per-algorithm loop remains only as Python orchestration around
device-resident compute.

Feedback loop (:527-589): when feedback=True, each query/prediction pair is
written back to the event store as a `predict` event with prId tagging.
"""

from __future__ import annotations

import asyncio
import contextlib
import dataclasses
import datetime as _dt
import functools
import json
import logging
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, List, Optional

from aiohttp import web

from predictionio_tpu.core.engine import Engine, TrainResult
from predictionio_tpu.core.params import params_from_json
from predictionio_tpu.data.datamap import DataMap
from predictionio_tpu.data.event import Event, UTC
from predictionio_tpu.deploy.canary import (
    ROLE_CANARY, ROLE_INCUMBENT, ROLE_SHADOW, CanaryConfig, CanaryController,
)
from predictionio_tpu.deploy.releases import release_to_json, resolve_release
from predictionio_tpu.deploy.warm import (
    DeployError, FoldinSwapRaced, ServingUnit, WarmupReport, build_unit,
    deploy_metrics, verify_unit, warmup_unit,
)
from predictionio_tpu.obs.anatomy import (
    SERVING_PATH, AnatomyMetrics, BatchBreakdown, active_breakdown,
    anatomy_enabled, anatomy_metrics, note_stage, observe_serving_batch,
    observe_stage, pop_breakdown, push_breakdown,
)
from predictionio_tpu.obs.capacity import (
    add_capacity_route, register_capacity_metrics, unit_capacity,
)
from predictionio_tpu.obs.jax_stats import register_jax_metrics
from predictionio_tpu.obs.middleware import add_metrics_routes, observability_middleware
from predictionio_tpu.obs.registry import MetricsRegistry, default_registry
from predictionio_tpu.obs.slo import SLOEngine, SLOSpec
from predictionio_tpu.obs.trace_context import record_event
from predictionio_tpu.obs.tracing import (
    capture_context, carried, current_trace, span, span_histogram,
    timed_stage,
)
from predictionio_tpu.ops.bucketing import bucket_size, padding_waste
from predictionio_tpu.server.plugins import PluginContext
from predictionio_tpu.storage.base import EngineInstance, Release, generate_id
from predictionio_tpu.storage.registry import Storage
from predictionio_tpu.utils.server_config import (
    DeployConfig, FoldinConfig, ScorerConfig, ServingConfig,
)

logger = logging.getLogger("pio.queryserver")

DEFAULT_PORT = 8000

#: ceiling of the ADAPTIVE linger window (`ServingConfig.batch_linger_s
#: = None`): the batcher never waits longer than this for stragglers,
#: and usually waits far less (2x the arrival-interval EWMA)
ADAPTIVE_LINGER_MAX_S = 0.002
#: EWMA smoothing for the arrival-interval estimate
_EWMA_ALPHA = 0.2
#: an arrival gap above this resets the estimator — idle-period gaps
#: describe nothing about burst spacing
_EWMA_RESET_S = 1.0


@contextlib.contextmanager
def _stage(hist, name: str):
    """Stage timing against a PRE-RESOLVED span histogram handle —
    `span(..., registry=...)` would re-resolve the histogram under the
    registry lock on every exit, which has no place on the hot path
    (the tracing.Trace.span_hist rule). When the executor thread runs
    under a carried request trace (MicroBatcher dispatch), the stage
    also lands in that trace so the flight recorder attributes it."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        dt = time.perf_counter() - t0
        timed_stage(name, dt, hist)
        # and into the active batch's anatomy breakdown (no-op outside
        # a micro-batch) so members get their per-request stage share
        note_stage(name, dt)


def _to_jsonable(obj: Any) -> Any:
    if hasattr(obj, "to_dict"):
        return obj.to_dict()
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return dataclasses.asdict(obj)
    return obj


def _query_class(train_result: TrainResult) -> Optional[type]:
    """Runtime query class resolution (BaseAlgorithm.queryClass:122 analog):
    an explicit `query_class` on the algorithm, else the annotation of
    predict's query parameter."""
    for algo in train_result.algorithms:
        qc = getattr(algo, "query_class", None)
        if qc is not None:
            return qc
        try:
            import typing

            hints = typing.get_type_hints(type(algo).predict)
            qc = hints.get("query")
            if isinstance(qc, type) and dataclasses.is_dataclass(qc):
                return qc
        except Exception:
            pass
    return None


class MicroBatcher:
    """Cross-request micro-batching onto the resident device model.

    The reference answers queries in a serial per-request loop
    (CreateServer.scala:508, marked "TODO: Parallelize"). Here every request
    queued while a batch is on the device is drained into ONE
    `Algorithm.batch_predict` call per algorithm — for vectorized algorithms
    (e.g. ALS recommend_batch) B concurrent queries cost one [B,K]@[K,N]
    matmul instead of B matvecs.

    Three serving-hot-path mechanisms beyond plain coalescing:

    * **pipelining** — up to `inflight` batches run concurrently on a
      dedicated bounded executor, so the worker assembles/supplements
      batch k+1 on the host while batch k is on the device (the classic
      host/device overlap; `inflight=1` restores strict serialization).
    * **adaptive linger** (`linger_s=None`) — the wait-for-stragglers
      window is derived from the arrival-interval EWMA: the worker
      lingers only when another batch is already in flight (the device
      is busy, so waiting is free) AND the EWMA says a second request is
      likely to arrive within ADAPTIVE_LINGER_MAX_S. A lone sequential
      client therefore never pays a linger tax, while a concurrent burst
      coalesces. An explicit `linger_s` number forces a fixed wait
      (0 disables lingering).
    * **shape bucketing** — not here but in the `predict_batch` callable
      (`QueryServer._predict_batch` pads each drained batch up to its
      power-of-two bucket via ops/bucketing before any jitted scorer
      sees it).
    """

    def __init__(self, predict_batch, max_batch: int = 64,
                 linger_s: Optional[float] = None, inflight: int = 2,
                 executor: Optional[ThreadPoolExecutor] = None,
                 registry: Optional[MetricsRegistry] = None):
        self._predict_batch = predict_batch
        self.max_batch = max(1, max_batch)
        #: None = adaptive (EWMA-derived); a number = fixed linger window
        self.linger_s = linger_s
        self.adaptive_linger_max_s = ADAPTIVE_LINGER_MAX_S
        self.inflight = max(1, inflight)
        self._executor = executor
        self._queue: Optional[asyncio.Queue] = None
        self._task: Optional[asyncio.Task] = None
        self._sem: Optional[asyncio.Semaphore] = None
        self._inflight_now = 0
        self._ewma_interval: Optional[float] = None
        self._last_arrival: Optional[float] = None
        self._registry = registry
        self._size_hist = self._inflight_gauge = self._span_hist = None
        self._anatomy: Optional[AnatomyMetrics] = None
        if registry is not None:
            self._anatomy = anatomy_metrics(registry)
            self._size_hist = registry.histogram(
                "pio_batch_size",
                "Queries coalesced per micro-batch drain",
                buckets=tuple(float(1 << i) for i in range(11)))
            self._inflight_gauge = registry.gauge(
                "pio_batch_inflight",
                "Micro-batches currently running on the predict executor")
            registry.gauge_callback(
                "pio_batch_queue_depth",
                "Queries waiting in the micro-batch queue",
                lambda: float(self.queue_depth()))
            self._span_hist = span_histogram(registry)

    # -- arrival-rate estimate (adaptive linger input) -----------------------
    def _note_arrival(self) -> None:
        now = time.monotonic()
        last, self._last_arrival = self._last_arrival, now
        if last is None:
            return
        dt = now - last
        if dt > _EWMA_RESET_S:
            # an idle gap says nothing about spacing WITHIN a burst
            self._ewma_interval = None
        elif self._ewma_interval is None:
            self._ewma_interval = dt
        else:
            self._ewma_interval += _EWMA_ALPHA * (dt - self._ewma_interval)

    def _linger_window(self) -> float:
        if self.linger_s is not None:
            return self.linger_s
        if self._inflight_now == 0:
            # device idle: dispatching now beats betting on a straggler
            return 0.0
        ewma = self._ewma_interval
        if ewma is None or ewma > self.adaptive_linger_max_s:
            return 0.0
        return min(self.adaptive_linger_max_s, 2.0 * ewma)

    def _observe_span(self, name: str, seconds: float) -> None:
        if self._span_hist is not None:
            self._span_hist.observe(seconds, span=name)

    def queue_depth(self) -> int:
        return self._queue.qsize() if self._queue is not None else 0

    async def shutdown(self) -> None:
        """Cancel the worker and wait for its drain to fail everything
        still queued — handlers see a fast RuntimeError, never a hang.
        Batches already on the executor resolve through their callbacks."""
        task = self._task
        if task is not None and not task.done():
            task.cancel()
            try:
                await task
            except asyncio.CancelledError:
                pass
            except Exception:      # worker died of its own accord
                pass

    # -- submit/worker -------------------------------------------------------
    async def submit(self, query):
        loop = asyncio.get_running_loop()
        self._note_arrival()
        fut = loop.create_future()
        # capture the submitting request's trace context so the executor
        # thread's batch spans stay linked to it (the thread hop used to
        # drop the contextvar trace); a cheap contextvar read, None when
        # tracing is off. The submit timestamp + the request's own Trace
        # feed the per-request anatomy (queue wait per member, stages
        # attached to EACH member's trace — not just the first
        # submitter's carried one).
        entry = (query, fut, capture_context(), time.perf_counter(),
                 current_trace())
        while True:
            if self._task is None or self._task.done():
                self._queue = asyncio.Queue()
                self._sem = asyncio.Semaphore(self.inflight)
                self._task = loop.create_task(
                    self._worker(self._queue, self._sem))
            task, queue = self._task, self._queue
            queue.put_nowait(entry)
            if not task.done() or fut.done():
                return await fut
            # the worker completed between the liveness check and the put
            # — its shutdown drain can have run BEFORE our entry landed,
            # which would orphan `fut` and hang this handler forever on
            # `await fut`. Re-check and requeue onto a fresh worker (the
            # dead queue is abandoned; nothing reads it again).

    async def _worker(self, queue: asyncio.Queue, sem: asyncio.Semaphore):
        loop = asyncio.get_running_loop()
        batch = []
        try:
            while True:
                batch = [await queue.get()]
                # take an in-flight slot BEFORE assembling: while every
                # slot is busy the queue keeps filling, which IS the
                # batching signal — no linger needed under saturation
                await sem.acquire()
                dispatched = False
                try:
                    while len(batch) < self.max_batch and not queue.empty():
                        batch.append(queue.get_nowait())
                    linger = self._linger_window()
                    linger_dt = 0.0
                    if linger > 0.0 and len(batch) < self.max_batch:
                        t0 = time.perf_counter()
                        await asyncio.sleep(linger)
                        linger_dt = time.perf_counter() - t0
                        self._observe_span("batch_linger", linger_dt)
                        while (len(batch) < self.max_batch
                               and not queue.empty()):
                            batch.append(queue.get_nowait())
                    if self._size_hist is not None:
                        self._size_hist.observe(float(len(batch)))
                    queries = [entry[0] for entry in batch]
                    # the batch runs under the FIRST traced submitter's
                    # context (coalesced siblings ride the same batch)
                    ctx = next((entry[2] for entry in batch
                                if entry[2] is not None), None)
                    # (submit perf_counter, submitter Trace) per member —
                    # the anatomy observation at batch end amortizes from
                    # these
                    meta = [(entry[3], entry[4]) for entry in batch]
                    t_dispatch = time.perf_counter()
                    ex_fut = loop.run_in_executor(
                        self._executor, self._run_batch, queries, ctx,
                        meta, linger_dt, t_dispatch)
                    self._inflight_now += 1
                    if self._inflight_gauge is not None:
                        self._inflight_gauge.set(float(self._inflight_now))
                    ex_fut.add_done_callback(
                        functools.partial(self._finish_batch, batch, sem))
                    dispatched = True
                finally:
                    if not dispatched:
                        sem.release()
                batch = []
        finally:
            # worker died (cancellation at shutdown, BaseException): fail
            # everything not yet dispatched so no HTTP handler hangs on
            # `await fut`; already-dispatched batches resolve through
            # their executor-future callbacks
            while not queue.empty():
                batch.append(queue.get_nowait())
            for entry in batch:
                fut = entry[1]
                if not fut.done():
                    fut.set_exception(
                        RuntimeError("query micro-batch worker stopped"))

    def _run_batch(self, queries, ctx, meta=(), linger_s=0.0,
                   t_dispatch=0.0):
        """Executor-side batch dispatch, re-entering the submitting
        request's trace when one was captured — the serving_batch hop
        (and its batch_* stage spans) land in the flight recorder under
        the request's trace id."""
        if ctx is None:
            return self._run_measured(queries, meta, linger_s, t_dispatch)
        with carried(ctx, "serving_batch", registry=self._registry,
                     span_hist=self._span_hist,
                     attrs={"batch": len(queries)}):
            return self._run_measured(queries, meta, linger_s, t_dispatch)

    def _run_measured(self, queries, meta, linger_s, t_dispatch):
        """Run the batch under an anatomy breakdown: the predict path's
        _stage blocks, the padding geometry, and the fn_cache dispatch
        wrapper fill it, and each member's per-request stage share is
        observed when the batch completes — before the futures resolve,
        so the stages are on the trace when the middleware records it."""
        if self._anatomy is None or not anatomy_enabled():
            return self._predict_batch(queries)
        bd = BatchBreakdown()
        token = push_breakdown(bd)
        try:
            results = self._predict_batch(queries)
        finally:
            pop_breakdown(token)
        try:
            observe_serving_batch(self._anatomy, bd, meta, linger_s,
                                  t_dispatch)
        except Exception:
            logger.exception("anatomy observation failed")
        return results

    def _finish_batch(self, batch, sem: asyncio.Semaphore, ex_fut) -> None:
        """Runs on the event loop when a dispatched batch's executor
        future settles: free the in-flight slot, then route per-query
        results/errors to their awaiting handlers."""
        self._inflight_now -= 1
        if self._inflight_gauge is not None:
            self._inflight_gauge.set(float(self._inflight_now))
        sem.release()
        try:
            results = ex_fut.result()
        except BaseException as e:   # noqa: BLE001 — must never orphan futs
            err = e if isinstance(e, Exception) else \
                RuntimeError(f"micro-batch dispatch failed: {e!r}")
            results = [err] * len(batch)
        for entry, res in zip(batch, results):
            fut = entry[1]
            if fut.done():
                continue
            if isinstance(res, Exception):
                fut.set_exception(res)
            else:
                fut.set_result(res)


@dataclasses.dataclass
class CanaryState:
    """One in-flight staged rollout: the candidate unit plus its judge."""

    unit: ServingUnit
    controller: CanaryController
    config: CanaryConfig


class QueryServer:
    def __init__(self, engine: Engine, train_result: TrainResult,
                 instance: EngineInstance, ctx,
                 feedback: bool = False,
                 feedback_app_name: Optional[str] = None,
                 access_key: Optional[str] = None,
                 plugin_context: Optional[PluginContext] = None,
                 log_url: Optional[str] = None,
                 log_prefix: str = "",
                 registry: Optional[MetricsRegistry] = None,
                 serving_config: Optional[ServingConfig] = None,
                 deploy_config: Optional[DeployConfig] = None,
                 release: Optional[Release] = None,
                 foldin_config: Optional[FoldinConfig] = None,
                 scorer_config: Optional[ScorerConfig] = None,
                 slo_spec: Optional[SLOSpec] = None,
                 telemetry=None,
                 pin_process_scorer: bool = True):
        self.engine = engine
        self.feedback = feedback
        self.feedback_app_name = feedback_app_name
        #: remote error sink (CreateServer.scala:435-446 remoteLog): on a
        #: failed query, POST log_prefix + {"engineInstance", "message"}
        self.log_url = log_url
        self.log_prefix = log_prefix
        # resolve the feedback app once; a per-query metadata lookup would
        # sit on the hot path
        self._feedback_target = None
        if feedback and feedback_app_name:
            from predictionio_tpu.data.eventstore import resolve_app

            self._feedback_target = resolve_app(feedback_app_name)
        self.access_key = access_key
        self.plugins = plugin_context or PluginContext(
            "predictionio_tpu.engineserver_plugins")
        self.start_time = _dt.datetime.now(tz=UTC)
        self.last_serving_sec = 0.0
        self._stop_event = asyncio.Event()
        self.registry = registry or MetricsRegistry()
        register_jax_metrics(default_registry())
        self.serving_config = serving_config or ServingConfig.from_env()
        self.deploy_config = deploy_config or DeployConfig.from_env()
        self.foldin_config = foldin_config or FoldinConfig.from_env()
        #: resolved scoring-kernel knobs (env > engine.json "scorer" >
        #: server.json — pio deploy passes the engine.json-aware config
        #: explicitly). Pinned process-wide so every scoring surface the
        #: serving units reach (models, warm-up, fold-in drives) sees
        #: ONE mode; /deploy/status.json echoes it per unit.
        from predictionio_tpu.ops import scoring as _scoring

        self.scorer_config = scorer_config or ScorerConfig.from_env()
        #: multi-tenant hosting passes pin_process_scorer=False: N
        #: co-hosted servers cannot all own the ONE process pin, so each
        #: stamps its resolved config onto its own model holders instead
        #: (ops/scoring.holder_scorer_config) — tenant A can hold int8
        #: residency while tenant B holds bf16 in the same process
        self._pin_process_scorer = bool(pin_process_scorer)
        if self._pin_process_scorer:
            _scoring.set_process_scorer_config(self.scorer_config)
        else:
            self._stamp_scorer_override(train_result)
        #: online fold-in controller (deploy/foldin.py), started on the
        #: server loop when enabled AND the engine supports it
        self._foldin = None
        #: dedicated bounded pool for predictions ONLY — feedback writes
        #: and remote logging stay on the loop's default executor, so a
        #: burst of event-store writes can never starve the hot path (and
        #: vice versa). Sized past `batch_inflight` so non-vectorized
        #: engines (per-request path) still get some parallelism.
        self._predict_executor = ThreadPoolExecutor(
            max_workers=max(4, self.serving_config.batch_inflight * 2),
            thread_name_prefix="pio-predict")
        #: one background lane for deploy phases (load/warmup/verify):
        #: a warmup compiling the whole shape ladder must never occupy a
        #: predict slot of the incumbent
        self._deploy_executor = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="pio-deploy")
        #: release-lineage writes are best-effort AND ordered: a single
        #: worker preserves submission order, so a canary's CANARY write
        #: and the operator rollback's ROLLED_BACK that follows it can
        #: never commit inverted (observed as a release stuck at CANARY
        #: when both rode the shared default executor)
        self._lineage_executor = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="pio-lineage")
        #: pre-resolved span-histogram handle for batch-stage timings
        #: (_predict_batch runs per batch on the executor — it must not
        #: take the registry lock to re-resolve the histogram each stage)
        self._span_hist = span_histogram(self.registry)
        #: anatomy stage histograms (serialize stage observes per request)
        self._anatomy = anatomy_metrics(self.registry)
        #: capacity ledger: per-unit residency gauge walks the live units
        register_capacity_metrics(self.registry, self._capacity_units)
        self._pad_waste = self.registry.counter(
            "pio_batch_pad_waste_rows_total",
            "Throwaway rows added padding batches up to their shape "
            "bucket (the price of a bounded compile-shape set)")
        self._deploy = deploy_metrics(self.registry)
        #: THE serving state: everything a query touches, swapped as one
        #: reference ('result' and 'vectorized' can never be observed
        #: half-updated). The previous LIVE unit is kept resident as the
        #: instant-rollback standby (blue/green).
        self._unit = ServingUnit(
            instance=instance, result=train_result, ctx=ctx,
            vectorized=self._compute_vectorized(train_result),
            release=release)
        self._attach_batcher(self._unit)
        self._standby: Optional[ServingUnit] = None
        self._canary: Optional["CanaryState"] = None
        #: serializes unit-reference cutover against the fold-in
        #: controller's executor-thread swaps (deploy/foldin.py): the
        #: deploy paths assign on the event loop, fold-in compare-and-
        #: swaps from the deploy executor — without the lock a reload
        #: completing during a fold-in solve could be silently reverted
        self._swap_lock = threading.Lock()
        #: strong refs to fire-and-forget deploy tasks (retire/verdict/
        #: shadow) — the loop holds tasks weakly, so an unreferenced one
        #: can be garbage-collected mid-flight
        self._bg_tasks: set = set()
        self._last_query = None          # warmup fallback for /reload
        self._last_warmup: Optional[WarmupReport] = None
        self._deploy.active_version.set(float(self._unit.release_version))
        self._query_hist = self.registry.histogram(
            "pio_query_duration_seconds",
            "Query hot-path wall time by engine variant",
            labelnames=("engine_variant",))
        self._query_failures = self.registry.counter(
            "pio_query_failures_total",
            "Failed queries by engine variant and cause "
            "(bad_json = client garbage, predict_error = engine failure)",
            labelnames=("engine_variant", "reason"))
        self._feedback_hist = self.registry.histogram(
            "pio_feedback_write_duration_seconds",
            "Feedback-loop event store write wall time")
        self._reload_total = self.registry.counter(
            "pio_reload_total", "Model reload attempts by outcome",
            labelnames=("status",))
        #: warm-eviction residency state (multi-tenant budgeter): an
        #: evicted server keeps serving a WARM unit (instance + registry
        #: release pointer retained, factors dropped) and reloads through
        #: the warmup ladder on the next hit — `_reload_event` is the
        #: single-flight latch queries wait on, `_warm_bytes` remembers
        #: the last resident attribution for pre-reload budget projection
        self._reload_event: Optional[asyncio.Event] = None
        self._warm_bytes: int = 0
        self._evict_total = self.registry.counter(
            "pio_unit_evictions_total",
            "Serving units evicted to warm on-host state (factors "
            "dropped, params + release pointer retained)",
            labelnames=("reason",))
        #: SLO burn-rate engine (obs/slo.py) when the host configured a
        #: server.json "slo" section — evaluated periodically on the loop
        #: and on-demand at /slo.json; canary + fold-in gating consume it
        self._slo = (SLOEngine(self.registry, slo_spec)
                     if slo_spec is not None else None)
        self._slo_task: Optional[asyncio.Task] = None
        #: durable-telemetry recorder (obs/telemetry.py), owned by this
        #: server when given: scrape loop persists the registry + flight
        #: recorder, /history/* serves the host's merged stores, and the
        #: SLO rings REHYDRATE from history so an error budget burned
        #: before a restart stays burned (breach-in-progress survives)
        self._telemetry = telemetry
        if self._telemetry is not None and self._slo is not None:
            try:
                self._slo.rehydrate(self._telemetry.reader())
            except Exception:
                logger.exception("SLO rehydration from history failed")
        self.app = web.Application(middlewares=[
            observability_middleware(self.registry, "query_server")])
        self.app.on_startup.append(self._on_startup_foldin)
        self.app.on_startup.append(self._on_startup_slo)
        self.app.on_cleanup.append(self._on_cleanup)
        self._routes()

    async def _on_startup_foldin(self, app) -> None:
        """Start the online fold-in controller when enabled and the
        deployed engine implements the fold-in hooks; an unsupported
        engine logs and serves exactly as before."""
        if not self.foldin_config.enabled:
            return
        from predictionio_tpu.deploy.foldin import (
            FoldInController, FoldinUnsupported,
        )

        try:
            self._foldin = FoldInController(self, self.foldin_config,
                                            registry=self.registry)
        except FoldinUnsupported as e:
            logger.warning("online fold-in disabled: %s", e)
            return
        self._foldin.start()
        logger.info("online fold-in armed: interval %.2fs, max pending %d",
                    self.foldin_config.apply_interval_s,
                    self.foldin_config.max_pending)

    async def _on_startup_slo(self, app) -> None:
        """Periodic SLO evaluation: burn-rate gauges and breach events
        update every eval interval even when nothing reads /slo.json."""
        if self._slo is None:
            return

        async def _loop():
            interval = self._slo.spec.eval_interval_s
            while True:
                await asyncio.sleep(interval)
                try:
                    self._slo.tick()
                except Exception:
                    logger.exception("SLO evaluation failed")

        self._slo_task = asyncio.get_running_loop().create_task(_loop())
        logger.info("SLO engine armed: %d objective(s), eval every %.2fs",
                    len(self._slo.spec.objectives),
                    self._slo.spec.eval_interval_s)

    async def _on_cleanup(self, app) -> None:
        if self._slo_task is not None:
            self._slo_task.cancel()
            try:
                await self._slo_task
            except (asyncio.CancelledError, Exception):
                pass
        if self._foldin is not None:
            await self._foldin.aclose()
        # settle the deploy background tasks first (a mid-drain
        # _retire_batcher would otherwise die as a destroyed-pending task)
        for task in list(self._bg_tasks):
            task.cancel()
        if self._bg_tasks:
            await asyncio.gather(*self._bg_tasks, return_exceptions=True)
        # then drain every batcher still alive — active, canary, AND a
        # standby whose retirement the cancel above interrupted — BEFORE
        # the executor goes away: their workers' finally fails queued
        # queries fast instead of leaving a pending task (and a 'Task
        # was destroyed' warning) behind the loop
        units = list(self._live_units())
        if self._standby is not None:
            units.append(self._standby)
        for unit in units:
            if unit.batcher is not None:
                await unit.batcher.shutdown()
        self._predict_executor.shutdown(wait=False)
        # join, not fire-and-forget: an in-flight fold-in apply on this
        # executor reads the event store — it must finish BEFORE the
        # caller tears shared state (Storage config) down under it
        await asyncio.get_running_loop().run_in_executor(
            None, lambda: self._deploy_executor.shutdown(wait=True))
        # lineage writes drain: the last status transition of a shutdown
        # (a rollback's ROLLED_BACK) must land before the process exits
        await asyncio.get_running_loop().run_in_executor(
            None, lambda: self._lineage_executor.shutdown(wait=True))
        if self._telemetry is not None:
            # LAST: the final drain must include the flight-recorder
            # records the steps above just emitted (fold-in close,
            # batcher retirement, the lineage lane's terminal writes)
            await asyncio.get_running_loop().run_in_executor(
                None, self._telemetry.stop)

    def _routes(self):
        r = self.app.router
        r.add_get("/", self.handle_root)
        r.add_post("/queries.json", self.handle_query)
        r.add_get("/reload", self.handle_reload)
        r.add_post("/stop", self.handle_stop)
        r.add_get("/plugins.json", self.handle_plugins)
        r.add_get("/releases.json", self.handle_releases)
        r.add_get("/deploy/status.json", self.handle_deploy_status)
        r.add_post("/deploy.json", self.handle_deploy)
        r.add_post("/rollback.json", self.handle_rollback)
        r.add_get("/slo.json", self.handle_slo)
        r.add_post("/debug/profile", self.handle_profile)
        add_capacity_route(self.app, self._capacity_units)
        add_metrics_routes(self.app, self.registry, default_registry())
        from predictionio_tpu.obs.telemetry import (
            add_history_routes, history_reader_factory,
        )

        add_history_routes(self.app,
                           history_reader_factory(self._telemetry))

    # -- serving-unit plumbing (deploy/ subsystem) ---------------------------
    @property
    def result(self) -> TrainResult:
        return self._unit.result

    @property
    def instance(self) -> EngineInstance:
        return self._unit.instance

    @property
    def ctx(self):
        return self._unit.ctx

    @property
    def batcher(self) -> MicroBatcher:
        return self._unit.batcher

    @property
    def _vectorized_cached(self) -> bool:
        return self._unit.vectorized

    @_vectorized_cached.setter
    def _vectorized_cached(self, value: bool) -> None:
        self._unit.vectorized = value

    def _attach_batcher(self, unit: ServingUnit) -> None:
        """Give a unit its own micro-batcher closed over ITS result —
        batches drained after a swap still score on the release they
        were routed to."""
        unit.batcher = MicroBatcher(
            functools.partial(self._predict_batch_unit, unit),
            max_batch=self.serving_config.batch_max,
            linger_s=self.serving_config.batch_linger_s,
            inflight=self.serving_config.batch_inflight,
            executor=self._predict_executor,
            registry=self.registry)
        # each MicroBatcher points the depth gauge at itself; the server
        # owns the truth: queued queries across every live unit
        self.registry.gauge_callback(
            "pio_batch_queue_depth",
            "Queries waiting in the micro-batch queue",
            lambda: float(sum(
                u.batcher.queue_depth()
                for u in self._live_units() if u.batcher is not None)))

    def _live_units(self) -> List[ServingUnit]:
        units = [self._unit]
        if self._canary is not None:
            units.append(self._canary.unit)
        return units

    def _capacity_units(self) -> List[dict]:
        """Per-unit residency roll-up for /capacity.json and the
        pio_capacity_unit_resident_bytes gauge: the active unit, the
        blue/green standby kept resident for instant rollback, and a
        staged canary — the exact set the memory budgeter must account."""
        units = [unit_capacity(self._unit, "active")]
        if self._standby is not None:
            units.append(unit_capacity(self._standby, "standby"))
        canary = self._canary
        if canary is not None:
            units.append(unit_capacity(canary.unit, "canary"))
        return units

    def _spawn(self, coro) -> None:
        """create_task with a strong reference held until completion."""
        task = asyncio.get_running_loop().create_task(coro)
        self._bg_tasks.add(task)
        task.add_done_callback(self._bg_tasks.discard)

    # -- info ---------------------------------------------------------------
    async def handle_root(self, request):
        """Engine/instance info + serving stats (CreateServer.scala:460-482),
        latency figures sourced from the metrics registry."""
        count = self._query_hist.total_count()
        total = self._query_hist.total_sum()
        uptime = (_dt.datetime.now(tz=UTC) - self.start_time).total_seconds()
        return web.json_response({
            "status": "alive",
            "engineInstance": {
                "id": self.instance.id,
                "engineId": self.instance.engine_id,
                "engineVariant": self.instance.engine_variant,
                "startTime": self.instance.start_time.isoformat(),
                "releaseVersion": self._unit.release_version or None,
            },
            "resident": self.resident,
            "algorithms": [type(a).__name__ for a in
                           (self.result.algorithms
                            if self.result is not None else ())],
            "startTime": self.start_time.isoformat(),
            "uptimeSeconds": uptime,
            "requestCount": int(count),
            "queryCount": int(count),
            "avgServingSec": (total / count) if count else 0.0,
            "p95ServingSec": self._query_hist.quantile(0.95),
            "lastServingSec": self.last_serving_sec,
        })

    async def _remote_log(self, message: str) -> None:
        """POST a serving failure to the operator's log sink
        (CreateServer.scala:435-446 remoteLog parity: prefix + JSON of
        engine-instance metadata and the message; delivery failures are
        logged locally and never propagate to the client response)."""
        import aiohttp

        payload = self.log_prefix + json.dumps({
            "engineInstance": {"id": self.instance.id,
                               "engineId": self.instance.engine_id,
                               "engineVariant": self.instance.engine_variant},
            "message": message})
        try:
            async with aiohttp.ClientSession() as session:
                async with session.post(
                        self.log_url, data=payload,
                        timeout=aiohttp.ClientTimeout(total=5)):
                    pass
        except Exception as e:
            logger.error("Unable to send remote log: %s", e)

    # -- hot path (CreateServer.scala:484-605) -------------------------------
    async def handle_query(self, request):
        t0 = time.perf_counter()
        variant = self.instance.engine_variant
        try:
            body = await request.json()
        except json.JSONDecodeError as e:
            self._query_failures.inc(engine_variant=variant,
                                     reason="bad_json")
            return web.json_response({"message": str(e)}, status=400)
        # route: snapshot the unit ONCE — everything this request touches
        # (result, vectorized flag, batcher) rides that one reference, so
        # a concurrent swap can never hand it mismatched halves
        role, unit, canary = ROLE_INCUMBENT, self._unit, self._canary
        if unit.result is None:
            # warm-evicted: factors were dropped under the device-memory
            # budget. Kick (or join) the single-flight reload and wait,
            # bounded — past the bound the client gets a clean 503 with
            # Retry-After rather than an unbounded queue
            if not await self.ensure_resident():
                self._query_failures.inc(engine_variant=variant,
                                         reason="not_resident")
                return web.json_response(
                    {"message": "serving unit is reloading; retry"},
                    status=503, headers={"Retry-After": "1"})
            role, unit, canary = ROLE_INCUMBENT, self._unit, self._canary
        if canary is not None and canary.controller.decided is None:
            if canary.controller.splitter.route():
                role, unit = ROLE_CANARY, canary.unit
            # publish the diffusion accumulator so the telemetry scrape
            # persists it; a restarted server restores the exact
            # mid-stream split instead of re-seeding at zero
            self._deploy.canary_splitter_acc.set(
                canary.controller.splitter.state())
        t_predict = time.perf_counter()
        try:
            # spans resolve through the middleware-installed trace, which
            # carries a pre-resolved histogram handle (no lock on hot path)
            with span("extract_query"):
                query = self._extract_query(body)
            self._last_query = query      # warmup fallback for /reload
            with span("predict"):
                prediction = await self._predict_via(unit, query)
        except Exception as e:
            self._observe_role(canary, role,
                               time.perf_counter() - t_predict, ok=False)
            logger.exception("query failed")
            self._query_failures.inc(engine_variant=variant,
                                     reason="predict_error")
            if self.log_url:
                await self._remote_log(
                    f"Query:\n{json.dumps(body)}\n\nError:\n{e!r}\n\n")
            return web.json_response({"message": str(e)}, status=400)
        self._observe_role(canary, role,
                           time.perf_counter() - t_predict, ok=True)
        t_serialize = time.perf_counter()
        if (canary is not None and canary.config.shadow
                and canary.controller.decided is None):
            # shadow mode: mirror the query into the candidate off the
            # response path; its result is scored for SLOs and discarded
            self._spawn(self._shadow_score(canary, query))

        pred_json = _to_jsonable(prediction)
        # feedback loop: tag with prId and record events (:527-589)
        if self.feedback and self.feedback_app_name:
            pr_id = (pred_json.get("prId") if isinstance(pred_json, dict)
                     else None) or generate_id()
            if isinstance(pred_json, dict):
                pred_json = dict(pred_json)
                pred_json["prId"] = pr_id
            asyncio.get_running_loop().run_in_executor(
                None, self._record_feedback, body, pred_json, pr_id)
        # output blockers transform; sniffers observe
        for blocker in self.plugins.output_blockers.values():
            try:
                pred_json = blocker.process(self.instance, body, pred_json)
            except Exception:
                logger.exception("output blocker failed")
        for sniffer in self.plugins.output_sniffers.values():
            try:
                sniffer.process(self.instance, body, pred_json)
            except Exception:
                logger.exception("output sniffer failed")

        if anatomy_enabled():
            # the post-predict tail: feedback scheduling, blockers,
            # sniffers, JSON conversion — the "serialize" anatomy stage
            observe_stage(self._anatomy, SERVING_PATH, "serialize",
                          time.perf_counter() - t_serialize,
                          current_trace())
        dt = time.perf_counter() - t0
        self.last_serving_sec = dt
        self._query_hist.observe(dt, engine_variant=variant)
        return web.json_response(pred_json)

    def _extract_query(self, body: dict):
        if self.result is None:        # warm-evicted: no algorithms to ask
            return body
        qc = _query_class(self.result)
        if qc is None:
            return body
        return params_from_json(body, qc)

    async def _predict_via(self, unit: ServingUnit, query):
        """Score one query on a specific serving unit (incumbent or
        canary): through ITS batcher when vectorized, else per-request
        on the predict pool."""
        if unit.vectorized:
            return await unit.batcher.submit(query)
        # no vectorized batch_predict to exploit — per-request
        # parallelism on the server's own bounded pool beats
        # serializing into one batch
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(
            self._predict_executor, self._predict_unit, unit, query)

    def _observe_role(self, canary: Optional["CanaryState"], role: str,
                      seconds: float, ok: bool) -> None:
        """Per-role accounting: every query increments
        pio_deploy_requests_total, and during a staged rollout feeds the
        SLO judge — whose verdict (promote/rollback) is acted on off the
        request path."""
        self._deploy.requests_total.inc(role=role)
        if canary is None or canary is not self._canary:
            return
        verdict = canary.controller.observe(role, seconds, ok)
        if verdict is not None:
            self._spawn(self._act_on_verdict(canary, verdict))

    def _restore_canary_splitter(self, controller) -> None:
        """Re-seed the canary splitter's diffusion accumulator from the
        durable telemetry store (the restart-skew fix: process-local, a
        restart mid-canary would re-seed at 0 and skew the realized
        fraction for the first ~1/fraction queries). The last persisted
        ``pio_deploy_canary_splitter_acc`` point wins; restore()
        ignores junk."""
        if self._telemetry is None:
            return
        try:
            points = [p for info in self._telemetry.reader().series(
                "pio_deploy_canary_splitter_acc") for p in info.points]
            if points:
                controller.splitter.restore(max(points)[1])
                self._deploy.canary_splitter_acc.set(
                    controller.splitter.state())
        except Exception:
            logger.exception("canary splitter restore failed; starting "
                             "from a zero accumulator")

    async def _shadow_score(self, canary: "CanaryState", query) -> None:
        """Score-but-discard: the candidate sees real traffic shape
        without serving a single user-visible byte."""
        t0 = time.perf_counter()
        try:
            await self._predict_via(canary.unit, query)
            ok = True
        except Exception:
            ok = False
        self._observe_role(canary, ROLE_SHADOW,
                           time.perf_counter() - t0, ok)

    def _vectorized(self) -> bool:
        """Cached per ServingUnit — the walk itself is cheap but it sat
        on EVERY request; recomputed only when a swap installs a new
        unit."""
        return self._unit.vectorized

    @staticmethod
    def _compute_vectorized(result: TrainResult) -> bool:
        """Micro-batching only pays when EVERY algorithm overrides
        batch_predict with a batched implementation — with a mix, the
        non-vectorized algorithms would run their serial per-query loop
        inside the single batcher worker, which is slower than the
        per-request thread-pool path."""
        from predictionio_tpu.core.base import Algorithm

        return bool(result.algorithms) and all(
            type(a).batch_predict is not Algorithm.batch_predict
            for a in result.algorithms)

    def _predict(self, query):
        return self._predict_unit(self._unit, query)

    def _predict_unit(self, unit: ServingUnit, query):
        result = unit.result
        supplemented = result.serving.supplement(query)
        predictions = [
            algo.predict(model, supplemented)
            for algo, model in zip(result.algorithms, result.models)]
        return result.serving.serve(query, predictions)

    def _predict_batch(self, queries):
        """Active-unit batch path (tests call this directly)."""
        return self._predict_batch_unit(self._unit, queries)

    def _predict_batch_unit(self, unit: ServingUnit, queries):
        """Batch path behind each unit's MicroBatcher (runs on the
        predict executor).

        Per-query errors are isolated: a failing query yields its
        Exception in the result slot, never poisoning the rest of the
        batch. Before the scorers run, the batch is padded up to its
        power-of-two shape bucket (ops/bucketing) with clones of the last
        real query under sentinel indices — jitted scorers therefore see
        at most `bucket_count(max_batch)` distinct batch shapes ever, and
        the padded rows are sliced off here so they never reach
        `serving.serve` or a client.

        This server-level pad is what protects engines whose
        batch_predict jits on the RAW batch length (classification's
        `_vector_batch_predict` scores an [B, d] feature matrix through
        a stable jit). ALS additionally re-buckets on its own device
        rows (unknown users shrink B mid-model, so it must); for
        host-BLAS scorers the pad is a few microseconds of duplicated
        matvec — the bounded price of one rule for every engine."""
        result = unit.result      # the unit IS the swap-consistency unit
        n = len(queries)
        out = [None] * n
        ok = []
        with _stage(self._span_hist, "batch_assemble"):
            for i, q in enumerate(queries):
                try:
                    ok.append((i, result.serving.supplement(q)))
                except Exception as e:
                    out[i] = e
            if not ok:
                return out
            bucket = bucket_size(len(ok), self.serving_config.batch_max)
            waste = padding_waste(len(ok), bucket)
            bd = active_breakdown()
            if bd is not None:
                # pad geometry for the per-member pad_share attribution
                bd.note_padding(len(ok), waste, bucket)
            if waste:
                # sentinel indices >= n mark pad rows; their predictions
                # are computed and thrown away — the bounded price of a
                # bounded compile-shape set
                pad_q = ok[-1][1]
                batch = ok + [(n + j, pad_q) for j in range(waste)]
                self._pad_waste.inc(waste)
            else:
                batch = ok
        try:
            per_query = {i: [] for i, _ in ok}
            with _stage(self._span_hist, "batch_device"):
                for algo, model in zip(result.algorithms, result.models):
                    for i, p in algo.batch_predict(model, batch):
                        if i in per_query:      # pad rows sliced off
                            per_query[i].append(p)
            with _stage(self._span_hist, "batch_serve"):
                for i, _ in ok:
                    try:
                        out[i] = result.serving.serve(queries[i],
                                                      per_query[i])
                    except Exception as e:
                        out[i] = e
        except Exception:
            # batch path failed (poison query inside a vectorized
            # batch_predict) — isolate by falling back to per-query predict
            for i, sq in ok:
                try:
                    preds = [a.predict(m, sq) for a, m in
                             zip(result.algorithms, result.models)]
                    out[i] = result.serving.serve(queries[i], preds)
                except Exception as e:
                    out[i] = e
        return out

    def _record_feedback(self, query_json, pred_json, pr_id):
        """Write predict/actual linkage events (CreateServer.scala:563-589)."""
        t0 = time.perf_counter()
        try:
            app_id, channel_id = self._feedback_target
            event = Event(
                event="predict",
                entity_type="pio_pr",
                entity_id=pr_id,
                properties=DataMap({"query": query_json,
                                    "prediction": pred_json}),
            )
            Storage.get_events().insert(event, app_id, channel_id)
            self._feedback_hist.observe(time.perf_counter() - t0)
        except Exception:
            logger.exception("feedback recording failed")

    # -- management ----------------------------------------------------------
    def _authorized(self, request) -> bool:
        if not self.access_key:
            return True
        return request.query.get("accessKey") == self.access_key

    # -- deploy lifecycle (deploy/ subsystem) --------------------------------
    def _effective_warmup(self, override: Optional[bool]) -> bool:
        """The warmup flag a prepare actually ran with: a per-deploy body
        override beats DeployConfig — the swap-mode metric label must
        agree with it."""
        return bool(self.deploy_config.warmup if override is None
                    else override)

    def _phase_timer(self, phase: str):
        """Time one deploy phase into the pio_deploy phase histogram AND
        the request trace (deploy_<phase> span)."""
        @contextlib.contextmanager
        def _cm():
            t0 = time.perf_counter()
            with span(f"deploy_{phase}"):
                try:
                    yield
                finally:
                    self._deploy.phase_hist.observe(
                        time.perf_counter() - t0, phase=phase)
        return _cm()

    async def _prepare_unit(self, instance: EngineInstance,
                            release: Optional[Release],
                            warmup: Optional[bool] = None,
                            warmup_query_json: Optional[dict] = None
                            ) -> ServingUnit:
        """The pre-cutover pipeline: load -> warmup -> verify, all on the
        deploy lane so the incumbent never donates a predict slot. The
        returned unit is fully compiled and health-checked but NOT yet
        taking traffic."""
        loop = asyncio.get_running_loop()
        with self._phase_timer("load"):
            unit = await loop.run_in_executor(
                self._deploy_executor, build_unit, self.engine, instance,
                release)
        self._stamp_scorer_override(unit.result)
        self._attach_batcher(unit)
        predict_batch = functools.partial(self._predict_batch_unit, unit)
        explicit_q = None
        if warmup_query_json is not None:
            explicit_q = self._extract_query(warmup_query_json)
        warm = self._effective_warmup(warmup)
        if warm:
            with self._phase_timer("warmup"):
                report = await loop.run_in_executor(
                    self._deploy_executor, warmup_unit, unit, predict_batch,
                    self.serving_config.batch_max,
                    explicit_q if explicit_q is not None else self._last_query)
            self._deploy.warmup_shapes.inc(len(report.buckets))
            self._last_warmup = report
            logger.info("warmup for instance %s: buckets=%s compiles=%d "
                        "(%.3fs)%s", instance.id, report.buckets,
                        report.compile_delta, report.seconds,
                        f" skipped={report.skipped}" if report.skipped else "")
        else:
            self._last_warmup = WarmupReport(skipped="disabled")
        with self._phase_timer("verify"):
            await loop.run_in_executor(
                self._deploy_executor, verify_unit, unit, predict_batch,
                explicit_q if explicit_q is not None else self._last_query)
        return unit

    def _swap_to(self, unit: ServingUnit, mode: str, reason: str,
                 retire_old: bool = True) -> None:
        """THE cutover: one reference assignment installs the new unit;
        the old unit becomes the instant-rollback standby and its batcher
        drains in the background. ``retire_old=False`` leaves the
        outgoing unit's release status to the caller (rollback marks it
        ROLLED_BACK, not RETIRED)."""
        with self._phase_timer("swap"):
            with self._swap_lock:
                old = self._unit
                self._unit = unit
        self._deploy.swap_total.inc(mode=mode, outcome="ok")
        self._deploy.active_version.set(float(unit.release_version))
        record_event("swap", {
            "mode": mode, "reason": reason,
            "engineInstanceId": unit.instance.id,
            "releaseVersion": unit.release_version or None})
        self._standby = old
        self._spawn(self._retire_batcher(old))
        self._set_release_status(unit.release, "LIVE", reason)
        if retire_old and old.release is not None and (
                unit.release is None or old.release.id != unit.release.id):
            self._set_release_status(old.release, "RETIRED",
                                     f"superseded: {reason}")
        logger.info("swapped to engine instance %s (%s: %s)",
                    unit.instance.id, mode, reason)

    # -- warm eviction / reload (multi-tenant residency budgeter) ------------
    def _stamp_scorer_override(self, result) -> None:
        """When this server does NOT own the process scorer pin (a
        multi-tenant host serves many servers in one process), stamp the
        per-tenant scorer config onto every model holder so
        ``holder_scorer_config`` resolves it instead of the process pin —
        tenant A can stay int8 while tenant B scores bf16."""
        if self._pin_process_scorer or result is None:
            return
        for model in getattr(result, "models", ()) or ():
            try:
                model._scorer_cfg_override = self.scorer_config
            except Exception:  # frozen/odd holders: fall back to process pin
                pass

    @property
    def resident(self) -> bool:
        """Whether the active unit holds device-resident factors."""
        return self._unit.result is not None

    @property
    def warm_bytes(self) -> int:
        """Last known resident attribution: live bytes while resident,
        the pre-eviction footprint while warm (the budgeter's projection
        of what a reload will cost)."""
        if self.resident:
            return int(sum(u.get("residentBytes", 0)
                           for u in self._capacity_units()))
        return self._warm_bytes

    async def evict_to_warm(self, reason: str = "budget") -> bool:
        """Drop the active unit to warm on-host state: the instance and
        registry release pointer stay, the factors (TrainResult, scorer
        caches, standby) go. Runs under the `_swap_lock` discipline — the
        cutover installs a NEW factor-less ServingUnit, so a fold-in
        compare-and-swap racing the eviction loses cleanly
        (FoldinSwapRaced) instead of resurrecting dropped factors.

        Refused (returns False) while a canary window is open (the judge
        would lose its incumbent baseline), while a reload is already in
        flight, and on an already-warm unit."""
        from predictionio_tpu.storage import faults

        if self._canary is not None or self._reload_event is not None:
            return False
        with self._swap_lock:
            old = self._unit
            if old.result is None:
                return False
            warm = ServingUnit(
                instance=old.instance, result=None, ctx=old.ctx,
                vectorized=False, release=old.release)
            self._unit = warm
        # attribution BEFORE the factors drop: the budgeter projects the
        # reload cost from this number
        self._warm_bytes = int(
            unit_capacity(old, "active").get("residentBytes", 0))
        standby, self._standby = self._standby, None
        # in-flight and already-queued batches finish on the old unit's
        # own batcher (they score on the factors they were promised)
        await self._retire_batcher(old)
        faults.maybe_kill("mt:evict:drained")
        old.result = None
        old.batcher = None
        old.foldin_of = None
        if standby is not None:
            standby.result = None
            standby.batcher = None
            standby.foldin_of = None
        self._evict_total.inc(reason=reason)
        self._deploy.swap_total.inc(mode="evict", outcome="ok")
        record_event("evict", {
            "reason": reason,
            "engineInstanceId": warm.instance.id,
            "releaseVersion": warm.release_version or None,
            "residentBytes": self._warm_bytes})
        logger.info("evicted instance %s to warm state (%s, %d bytes)",
                    warm.instance.id, reason, self._warm_bytes)
        faults.maybe_kill("mt:evict:committed")
        return True

    async def ensure_resident(self, wait_s: Optional[float] = None) -> bool:
        """Queries hitting a warm unit call this: start (or join) the
        single-flight warm reload and wait for it, bounded by ``wait_s``
        (default: the deploy drain timeout). True when the active unit is
        resident on return."""
        if self._unit.result is not None:
            return True
        ev = self._reload_event
        if ev is None:
            self._reload_event = ev = asyncio.Event()
            self._spawn(self._reload_from_warm(ev))
        timeout = (wait_s if wait_s is not None
                   else self.deploy_config.drain_timeout_s)
        try:
            await asyncio.wait_for(asyncio.shield(ev.wait()), timeout)
        except asyncio.TimeoutError:
            return False
        return self._unit.result is not None

    async def _reload_from_warm(self, ev: asyncio.Event) -> None:
        """The reload half of the eviction cycle: drive the SAME
        load -> warmup -> verify ladder a deploy uses (the unit that
        swaps in is fully compiled and health-checked — never
        half-resident), then compare-and-swap it over the warm
        placeholder. A deploy/rollback that landed mid-reload wins: the
        reloaded unit is discarded, never silently installed."""
        from predictionio_tpu.storage import faults

        warm = self._unit
        try:
            unit = await self._prepare_unit(warm.instance, warm.release)
            faults.maybe_kill("mt:reload:loaded")
            with self._swap_lock:
                raced = self._unit is not warm
                if not raced:
                    self._unit = unit
            if raced:
                if unit.batcher is not None:
                    await unit.batcher.shutdown()
                unit.result = None
                self._reload_total.inc(status="warm_reload_raced")
                return
            self._deploy.swap_total.inc(mode="warm_reload", outcome="ok")
            self._deploy.active_version.set(float(unit.release_version))
            self._reload_total.inc(status="warm_reload")
            record_event("swap", {
                "mode": "warm_reload",
                "engineInstanceId": unit.instance.id,
                "releaseVersion": unit.release_version or None})
            faults.maybe_kill("mt:reload:committed")
        except DeployError:
            self._reload_total.inc(status="warm_reload_failed")
            self._deploy.swap_total.inc(mode="warm_reload",
                                        outcome="failed")
            logger.exception("warm reload failed; unit stays warm")
        finally:
            # waiters wake either way: resident -> serve, still warm ->
            # clean 503 (and the next hit retries the reload)
            self._reload_event = None
            ev.set()

    # -- online fold-in cutover (deploy/foldin.py) ---------------------------
    def build_foldin_unit(self, new_models, applied_rows: int,
                          drift_release: Optional[Release] = None,
                          base_unit: Optional[ServingUnit] = None
                          ) -> ServingUnit:
        """A fold-in drift of the active unit: same instance/ctx, new
        models, and `foldin_of` pinned to the PRE-fold-in base so every
        later drift (and the rollback path) can find it."""
        base = base_unit if base_unit is not None else self._unit
        result = dataclasses.replace(base.result, models=list(new_models))
        unit = ServingUnit(
            instance=base.instance, result=result, ctx=base.ctx,
            vectorized=self._compute_vectorized(result),
            release=drift_release or base.release)
        unit.foldin_of = base.foldin_of or base
        unit.foldin_rows = base.foldin_rows + applied_rows
        self._stamp_scorer_override(result)
        return unit

    def swap_foldin_unit(self, unit: ServingUnit, loop=None,
                         expected_base: Optional[ServingUnit] = None
                         ) -> None:
        """Fold-in cutover: the /reload atomic-swap discipline, warmup
        only when the drift grew the catalog (the controller pre-warms
        before calling; a user-only drift keeps the base's shapes). One
        reference assignment; in-flight batches keep scoring the unit
        they were routed to; the standby is pinned to the PRE-fold-in
        base so `pio rollback` restores pre-fold-in answers. Callable
        from any thread — the old batcher's drain is marshaled onto
        `loop` when one is running.

        ``expected_base`` makes it a compare-and-swap: the solve ran
        against a snapshot of the serving unit, and a /reload, /deploy,
        rollback, or canary cutover that landed meanwhile must win —
        raises :class:`FoldinSwapRaced` (the controller requeues its
        deltas) instead of silently reverting a real deploy to a drift
        of the old model."""
        if unit.batcher is None:
            self._attach_batcher(unit)
        with self._phase_timer("swap"):
            with self._swap_lock:
                if expected_base is not None and \
                        self._unit is not expected_base:
                    self._deploy.swap_total.inc(mode="foldin",
                                                outcome="raced")
                    raise FoldinSwapRaced(
                        "serving unit changed during the fold-in solve "
                        f"(now instance {self._unit.instance.id})")
                if self._canary is not None:
                    self._deploy.swap_total.inc(mode="foldin",
                                                outcome="raced")
                    raise FoldinSwapRaced(
                        "canary window opened during the fold-in solve")
                old = self._unit
                self._unit = unit
        self._deploy.swap_total.inc(mode="foldin", outcome="ok")
        self._deploy.active_version.set(float(unit.release_version))
        record_event("swap", {
            "mode": "foldin",
            "engineInstanceId": unit.instance.id,
            "releaseVersion": unit.release_version or None,
            "foldinRows": unit.foldin_rows})
        self._standby = unit.foldin_of
        if loop is not None and loop.is_running():
            fut = asyncio.run_coroutine_threadsafe(
                self._retire_batcher(old), loop)
            fut.add_done_callback(_log_retire_failure)

    async def _retire_batcher(self, unit: ServingUnit,
                              timeout: Optional[float] = None) -> None:
        """Graceful retirement: already-routed batches drain on the old
        unit's own batcher (they score on the release they were promised)
        before the worker is torn down. Aborts if the unit was promoted
        back to live mid-drain (a rollback inside the drain window must
        not tear down the batcher now serving traffic)."""
        batcher = unit.batcher
        if batcher is None:
            return

        def _reinstated() -> bool:
            return unit is self._unit or unit.batcher is not batcher

        t0 = time.perf_counter()
        deadline = t0 + (timeout if timeout is not None
                         else self.deploy_config.drain_timeout_s)
        while (batcher.queue_depth() > 0 or batcher._inflight_now > 0) \
                and time.perf_counter() < deadline:
            if _reinstated():
                return
            await asyncio.sleep(0.02)
        if _reinstated():
            return
        await batcher.shutdown()
        if unit.batcher is batcher:
            unit.batcher = None
        self._deploy.phase_hist.observe(time.perf_counter() - t0,
                                        phase="drain")

    def _set_release_status(self, release: Optional[Release], status: str,
                            reason: str) -> None:
        """Best-effort lineage write-back (off-thread; a registry outage
        must never wedge serving), ordered by the single lineage lane."""
        if release is None:
            return
        ctx = capture_context()

        def _write():
            with carried(ctx, "release_status", record=False):
                try:
                    Storage.get_meta_data_releases().set_status(
                        release.id, status, reason=reason)
                except Exception:
                    logger.exception(
                        "release status update failed (%s -> %s)",
                        release.id, status)
        release.status = status          # keep the resident copy honest
        try:
            asyncio.get_running_loop()
            self._lineage_executor.submit(_write)
        except RuntimeError:             # no loop (tests calling directly)
            _write()

    async def _act_on_verdict(self, canary: "CanaryState",
                              verdict) -> None:
        decision, reason = verdict
        if self._canary is not canary:
            return
        self._canary = None
        self._deploy.canary_fraction.set(0.0)
        record_event("canary_verdict", {
            "decision": decision, "reason": reason,
            "engineInstanceId": canary.unit.instance.id,
            "releaseVersion": canary.unit.release_version or None})
        if decision == "promote":
            self._deploy.promote_total.inc(
                reason="healthy" if reason.startswith("healthy") else reason)
            self._swap_to(canary.unit, mode="canary", reason=reason)
        else:
            slug = reason.split(":", 1)[0]
            self._deploy.rollback_total.inc(reason=slug)
            self._set_release_status(canary.unit.release, "ROLLED_BACK",
                                     reason)
            await self._retire_batcher(canary.unit)
            logger.warning("canary rolled back: %s", reason)

    async def handle_reload(self, request):
        """Warm-swap to the latest COMPLETED instance — "prepare new,
        verify healthy, atomically swap, retire old" (the reference's
        :342-371 ReloadServer reloaded cold, in place)."""
        if not self._authorized(request):
            return web.json_response({"message": "Unauthorized"}, status=401)
        blocked = await self._settle_canary_first()
        if blocked is not None:
            return blocked
        loop = asyncio.get_running_loop()

        def _lookup():
            instances = Storage.get_meta_data_engine_instances()
            latest = instances.get_latest_completed(
                self.instance.engine_id, self.instance.engine_version,
                self.instance.engine_variant)
            release = None
            if latest is not None:
                try:
                    releases = Storage.get_meta_data_releases()
                    for r in releases.get_for_variant(
                            latest.engine_id, latest.engine_version,
                            latest.engine_variant):
                        if r.instance_id == latest.id:
                            release = r
                            break
                except Exception:
                    logger.exception("release lookup failed")
            return latest, release

        latest, release = await loop.run_in_executor(None, _lookup)
        if latest is None:
            self._reload_total.inc(status="not_found")
            return web.json_response(
                {"message": "No COMPLETED instance found"}, status=404)
        mode = "warm" if self._effective_warmup(None) else "cold"
        try:
            unit = await self._prepare_unit(latest, release)
        except DeployError as e:
            self._reload_total.inc(status="failed")
            self._deploy.swap_total.inc(mode=mode, outcome="failed")
            return web.json_response({"message": str(e)}, status=500)
        self._swap_to(unit, mode=mode, reason="reload")
        self._reload_total.inc(status="reloaded")
        return web.json_response({
            "message": "Reloaded",
            "engineInstanceId": latest.id,
            "releaseVersion": unit.release_version or None,
            "warmup": (self._last_warmup.to_dict()
                       if self._last_warmup else None)})

    async def handle_deploy(self, request):
        """Warm-deploy a specific release: full cutover by default, a
        canary/shadow rollout when the body asks for one."""
        if not self._authorized(request):
            return web.json_response({"message": "Unauthorized"}, status=401)
        try:
            body = await request.json() if request.can_read_body else {}
        except json.JSONDecodeError as e:
            return web.json_response({"message": str(e)}, status=400)
        blocked = await self._settle_canary_first()
        if blocked is not None:
            return blocked
        loop = asyncio.get_running_loop()

        def _resolve():
            instances = Storage.get_meta_data_engine_instances()
            release = None
            if body.get("engineInstanceId"):
                instance = instances.get(str(body["engineInstanceId"]))
            else:
                selector = body.get("releaseId") or body.get("version")
                releases = Storage.get_meta_data_releases()
                release = resolve_release(
                    releases, self.instance.engine_id,
                    self.instance.engine_version,
                    self.instance.engine_variant,
                    str(selector) if selector is not None else None)
                instance = (instances.get(release.instance_id)
                            if release is not None else None)
            return instance, release

        instance, release = await loop.run_in_executor(None, _resolve)
        if instance is None or instance.status != "COMPLETED":
            return web.json_response(
                {"message": "No deployable release/instance matched."},
                status=404)
        mode = "warm" if self._effective_warmup(body.get("warmup")) \
            else "cold"
        try:
            unit = await self._prepare_unit(
                instance, release, warmup=body.get("warmup"),
                warmup_query_json=body.get("warmupQuery"))
        except DeployError as e:
            self._deploy.swap_total.inc(mode=mode, outcome="failed")
            self._set_release_status(release, "ROLLED_BACK",
                                     f"prepare failed: {e}")
            return web.json_response({"message": str(e)}, status=500)

        cfg = self._canary_config(body)
        if cfg is not None:
            controller = CanaryController(cfg)
            self._restore_canary_splitter(controller)
            self._canary = CanaryState(unit=unit, controller=controller,
                                       config=controller.config)
            self._deploy.canary_fraction.set(
                0.0 if cfg.shadow else controller.config.fraction)
            self._set_release_status(release, "CANARY",
                                     "shadow" if cfg.shadow else
                                     f"fraction={controller.config.fraction}")
            record_event("canary_start", {
                "engineInstanceId": instance.id,
                "releaseVersion": unit.release_version or None,
                "shadow": cfg.shadow,
                "fraction": controller.config.fraction})
            return web.json_response({
                "message": "Canary started",
                "engineInstanceId": instance.id,
                "releaseVersion": unit.release_version or None,
                "canary": controller.to_dict(),
                "warmup": (self._last_warmup.to_dict()
                           if self._last_warmup else None)})
        self._swap_to(unit, mode=mode, reason="deploy")
        return web.json_response({
            "message": "Deployed",
            "engineInstanceId": instance.id,
            "releaseVersion": unit.release_version or None,
            "warmup": (self._last_warmup.to_dict()
                       if self._last_warmup else None)})

    async def _settle_canary_first(self) -> Optional[web.Response]:
        """Swap-initiating endpoints (deploy/reload) must not run over a
        live canary: an undecided rollout is refused with 409 (a swap
        would poison the judge's incumbent baseline), and a decided-but-
        not-yet-acted verdict is acted on NOW so it can never be silently
        overwritten (or resurface after an operator action)."""
        canary = self._canary
        if canary is None:
            return None
        if canary.controller.decided is None:
            return web.json_response(
                {"message": "A canary rollout is already in progress; "
                            "rollback or wait for its verdict first."},
                status=409)
        await self._act_on_verdict(canary, canary.controller.decided)
        return None

    def _canary_config(self, body: dict) -> Optional[CanaryConfig]:
        """A deploy body opts into a staged rollout with canaryFraction
        or shadow; DeployConfig supplies every unspecified knob."""
        if not (body.get("canaryFraction") or body.get("shadow")):
            return None
        dc = self.deploy_config
        return CanaryConfig(
            fraction=float(body.get("canaryFraction",
                                    dc.canary_fraction) or 0.0),
            shadow=bool(body.get("shadow", False)),
            window=int(body.get("canaryWindow", dc.canary_window)),
            min_samples=int(body.get("canaryMinSamples",
                                     dc.canary_min_samples)),
            promote_after=int(body.get("canaryPromoteAfter",
                                       dc.canary_promote_after)),
            p99_ratio=float(body.get("canaryP99Ratio", dc.canary_p99_ratio)),
            latency_slack_s=float(body.get("canaryLatencySlackS",
                                           dc.canary_latency_slack_s)),
            error_rate_slack=float(body.get("canaryErrorRateSlack",
                                            dc.canary_error_rate_slack)),
        )

    async def handle_rollback(self, request):
        """Operator rollback: abort an active canary, else restore the
        resident standby (previous LIVE release) — and as a last resort
        re-load the previous release from the registry."""
        if not self._authorized(request):
            return web.json_response({"message": "Unauthorized"}, status=401)
        canary = self._canary
        if canary is not None:
            if canary.controller.decided is None:
                canary.controller.decided = ("rollback", "operator")
                await self._act_on_verdict(canary, ("rollback", "operator"))
                return web.json_response({
                    "message": "Canary aborted",
                    "engineInstanceId": canary.unit.instance.id})
            # a verdict is queued but unacted: settle it before rolling
            # back, or a pending promote task would silently re-install
            # the release the operator just rolled away from
            decision = canary.controller.decided
            await self._act_on_verdict(canary, decision)
            if decision[0] == "rollback":
                # the SLO guard already did what the operator came to do;
                # demoting the healthy incumbent too would punish a
                # timing race, not a release
                return web.json_response({
                    "message": "Canary aborted",
                    "engineInstanceId": canary.unit.instance.id})
        target = self._standby
        if target is None or target.result is None:
            target = await self._load_previous_release()
        if target is None:
            return web.json_response(
                {"message": "No previous release to roll back to."},
                status=404)
        rolled_back = self._unit
        if target.batcher is None:
            self._attach_batcher(target)
        self._deploy.rollback_total.inc(reason="operator")
        self._swap_to(target, mode="rollback", reason="operator rollback",
                      retire_old=False)
        self._set_release_status(rolled_back.release, "ROLLED_BACK",
                                 "operator rollback")
        self._standby = None      # never flip-flop back onto the bad one
        return web.json_response({
            "message": "Rolled back",
            "engineInstanceId": target.instance.id,
            "releaseVersion": target.release_version or None})

    async def _load_previous_release(self) -> Optional[ServingUnit]:
        """Registry-backed rollback target: the newest RETIRED release
        below the active version (used when no standby is resident —
        e.g. the server restarted since the last swap)."""
        loop = asyncio.get_running_loop()

        def _find():
            try:
                releases = Storage.get_meta_data_releases()
                instances = Storage.get_meta_data_engine_instances()
            except Exception:
                return None, None
            active_v = self._unit.release_version
            for r in releases.get_for_variant(
                    self.instance.engine_id, self.instance.engine_version,
                    self.instance.engine_variant):
                if active_v and r.version >= active_v:
                    continue
                if r.status not in ("RETIRED", "LIVE"):
                    continue
                inst = instances.get(r.instance_id)
                if inst is not None and inst.status == "COMPLETED":
                    return inst, r
            return None, None

        instance, release = await loop.run_in_executor(None, _find)
        if instance is None:
            return None
        try:
            return await self._prepare_unit(instance, release)
        except DeployError:
            logger.exception("previous release failed to prepare")
            return None

    async def handle_releases(self, request):
        """Release manifests for this engine variant, newest first."""
        loop = asyncio.get_running_loop()

        def _list():
            try:
                releases = Storage.get_meta_data_releases()
                return [release_to_json(r) for r in releases.get_for_variant(
                    self.instance.engine_id, self.instance.engine_version,
                    self.instance.engine_variant)]
            except Exception:
                logger.exception("release listing failed")
                return []

        listing = await loop.run_in_executor(None, _list)
        return web.json_response({
            "releases": listing,
            "serving": {
                "engineInstanceId": self.instance.id,
                "releaseVersion": self._unit.release_version or None,
            }})

    async def handle_deploy_status(self, request):
        canary = self._canary
        return web.json_response({
            "active": {
                "engineInstanceId": self.instance.id,
                "releaseVersion": self._unit.release_version or None,
                "vectorized": self._unit.vectorized,
            },
            "standby": ({
                "engineInstanceId": self._standby.instance.id,
                "releaseVersion": self._standby.release_version or None,
            } if self._standby is not None else None),
            "canary": ({
                "engineInstanceId": canary.unit.instance.id,
                "releaseVersion": canary.unit.release_version or None,
                **canary.controller.to_dict(),
            } if canary is not None else None),
            "lastWarmup": (self._last_warmup.to_dict()
                           if self._last_warmup else None),
            "foldin": (self._foldin.status_dict()
                       if self._foldin is not None
                       else {"enabled": False}),
            "scorer": self._scorer_status(),
            "resident": self.resident,
        })

    def _scorer_status(self) -> dict:
        """Resolved scorer mode + per-unit quantized residency (the
        pio deploy echo's live counterpart, mirroring the ALS-solver
        echo). ``units`` is empty until a unit's first device-scored
        batch builds its scorer — warm-up does that on warmed deploys."""
        from predictionio_tpu.ops import scoring

        return {
            "mode": self.scorer_config.mode,
            "tileItems": self.scorer_config.tile_items,
            "shortlist": self.scorer_config.shortlist,
            "units": scoring.unit_scorer_status(self._unit.result),
        }

    async def handle_stop(self, request):
        if not self._authorized(request):
            return web.json_response({"message": "Unauthorized"}, status=401)
        self._stop_event.set()
        asyncio.get_running_loop().call_later(0.2, _raise_shutdown)
        return web.json_response({"message": "Shutting down"})

    async def handle_plugins(self, request):
        return web.json_response({"plugins": self.plugins.describe()})

    # -- SLO + profiling surface (obs/slo.py, obs/profiler.py) ---------------
    async def handle_slo(self, request):
        """The burn-rate engine's current evaluation; a read also ticks
        the engine so a breach is visible within one evaluation window
        even between periodic ticks."""
        if self._slo is None:
            return web.json_response({
                "enabled": False,
                "message": 'no SLO spec configured (server.json "slo")'})
        try:
            status = self._slo.tick()
        except Exception as e:
            logger.exception("SLO evaluation failed")
            return web.json_response({"enabled": True, "error": str(e)},
                                     status=500)
        return web.json_response({
            "enabled": True,
            "release": {
                "engineInstanceId": self.instance.id,
                "releaseVersion": self._unit.release_version or None,
            },
            **status})

    async def handle_profile(self, request):
        """Bounded on-demand device profile (key-auth like the deploy
        API): a jax.profiler capture plus the per-family dispatch-time
        attribution table."""
        from predictionio_tpu.obs import profiler

        if not self._authorized(request):
            return web.json_response({"message": "Unauthorized"}, status=401)
        try:
            body = await request.json() if request.can_read_body else {}
            if not isinstance(body, dict):
                raise ValueError("body must be a JSON object")
            seconds = float(body.get("seconds", 1.0) or 1.0)
            outdir = body.get("dir")
        except (json.JSONDecodeError, TypeError, ValueError) as e:
            return web.json_response({"message": str(e)}, status=400)
        loop = asyncio.get_running_loop()
        try:
            # the capture sleeps for its whole window: run it on the
            # deploy lane so neither the event loop nor a predict slot
            # blocks for the duration
            out = await loop.run_in_executor(
                self._deploy_executor, profiler.capture, seconds, outdir)
        except profiler.ProfileBusy as e:
            return web.json_response({"message": str(e)}, status=409)
        except RuntimeError as e:
            return web.json_response({"message": str(e)}, status=501)
        record_event("profile_capture", {"seconds": out["seconds"],
                                         "traceDir": out["traceDir"]})
        return web.json_response(out)


def _raise_shutdown():
    raise web.GracefulExit()


def _log_retire_failure(fut) -> None:
    """Done-callback for the fold-in swap's cross-thread batcher drain:
    surface failures instead of letting the future swallow them."""
    try:
        fut.result()
    except Exception:
        logger.exception("fold-in batcher retirement failed")


def create_query_server(engine: Engine, train_result: TrainResult,
                        instance: EngineInstance, ctx,
                        **kwargs) -> QueryServer:
    return QueryServer(engine, train_result, instance, ctx, **kwargs)


def run_query_server(engine: Engine, train_result: TrainResult,
                     instance: EngineInstance, ctx,
                     ip: str = "localhost", port: int = DEFAULT_PORT,
                     **kwargs) -> None:
    from predictionio_tpu.utils.server_config import ServerConfig

    cfg = ServerConfig.load()
    # server.conf key guards /stop, /reload and the deploy endpoints when
    # no explicit key given (CreateServer + KeyAuthentication.scala:33-62)
    kwargs.setdefault("access_key", cfg.key or None)
    # micro-batch tuning from server.json "serving" + PIO_BATCH_* env
    kwargs.setdefault("serving_config", cfg.serving)
    # warm-swap/canary tuning from server.json "deploy" + PIO_CANARY_* env
    kwargs.setdefault("deploy_config", cfg.deploy)
    # online fold-in knobs from server.json "foldin" + PIO_FOLDIN_* env
    # (pio deploy passes an engine.json-aware config explicitly)
    kwargs.setdefault("foldin_config", cfg.foldin)
    # scoring-kernel knobs from server.json "scorer" + PIO_SCORER_* env
    # (pio deploy passes an engine.json-aware config explicitly)
    kwargs.setdefault("scorer_config", cfg.scorer)
    # per-release SLO objectives from server.json "slo" (PIO_SLO=0 off)
    from predictionio_tpu.obs.slo import slo_spec_from_server_json

    kwargs.setdefault("slo_spec", slo_spec_from_server_json())
    # durable telemetry: scrape loop + history surface + SLO rehydration
    # (env > engine.json "telemetry" > server.json; PIO_TELEMETRY=0 off;
    # pio deploy passes the engine.json-aware config explicitly)
    tcfg = kwargs.pop("telemetry_config", None) or cfg.telemetry
    if "telemetry" not in kwargs:
        from predictionio_tpu.obs.telemetry import build_recorder

        registry = kwargs.setdefault("registry", MetricsRegistry())
        kwargs["telemetry"] = build_recorder(
            "query_server", tcfg, instance=str(port),
            registries=[registry, default_registry()])
    server = create_query_server(engine, train_result, instance, ctx, **kwargs)
    ssl_ctx = cfg.ssl_context()
    logger.info("Query server listening on %s:%s%s", ip, port,
                " (TLS)" if ssl_ctx else "")
    web.run_app(server.app, host=ip, port=port,
                ssl_context=ssl_ctx, print=None)
