"""Online fold-in: close the event→serving loop between full retrains.

Every batch pillar is fast (columnar ingest, subspace-ALS kernel,
bucketed serving, pipelined batchpredict) — but a new user or item was
still invisible until a full ``pio train`` + redeploy. This subsystem
makes the model *move* with the event stream: fresh events become
updated factor rows applied to the live :class:`deploy.ServingUnit`,
with "seconds from event ingested → reflected in recommendations" as a
metered headline number.

The shape follows iALS++ (arXiv:2110.14044) and ALX (arXiv:2112.02194):
with the opposite side's factors frozen, one entity's row is a cheap
independent least-squares solve — so pending rows batch into ONE device
program (:class:`models.als.FoldInSolver`, ``als_foldin`` compile-ledger
family, power-of-two bucketing).

Event delta collection is push-first, pull-fallback:

* **push** — a tap on the group-commit ``WriteBuffer`` flush
  (data/write_buffer.py): an in-process event server marks entities
  dirty the moment their events durably commit, costing the write path
  one dict insert.
* **pull** — a short-timer columnar scan (``find_columnar`` since the
  event-time watermark) catches events ingested by OTHER processes;
  push and pull overlap by design and a bounded seen-id set dedups
  them. (Caveat: backdated ``eventTime``s are only caught by push — the
  pull scan indexes on event time.) On a partitioned event store
  (``PIO_INGEST_PARTITIONS``, storage/partitioned.py) the pull scan
  reads the partitions concurrently and merges time-ordered at the
  store layer, and each dirty entity's full-history read routes to
  exactly one partition (events hash by entity).

Each apply tick: pull, take up to ``max_pending`` dirty entities, read
each one's FULL event history through the columnar find path (the solve
is exact least squares on all of the entity's ratings, not an
approximation from deltas), solve the batch on device, and hand the
engine's ``foldin_apply`` hook the solved rows (plus incremental count
delta-merges, e.g. e-commerce buy-popularity) to produce a new model —
installed via the same atomic-swap discipline as ``/reload``: in-flight
batches keep scoring the unit they were routed to.

The drift is gated behind the release registry: the first apply after a
real deploy registers a *drift revision* (one row per generation, not
per apply), the pre-fold-in unit stays resident as the rollback
standby, and ``pio rollback`` restores pre-fold-in answers exactly.
"""

from __future__ import annotations

import dataclasses
import logging
import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import numpy as np

from predictionio_tpu.data.bimap import batch_lookup, vocab_index
from predictionio_tpu.models.als import ALSParams, FoldInSolver
from predictionio_tpu.obs.foldin_stats import (
    foldin_applied_rows, foldin_applies, foldin_apply_seconds,
    foldin_batch_rows, foldin_event_to_applied, foldin_pending,
    foldin_solve_seconds,
)
from predictionio_tpu.storage.base import Release
from predictionio_tpu.utils.server_config import FoldinConfig

logger = logging.getLogger("pio.foldin")

#: bounded dedup window between the push tap and the pull scan — large
#: enough to cover several apply intervals of overlap, small enough to
#: never matter for memory
SEEN_IDS_MAX = 16384


class FoldinUnsupported(Exception):
    """The deployed engine cannot fold in (no/ambiguous foldin hooks)."""


@dataclasses.dataclass
class FoldinSpec:
    """How one algorithm's events map to fold-in deltas.

    Engines return this from ``Algorithm.foldin_spec(model,
    engine_params)``; the controller stays engine-agnostic."""

    app_name: str
    als_params: ALSParams            # reg/alpha/implicit/weighted for solves
    entity_type: str = "user"
    target_entity_type: str = "item"
    #: events that produce rating rows for the entity's solve
    event_names: Tuple[str, ...] = ()
    #: value per event name (an event absent here counts 1.0)
    event_weights: Dict[str, float] = dataclasses.field(default_factory=dict)
    #: event whose value comes from properties["rating"] (None = none)
    rate_event: Optional[str] = None
    #: "rows" = every event is one rating row (recommendation training
    #: parity); "sum" = weights summed per (entity, target) pair
    #: (e-commerce pair_counts parity)
    aggregate: str = "rows"
    #: also fold target-side (item) rows against the updated users
    fold_items: bool = False
    #: events feeding incremental count delta-merges (e.g. buy counts
    #: behind e-commerce popularity fallback)
    count_events: Tuple[str, ...] = ()
    channel_name: Optional[str] = None


@dataclasses.dataclass
class FoldinFactors:
    """Generic accessors over an engine's factor model, returned by
    ``Algorithm.foldin_factors(model)`` so the controller can solve
    without knowing the model class."""

    user_vocab: np.ndarray
    item_vocab: np.ndarray
    U: np.ndarray
    V: np.ndarray
    V_device: Optional[object] = None   # resident device copy, if cached


def upsert_factor_rows(vocab: np.ndarray, M: np.ndarray,
                       rows: Dict[str, np.ndarray]
                       ) -> Tuple[np.ndarray, np.ndarray]:
    """Insert/overwrite factor rows by string id, keeping the vocab
    SORTED (the `vocab_index` binary-search contract every model relies
    on). Returns (vocab', M'); inputs are never mutated."""
    if not rows:
        return vocab, M
    M2 = np.array(M, copy=True)
    fresh: List[Tuple[str, np.ndarray]] = []
    for rid, row in rows.items():
        idx = vocab_index(vocab, rid)
        if idx is None:
            fresh.append((str(rid), np.asarray(row, M2.dtype)))
        else:
            M2[idx] = row
    if not fresh:
        return vocab, M2
    fresh.sort(key=lambda t: t[0])
    ids = np.asarray([t[0] for t in fresh], dtype=object)
    new_rows = np.stack([t[1] for t in fresh])
    pos = np.searchsorted(vocab, ids)
    return (np.insert(vocab, pos, ids),
            np.insert(M2, pos, new_rows, axis=0))


def read_entity_ratings(spec: FoldinSpec, entity_id: str,
                        side: str = "user"
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """One entity's FULL rating history through the columnar find path:
    (opposite-side ids, values) under the spec's event→value mapping —
    exactly the training read's semantics restricted to one entity, so a
    folded row solves the same least squares a retrain would."""
    from predictionio_tpu.data.columnar import property_column
    from predictionio_tpu.data.eventstore import EventStoreClient
    from predictionio_tpu.data.ingest import event_columns

    if side == "user":
        filters = dict(entity_type=spec.entity_type, entity_id=entity_id,
                       target_entity_type=spec.target_entity_type)
        other = "target_entity_id"
    else:
        filters = dict(entity_type=spec.entity_type,
                       target_entity_type=spec.target_entity_type,
                       target_entity_id=entity_id)
        other = "entity_id"
    table = EventStoreClient.find_columnar(
        spec.app_name, spec.channel_name,
        event_names=list(spec.event_names), ordered=False,
        columns=("event", other, "properties"), **filters)
    events, others = event_columns(table, "event", other)
    values = np.ones(len(events), np.float32)
    for name in set(events.tolist()):
        if name != spec.rate_event:
            values[events == name] = float(
                spec.event_weights.get(name, 1.0))
    if spec.rate_event is not None:
        is_rate = events == spec.rate_event
        if is_rate.any():
            import pyarrow as pa

            # a rate event without a rating property is dropped (the
            # training read raises; the online path must keep serving)
            values[is_rate] = property_column(
                table.filter(pa.array(is_rate)), "rating")
    keep = np.fromiter((o is not None for o in others), bool,
                       count=len(others)) & ~np.isnan(values)
    others, values = others[keep], values[keep]
    if spec.aggregate == "sum" and len(others):
        uniq, inv = np.unique(others, return_inverse=True)
        sums = np.zeros(len(uniq), np.float32)
        np.add.at(sums, inv, values)
        return uniq, sums
    return others, values


def resolve_foldin(result) -> Optional[Tuple[int, "FoldinSpec"]]:
    """The (algorithm index, spec) a TrainResult folds through, or None
    when unsupported. Exactly ONE algorithm may implement the hooks —
    with several, which model absorbs an event is ambiguous."""
    hits = []
    for i, (algo, model) in enumerate(zip(result.algorithms,
                                          result.models)):
        fn = getattr(algo, "foldin_spec", None)
        if fn is None:
            continue
        try:
            spec = fn(model, result.engine_params)
        except Exception:
            logger.exception("foldin_spec failed on %s",
                             type(algo).__name__)
            continue
        if spec is not None:
            hits.append((i, spec))
    if len(hits) != 1:
        return None
    return hits[0]


def register_drift_release(base: Release) -> Optional[Release]:
    """Register the fold-in drift as its own release revision (versioned
    under the base's variant), so the registry lineage shows WHEN a
    serving model started drifting from its trained blob and
    ``pio rollback`` has an explicit row to mark ROLLED_BACK. One row
    per drift generation — re-registered only after the next real
    deploy, never per apply. Best-effort: a registry outage must not
    stop fold-in."""
    from predictionio_tpu.storage.registry import Storage

    now_ms = int(time.time() * 1000)
    drift = Release(
        engine_id=base.engine_id,
        engine_version=base.engine_version,
        engine_variant=base.engine_variant,
        instance_id=base.instance_id,
        params_digest=base.params_digest,
        model_digest="",             # the resident model drifts from the blob
        status="LIVE",
        batch=f"foldin drift of v{base.version}",
        history=[
            {"status": "REGISTERED", "timeMs": now_ms,
             "reason": f"online fold-in drift of release v{base.version}"},
            {"status": "LIVE", "timeMs": now_ms,
             "reason": "first fold-in apply"},
        ],
    )
    try:
        releases = Storage.get_meta_data_releases()
        releases.insert(drift)
        # a FLEET folds in concurrently: N replicas each reach their
        # first apply over the same base and each insert a drift row.
        # Converge on one — every replica keeps the lowest-versioned
        # LIVE row for this generation and retires the rest; the store
        # serializes the inserts, so whichever replica commits later
        # sees both rows and the fleet agrees on the winner.
        peers = sorted(
            (r for r in releases.get_all()
             if r.status == "LIVE" and r.batch == drift.batch),
            key=lambda r: r.version)
        for extra in peers[1:]:
            releases.set_status(
                extra.id, "RETIRED",
                reason=f"duplicate drift row; v{peers[0].version} wins")
        if peers and peers[0].id != drift.id:
            drift = peers[0]
        releases.set_status(base.id, "RETIRED",
                            reason=f"superseded: fold-in drift v"
                                   f"{drift.version}")
        logger.info("registered fold-in drift release v%d over v%d",
                    drift.version, base.version)
        return drift
    except Exception:
        logger.exception("fold-in drift registration failed")
        return None


class FoldInController:
    """Collects event deltas (push tap + pull fallback), batch-solves
    pending rows on device, and swaps updated models into the live
    serving unit on a bounded cadence. Thread-safe: the tap runs on the
    ingest writer thread, applies on the server's deploy executor, the
    swap is one reference assignment."""

    def __init__(self, server, config: FoldinConfig, registry=None):
        self.server = server
        self.config = config
        sup = resolve_foldin(server.result)
        if sup is None:
            raise FoldinUnsupported(
                "no single algorithm with foldin hooks in this engine")
        self.algo_index, self.spec = sup
        names = set(self.spec.event_names) | set(self.spec.count_events)
        self._all_events = tuple(sorted(names))
        self._lock = threading.Lock()
        self._dirty_users: "OrderedDict[str, float]" = OrderedDict()
        self._dirty_items: "OrderedDict[str, float]" = OrderedDict()
        self._counts: Dict[str, float] = {}
        self._seen: "OrderedDict[str, None]" = OrderedDict()
        self._watermark_ms = int(time.time() * 1000)
        self._app: Optional[Tuple[int, Optional[int]]] = None
        self._app_warned = False
        self._solver_cache: Optional[Tuple[int, FoldInSolver]] = None
        self._loop = None
        self._task = None
        self._kick: Optional[threading.Event] = None
        self.applied_users = 0
        self.applied_items = 0
        self.applies = 0
        self.last_apply_s: Optional[float] = None
        #: the most recent tap's captured trace context: the next apply
        #: re-enters it, so one trace id stitches ingest request ->
        #: group-commit flush -> fold-in apply -> swap
        self._last_trace = None
        self._registry = registry

        reg = registry
        self._m_pending = foldin_pending(reg)
        self._m_batch = foldin_batch_rows(reg)
        self._m_solve = foldin_solve_seconds(reg)
        self._m_apply = foldin_apply_seconds(reg)
        self._m_rows = foldin_applied_rows(reg)
        self._m_applies = foldin_applies(reg)
        self._m_latency = foldin_event_to_applied(reg)

    # -- delta collection ----------------------------------------------------
    def pending_rows(self) -> int:
        with self._lock:
            return len(self._dirty_users) + len(self._dirty_items)

    def _resolve_app(self) -> Optional[Tuple[int, Optional[int]]]:
        if self._app is None:
            from predictionio_tpu.data.eventstore import resolve_app

            try:
                self._app = resolve_app(self.spec.app_name,
                                        self.spec.channel_name)
            except Exception:
                if not self._app_warned:
                    logger.warning(
                        "fold-in cannot resolve app %r yet; deltas are "
                        "dropped until it exists", self.spec.app_name)
                    self._app_warned = True
                return None
        return self._app

    def tap(self, events, app_id, channel_id) -> None:
        """The WriteBuffer flush tap: called on the ingest writer thread
        AFTER a durable group commit — must stay cheap (filter + mark)."""
        app = self._resolve_app()
        if app is None or (app_id, channel_id) != app:
            return
        self.offer(events)

    def offer(self, events) -> None:
        """Mark the entities behind `events` dirty (dedup'd by event id).
        Accepts data.event.Event objects; unknown event names and other
        entity types are ignored."""
        now = time.monotonic()
        kick = False
        # the tap runs on the writer thread INSIDE the flush's carried
        # trace — capture it so the apply that folds these events stays
        # on the same trace id (None when tracing is off)
        from predictionio_tpu.obs.tracing import capture_context

        ctx = capture_context()
        if ctx is not None:
            self._last_trace = ctx
        with self._lock:
            for e in events:
                eid = e.event_id
                if eid:
                    if eid in self._seen:
                        continue
                    self._seen[eid] = None
                    while len(self._seen) > SEEN_IDS_MAX:
                        self._seen.popitem(last=False)
                self._mark_locked(e.event, e.entity_type, e.entity_id,
                                  e.target_entity_type, e.target_entity_id,
                                  now)
            kick = (len(self._dirty_users) + len(self._dirty_items)
                    >= self.config.max_pending)
        self._update_pending_gauge()
        if kick:
            self._kick_apply()

    def _mark_locked(self, event, entity_type, entity_id,
                     target_entity_type, target_entity_id, now) -> None:
        spec = self.spec
        if entity_type != spec.entity_type or not entity_id:
            return
        relevant = event in spec.event_names and (
            target_entity_type is None
            or target_entity_type == spec.target_entity_type)
        if relevant:
            self._dirty_users.setdefault(entity_id, now)
            # only items the model has NEVER seen fold in — that is the
            # invisibility gap this subsystem closes; a known item's row
            # refreshing with every new rating would re-solve (and
            # re-swap V for) half the catalog under steady traffic, for
            # marginal freshness the next retrain delivers anyway
            if (spec.fold_items and target_entity_id
                    and not self._known_item(target_entity_id)):
                self._dirty_items.setdefault(target_entity_id, now)
        if event in spec.count_events and target_entity_id:
            self._counts[target_entity_id] = \
                self._counts.get(target_entity_id, 0.0) + 1.0

    def _known_item(self, item_id: str) -> bool:
        """Is `item_id` in the CURRENT model's item vocab? (Cheap binary
        search against a per-model cached vocab; unknown on any failure
        so a questionable id still gets a fold attempt.)"""
        try:
            model = self.server._unit.result.models[self.algo_index]
            cached = self._vocab_cache if hasattr(self, "_vocab_cache") \
                else None
            if cached is None or cached[0] is not model:
                algo = self.server._unit.result.algorithms[self.algo_index]
                cached = (model, algo.foldin_factors(model).item_vocab)
                self._vocab_cache = cached
            return vocab_index(cached[1], item_id) is not None
        except Exception:
            return False

    def _update_pending_gauge(self) -> None:
        with self._lock:
            n = len(self._dirty_users) + len(self._dirty_items)
        self._m_pending.set(float(n))

    def _kick_apply(self) -> None:
        """Wake the apply loop early once max_pending rows are waiting."""
        kick = self._kick
        if kick is not None:
            kick.set()

    def pull(self) -> None:
        """Columnar pull fallback: scan events since the event-time
        watermark — the cross-process path (event server in another
        process, bulk imports). Overlap with pushed events dedups by
        event id."""
        app = self._resolve_app()
        if app is None:
            return
        import datetime as _dt

        from predictionio_tpu.data.event import UTC
        from predictionio_tpu.data.eventstore import EventStoreClient
        from predictionio_tpu.data.ingest import event_columns

        since = _dt.datetime.fromtimestamp(self._watermark_ms / 1000.0,
                                           tz=UTC)
        table = EventStoreClient.find_columnar(
            self.spec.app_name, self.spec.channel_name,
            start_time=since, entity_type=self.spec.entity_type,
            event_names=list(self._all_events), ordered=False,
            columns=("event_id", "event", "entity_id",
                     "target_entity_type", "target_entity_id",
                     "event_time_ms"))
        if table.num_rows == 0:
            return
        ids, events, ents, ttypes, tids = event_columns(
            table, "event_id", "event", "entity_id",
            "target_entity_type", "target_entity_id")
        times, = event_columns(table, "event_time_ms")
        now = time.monotonic()
        with self._lock:
            for i in range(len(ids)):
                eid = ids[i]
                if eid and eid in self._seen:
                    continue
                if eid:
                    self._seen[eid] = None
                    while len(self._seen) > SEEN_IDS_MAX:
                        self._seen.popitem(last=False)
                self._mark_locked(events[i], self.spec.entity_type,
                                  ents[i], ttypes[i], tids[i], now)
            # keep the watermark AT the max seen time (not +1ms): a
            # same-millisecond straggler lands in the next overlapping
            # scan and the seen-id set absorbs the re-delivery
            self._watermark_ms = max(self._watermark_ms,
                                     int(times.max()))
        self._update_pending_gauge()

    # -- apply ---------------------------------------------------------------
    def _solver_for(self, factors: np.ndarray, params: ALSParams,
                    device=None) -> FoldInSolver:
        """Per-factor-matrix solver cache: the implicit global Gramian
        and the resident device copy survive across applies until the
        factors object itself changes (a swap/retrain/item fold)."""
        cached = self._solver_cache
        if cached is not None and cached[0] is factors:
            return cached[1]
        solver = FoldInSolver(factors, params,
                              row_len=self.config.row_len,
                              factors_device=device)
        self._solver_cache = (factors, solver)
        return solver

    def _solve_side(self, solver: FoldInSolver, vocab: np.ndarray,
                    entity_ids: List[str], side: str,
                    deferred: Optional[Dict[str, set]] = None,
                    failed: Optional[List[str]] = None
                    ) -> Dict[str, np.ndarray]:
        """Read each entity's history, batch-solve the non-empty ones.
        Targets the model has never seen cannot join a solve (a
        brand-new user rating a brand-new item); `deferred` collects
        them per entity so the caller can re-queue the entity once the
        missing side folds in. An entity whose history READ fails lands
        in `failed` so the caller can requeue it — a transient storage
        error must not silently drop the delta (the entity was already
        popped from the dirty map, and neither push nor pull will
        re-deliver an already-seen event)."""
        kept: List[str] = []
        rated: List[np.ndarray] = []
        values: List[np.ndarray] = []
        for ent in entity_ids:
            try:
                others, vals = read_entity_ratings(self.spec, ent, side)
            except Exception:
                logger.exception("fold-in history read failed for %s %r",
                                 side, ent)
                if failed is not None:
                    failed.append(ent)
                continue
            if not len(others):
                continue
            idx = batch_lookup(vocab, others)
            known = idx >= 0
            if deferred is not None and not known.all():
                deferred[ent] = {str(o) for o in others[~known]}
            if not known.any():
                continue
            kept.append(ent)
            rated.append(idx[known])
            values.append(vals[known])
        if not kept:
            return {}
        t0 = time.perf_counter()
        rows = solver.solve(rated, values)
        self._m_solve.observe(time.perf_counter() - t0)
        self._m_batch.observe(float(len(kept)))
        return {ent: rows[i] for i, ent in enumerate(kept)}

    def _warm_grown_catalog(self, unit) -> None:
        """Pre-compile a catalog-growing drift's scorer shapes before
        cutover (deploy/warm.py's ladder, honoring the server's warmup
        knob). Runs on the caller's thread — apply_pending already sits
        on the deploy executor, so live traffic never waits on XLA.
        Per-unit-lifetime the `als_topk*` ledger gains one catalog-size
        key per item-adding apply; an item folds at most once ever (only
        never-seen items fold), so the keys are bounded by the distinct
        catalog sizes between retrains, not by the event stream."""
        import functools

        from predictionio_tpu.deploy.warm import warmup_unit

        server = self.server
        if not getattr(server, "_effective_warmup", None) or \
                not server._effective_warmup(None):
            return
        t0 = time.perf_counter()
        report = warmup_unit(
            unit, functools.partial(server._predict_batch_unit, unit),
            server.serving_config.batch_max,
            getattr(server, "_last_query", None))
        logger.info("fold-in catalog warmup: buckets=%s compiles=%d "
                    "(%.3fs)", report.buckets, report.compile_delta,
                    time.perf_counter() - t0)

    def apply_pending(self) -> Optional[dict]:
        """One apply tick (synchronous; runs on the deploy executor or a
        caller's thread): pull, snapshot up to max_pending dirty rows,
        solve, hand the engine its new model, swap. Returns a stats dict
        or None when nothing was pending."""
        t_start = time.perf_counter()
        if getattr(self.server, "_canary", None) is not None:
            # a staged rollout is being judged against the incumbent;
            # folding the incumbent mid-window would poison the judge's
            # baseline — deltas stay pending until the verdict lands
            return None
        slo = getattr(self.server, "_slo", None)
        if slo is not None and slo.breached(exclude_kinds=("freshness",)):
            # SLO gating (obs/slo.py): while the serving latency/error
            # SLO burns, a swap could make things worse — deltas stay
            # pending (not lost) until the burn clears. Freshness
            # breaches are EXCLUDED: deferring the apply is exactly what
            # would deepen a freshness breach.
            self._m_applies.inc(outcome="deferred")
            logger.warning("fold-in apply deferred: serving SLO breached")
            return None
        try:
            self.pull()
        except Exception:
            logger.exception("fold-in pull scan failed (push-only tick)")
        with self._lock:
            users: Dict[str, float] = {}
            items: Dict[str, float] = {}
            budget = self.config.max_pending
            while self._dirty_users and len(users) < budget:
                uid, ts = self._dirty_users.popitem(last=False)
                users[uid] = ts
            budget -= len(users)
            while self._dirty_items and len(items) < budget:
                iid, ts = self._dirty_items.popitem(last=False)
                items[iid] = ts
            counts, self._counts = self._counts, {}
        self._update_pending_gauge()
        if not users and not items and not counts:
            self._m_applies.inc(outcome="empty")
            return None
        def _requeue() -> None:
            # put the rows back: an apply failure must not LOSE deltas
            with self._lock:
                for uid, ts in users.items():
                    self._dirty_users.setdefault(uid, ts)
                for iid, ts in items.items():
                    self._dirty_items.setdefault(iid, ts)
                for tid, c in counts.items():
                    self._counts[tid] = self._counts.get(tid, 0.0) + c
            self._update_pending_gauge()

        from predictionio_tpu.deploy.warm import FoldinSwapRaced
        from predictionio_tpu.obs.tracing import carried

        # re-enter the last tap's trace so this apply (and the swap
        # inside it) is recorded under the ingest request's trace id
        ctx, self._last_trace = self._last_trace, None
        try:
            if ctx is not None:
                with carried(ctx, "foldin_apply",
                             registry=self._registry,
                             attrs={"users": len(users),
                                    "items": len(items)}):
                    stats = self._apply(users, items, counts)
            else:
                stats = self._apply(users, items, counts)
        except FoldinSwapRaced as e:
            # a reload/deploy/rollback/canary cutover landed mid-solve
            # and won the compare-and-swap — expected under operation,
            # not an error: the next tick re-solves against the NEW unit
            _requeue()
            self._last_trace = ctx
            self._m_applies.inc(outcome="raced")
            logger.info("fold-in apply raced a deploy cutover, deltas "
                        "requeued: %s", e)
            return None
        except Exception:
            _requeue()
            self._last_trace = ctx
            self._m_applies.inc(outcome="error")
            raise
        self._m_applies.inc(outcome="applied")
        self.applies += 1
        dt = time.perf_counter() - t_start
        self.last_apply_s = dt
        self._m_apply.observe(dt)
        from predictionio_tpu.obs.trace_context import record_event

        record_event("foldin_apply", {
            "users": len(users), "items": len(items),
            "applySeconds": round(dt, 4)},
            trace_id=ctx.trace_id if ctx is not None else None)
        now = time.monotonic()
        for ts in list(users.values()) + list(items.values()):
            self._m_latency.observe(max(0.0, now - ts))
        stats["applySeconds"] = dt
        return stats

    def _apply(self, users: Dict[str, float], items: Dict[str, float],
               counts: Dict[str, float]) -> dict:
        server = self.server
        unit = server._unit
        algo = unit.result.algorithms[self.algo_index]
        model = unit.result.models[self.algo_index]
        fa: FoldinFactors = algo.foldin_factors(model)
        params = self.spec.als_params

        user_rows = {}
        deferred: Dict[str, set] = {}
        failed_users: List[str] = []
        failed_items: List[str] = []
        if users:
            solver = self._solver_for(fa.V, params, device=fa.V_device)
            user_rows = self._solve_side(solver, fa.item_vocab,
                                         list(users), "user",
                                         deferred=deferred,
                                         failed=failed_users)
        item_rows = {}
        if items and self.spec.fold_items:
            # items solve against the UPDATED user side (alternating
            # order: a brand-new user's row exists before their item's
            # raters are gathered)
            uv, U2 = upsert_factor_rows(fa.user_vocab, fa.U, user_rows)
            item_solver = FoldInSolver(U2, params,
                                       row_len=self.config.row_len)
            item_rows = self._solve_side(item_solver, uv, list(items),
                                         "item", failed=failed_items)
            if item_rows:
                # the item side (and so the cached V Gramian) changes
                self._solver_cache = None
        if failed_users or failed_items:
            # requeue read-failed entities (keeping their first-seen
            # timestamp) and pull them out of THIS tick's latency
            # observation — they did not apply
            with self._lock:
                for ent in failed_users:
                    ts = users.pop(ent, None)
                    self._dirty_users.setdefault(
                        ent, ts if ts is not None else time.monotonic())
                for ent in failed_items:
                    ts = items.pop(ent, None)
                    self._dirty_items.setdefault(
                        ent, ts if ts is not None else time.monotonic())
            self._update_pending_gauge()
        if not user_rows and not item_rows and not counts:
            return {"users": 0, "items": 0, "counts": 0}

        new_model = algo.foldin_apply(model, self.spec, user_rows,
                                      item_rows, counts)
        new_models = list(unit.result.models)
        new_models[self.algo_index] = new_model
        applied = len(user_rows) + len(item_rows)
        drift = None
        if unit.foldin_of is None and unit.release is not None:
            # registered BEFORE the compare-and-swap: a raced swap can
            # strand one cosmetic drift row in the registry (best-effort
            # by contract), but a crash between swap and registration
            # could never hide a live drift from `pio releases`
            drift = register_drift_release(unit.release)
        new_unit = server.build_foldin_unit(new_models, applied,
                                            drift_release=drift,
                                            base_unit=unit)
        if item_rows:
            # the drift GREW the catalog, re-keying the scorers' shapes
            # (n_items is part of the als_topk compile key) — drive the
            # bucket ladder NOW, on this deploy-executor thread, so the
            # first post-swap query never pays the compile; user-only
            # drifts keep the base's shapes and skip this entirely
            self._warm_grown_catalog(new_unit)
        server.swap_foldin_unit(new_unit, loop=self._loop,
                                expected_base=unit)
        if user_rows:
            self._m_rows.inc(len(user_rows), side="user")
            self.applied_users += len(user_rows)
        if item_rows:
            self._m_rows.inc(len(item_rows), side="item")
            self.applied_items += len(item_rows)
        if item_rows and deferred:
            # users whose ratings referenced a then-unknown item that
            # JUST folded in: re-queue them so the next tick completes
            # their row with the now-known item (bounded: only targets
            # that actually folded re-queue — no unknown-forever loop)
            folded = set(item_rows)
            now = time.monotonic()
            requeue = [u for u, missing in deferred.items()
                       if missing & folded]
            if requeue:
                with self._lock:
                    for uid in requeue:
                        self._dirty_users.setdefault(uid, now)
                self._update_pending_gauge()
        logger.info("fold-in applied %d user / %d item rows "
                    "(%d count deltas) onto instance %s",
                    len(user_rows), len(item_rows), len(counts),
                    unit.instance.id)
        return {"users": len(user_rows), "items": len(item_rows),
                "counts": len(counts)}

    # -- lifecycle -----------------------------------------------------------
    def start(self) -> None:
        """Arm the push tap and (when called on a running loop) the
        apply task. Callers without a loop (tests) drive
        `apply_pending` themselves."""
        from predictionio_tpu.data.write_buffer import add_flush_tap

        add_flush_tap(self.tap)
        self._kick = threading.Event()
        try:
            import asyncio

            self._loop = asyncio.get_running_loop()
        except RuntimeError:
            self._loop = None
            return
        self._task = self._loop.create_task(self._run())

    async def _run(self):
        import asyncio

        interval = self.config.apply_interval_s
        loop = self._loop
        while True:
            kicked = self._kick.is_set()
            if not kicked:
                # sleep the interval, but wake early on a kick (the
                # threading.Event is set from the ingest writer thread;
                # poll it at a fraction of the interval — cheap, and it
                # keeps the controller loop-agnostic for sync drivers)
                slept = 0.0
                step = min(interval, max(0.05, interval / 8.0))
                while slept < interval and not self._kick.is_set():
                    await asyncio.sleep(step)
                    slept += step
            self._kick.clear()
            try:
                await loop.run_in_executor(self.server._deploy_executor,
                                           self.apply_pending)
            except asyncio.CancelledError:
                raise
            except Exception:
                logger.exception("fold-in apply tick failed")

    async def aclose(self) -> None:
        import asyncio

        self.stop_tap()
        task = self._task
        if task is not None and not task.done():
            task.cancel()
            try:
                await task
            except (asyncio.CancelledError, Exception):
                # the cancel (or whatever the tick died of) is expected
                # here; BaseException kill points (CrashError) still
                # propagate so chaos tests die where they were injected
                pass
        self._task = None

    def stop_tap(self) -> None:
        from predictionio_tpu.data.write_buffer import remove_flush_tap

        remove_flush_tap(self.tap)

    def status_dict(self) -> dict:
        return {
            "enabled": True,
            "applyIntervalS": self.config.apply_interval_s,
            "maxPending": self.config.max_pending,
            "pendingRows": self.pending_rows(),
            "applies": self.applies,
            "appliedUserRows": self.applied_users,
            "appliedItemRows": self.applied_items,
            "lastApplySeconds": self.last_apply_s,
        }
