"""Storage SPI: interfaces every backend implements, plus metadata records.

Parity map (reference file:line):
  * EventStore      <- LEvents trait (data/.../storage/LEvents.scala:40-513);
                       the parallel PEvents path (PEvents.scala:38-189) becomes
                       EventStore.find_columnar -> pyarrow table for training
  * Apps            <- Apps.scala:32-61
  * AccessKeys      <- AccessKeys.scala:35-77
  * Channels        <- Channels.scala:32-82 (name rule :54-57)
  * EngineInstances <- EngineInstances.scala:46-180
  * EvaluationInstances <- EvaluationInstances.scala:42-138
  * Models          <- Models.scala:33-86

The rebuild's API is synchronous; the event server wraps calls in its asyncio
executor. Instead of Scala's Option[Option[T]] target filters, the sentinel
UNFILTERED distinguishes "no filter" from "must be absent" (None).
"""

from __future__ import annotations

import abc
import contextlib
import dataclasses
import datetime as _dt
import io
import os
import random
import re
import secrets
from typing import BinaryIO, Dict, Iterator, List, Optional, Sequence

from predictionio_tpu.data.datamap import PropertyMap
from predictionio_tpu.data.event import Event, UTC


class StorageError(Exception):
    """Backend-level storage failure (parity with StorageException)."""


class _Unfiltered:
    """Sentinel: this filter is not applied at all."""

    _instance = None

    def __new__(cls):
        if cls._instance is None:
            cls._instance = super().__new__(cls)
        return cls._instance

    def __repr__(self):
        return "UNFILTERED"


UNFILTERED = _Unfiltered()


#: urandom-seeded PRNG for id generation. uuid4() draws from os.urandom
#: per call — a syscall that costs ~90us under sandboxed kernels, which
#: at group-commit ingest rates dominated the submit path. Ids need
#: uniqueness (128 random bits), not cryptographic strength; one urandom
#: seed per process keeps independent processes collision-free.
_id_rng = random.Random()
if hasattr(os, "register_at_fork"):
    # a forked child inherits the parent's PRNG state; without a reseed
    # both sides would emit the SAME id stream and the idempotent insert
    # paths would silently drop the child's events as duplicates
    os.register_at_fork(after_in_child=_id_rng.seed)


def generate_id() -> str:
    """Random identifier for events/instances (JDBCUtils.generateId parity).

    No lock: random.Random.getrandbits is a single C call, atomic under
    the GIL (a shared lock here would also be a fork-time deadlock
    hazard — a child forked while another thread held it could never
    generate an id again)."""
    return f"{_id_rng.getrandbits(128):032x}"


# ---------------------------------------------------------------------------
# Metadata records
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class App:
    """Apps.scala:32 — (id, name, description)."""
    id: int
    name: str
    description: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class AccessKey:
    """AccessKeys.scala:35 — (key, appid, allowed event names; [] = all)."""
    key: str
    appid: int
    events: Sequence[str] = ()


CHANNEL_NAME_RE = re.compile(r"^[a-zA-Z0-9-]{1,16}$")
CHANNEL_NAME_CONSTRAINT = "Only alphanumeric and - characters are allowed and max length is 16."


def is_valid_channel_name(name: str) -> bool:
    """Channels.scala:54-57 — 1-16 alphanumeric or '-' characters."""
    return bool(CHANNEL_NAME_RE.match(name))


@dataclasses.dataclass(frozen=True)
class Channel:
    """Channels.scala:32 — (id, name unique within app, appid)."""
    id: int
    name: str
    appid: int

    def __post_init__(self):
        if not is_valid_channel_name(self.name):
            raise ValueError(
                f"Invalid channel name: {self.name}. {CHANNEL_NAME_CONSTRAINT}")


def _utcnow() -> _dt.datetime:
    return _dt.datetime.now(tz=UTC)


@dataclasses.dataclass
class EngineInstance:
    """EngineInstances.scala:46 — one train run and its deployable artifact.

    `runtime_conf` replaces the reference's sparkConf (jax/XLA settings:
    mesh shape, precision, compilation flags).
    """
    id: str = ""
    status: str = "INIT"  # INIT -> COMPLETED (failed runs stay INIT)
    start_time: _dt.datetime = dataclasses.field(default_factory=_utcnow)
    end_time: _dt.datetime = dataclasses.field(default_factory=_utcnow)
    engine_id: str = ""
    engine_version: str = ""
    engine_variant: str = ""
    engine_factory: str = ""
    batch: str = ""
    env: Dict[str, str] = dataclasses.field(default_factory=dict)
    runtime_conf: Dict[str, str] = dataclasses.field(default_factory=dict)
    data_source_params: str = ""
    preparator_params: str = ""
    algorithms_params: str = ""
    serving_params: str = ""


@dataclasses.dataclass
class EvaluationInstance:
    """EvaluationInstances.scala:42 — one evaluation run and its results."""
    id: str = ""
    status: str = ""
    start_time: _dt.datetime = dataclasses.field(default_factory=_utcnow)
    end_time: _dt.datetime = dataclasses.field(default_factory=_utcnow)
    evaluation_class: str = ""
    engine_params_generator_class: str = ""
    batch: str = ""
    env: Dict[str, str] = dataclasses.field(default_factory=dict)
    runtime_conf: Dict[str, str] = dataclasses.field(default_factory=dict)
    evaluator_results: str = ""
    evaluator_results_html: str = ""
    evaluator_results_json: str = ""


@dataclasses.dataclass(frozen=True)
class Model:
    """Models.scala:33 — serialized model blob keyed by engine instance id."""
    id: str
    models: bytes


#: the release lifecycle (deploy/ subsystem). A release is REGISTERED by
#: run_train, becomes CANARY while a traffic split judges it, LIVE when
#: serving full traffic, RETIRED when superseded by a newer LIVE release,
#: and ROLLED_BACK when the SLO guard (or an operator) rejected it.
RELEASE_STATUSES = ("REGISTERED", "CANARY", "LIVE", "RETIRED", "ROLLED_BACK")


@dataclasses.dataclass
class Release:
    """One deployable version of an engine variant (deploy/ subsystem).

    The EngineInstance row records *how a train ran*; the Release records
    *what is shippable*: a monotonically increasing version per
    (engine_id, engine_version, engine_variant), content digests of the
    params and the serialized model blob (so "did anything actually
    change?" is answerable without loading the blob), and a status whose
    full lineage is kept in `history` as
    ``[{"status": ..., "timeMs": ..., "reason": ...}, ...]``.
    """

    id: str = ""
    version: int = 0                 # assigned by insert(): max+1 per variant
    engine_id: str = ""
    engine_version: str = ""
    engine_variant: str = ""
    instance_id: str = ""            # the COMPLETED EngineInstance behind it
    params_digest: str = ""
    model_digest: str = ""
    model_size_bytes: int = 0
    status: str = "REGISTERED"
    created_time: _dt.datetime = dataclasses.field(default_factory=_utcnow)
    train_seconds: float = 0.0
    batch: str = ""
    history: List[Dict] = dataclasses.field(default_factory=list)


# ---------------------------------------------------------------------------
# Metadata store interfaces
# ---------------------------------------------------------------------------

class Apps(abc.ABC):
    @abc.abstractmethod
    def insert(self, app: App) -> Optional[int]:
        """Insert; generates an id when app.id == 0. Returns the id."""

    @abc.abstractmethod
    def get(self, app_id: int) -> Optional[App]: ...

    @abc.abstractmethod
    def get_by_name(self, name: str) -> Optional[App]: ...

    @abc.abstractmethod
    def get_all(self) -> List[App]: ...

    @abc.abstractmethod
    def update(self, app: App) -> None: ...

    @abc.abstractmethod
    def delete(self, app_id: int) -> None: ...


class AccessKeys(abc.ABC):
    @abc.abstractmethod
    def insert(self, k: AccessKey) -> Optional[str]:
        """Insert; generates a key when k.key is empty. Returns the key."""

    @abc.abstractmethod
    def get(self, key: str) -> Optional[AccessKey]: ...

    @abc.abstractmethod
    def get_all(self) -> List[AccessKey]: ...

    @abc.abstractmethod
    def get_by_appid(self, appid: int) -> List[AccessKey]: ...

    @abc.abstractmethod
    def update(self, k: AccessKey) -> None: ...

    @abc.abstractmethod
    def delete(self, key: str) -> None: ...

    @staticmethod
    def generate_key() -> str:
        """Random URL-safe key (AccessKeys.scala:68 parity)."""
        return secrets.token_urlsafe(48)


class Channels(abc.ABC):
    @abc.abstractmethod
    def insert(self, channel: Channel) -> Optional[int]:
        """Insert; generates an id when channel.id == 0. Returns the id."""

    @abc.abstractmethod
    def get(self, channel_id: int) -> Optional[Channel]: ...

    @abc.abstractmethod
    def get_by_appid(self, appid: int) -> List[Channel]: ...

    @abc.abstractmethod
    def delete(self, channel_id: int) -> None: ...


class EngineInstances(abc.ABC):
    @abc.abstractmethod
    def insert(self, i: EngineInstance) -> str: ...

    @abc.abstractmethod
    def get(self, instance_id: str) -> Optional[EngineInstance]: ...

    @abc.abstractmethod
    def get_all(self) -> List[EngineInstance]: ...

    @abc.abstractmethod
    def get_completed(self, engine_id: str, engine_version: str,
                      engine_variant: str) -> List[EngineInstance]:
        """COMPLETED instances, latest start_time first (EngineInstances.scala:88)."""

    def get_latest_completed(self, engine_id: str, engine_version: str,
                             engine_variant: str) -> Optional[EngineInstance]:
        """EngineInstances.scala:82."""
        completed = self.get_completed(engine_id, engine_version, engine_variant)
        return completed[0] if completed else None

    @abc.abstractmethod
    def update(self, i: EngineInstance) -> None: ...

    @abc.abstractmethod
    def delete(self, instance_id: str) -> None: ...


class EvaluationInstances(abc.ABC):
    @abc.abstractmethod
    def insert(self, i: EvaluationInstance) -> str: ...

    @abc.abstractmethod
    def get(self, instance_id: str) -> Optional[EvaluationInstance]: ...

    @abc.abstractmethod
    def get_all(self) -> List[EvaluationInstance]: ...

    @abc.abstractmethod
    def get_completed(self) -> List[EvaluationInstance]:
        """EVALCOMPLETED instances, latest start_time first."""

    @abc.abstractmethod
    def update(self, i: EvaluationInstance) -> None: ...

    @abc.abstractmethod
    def delete(self, instance_id: str) -> None: ...


class Models(abc.ABC):
    """Binary model blob store (Models.scala:33-86)."""

    #: True where `open_write` hands out the store's own file, so bytes
    #: reach the store as they are written; False where it buffers the
    #: blob for `insert` (a store that keeps a blob in a row)
    streams_writes = False

    @abc.abstractmethod
    def insert(self, model: Model) -> None: ...

    @contextlib.contextmanager
    def open_write(self, model_id: str) -> Iterator[BinaryIO]:
        """A binary writable for `model_id`'s blob. On a clean exit the
        blob is in the store, whole, as after `insert`; on an exception
        nothing of it is visible and the previous blob, if any, stays."""
        from predictionio_tpu.obs.tracing import span

        buf = io.BytesIO()
        yield buf
        with span("persist_commit"):
            self.insert(Model(id=model_id, models=buf.getvalue()))

    @abc.abstractmethod
    def get(self, model_id: str) -> Optional[Model]: ...

    @abc.abstractmethod
    def delete(self, model_id: str) -> None: ...


class Releases(abc.ABC):
    """Versioned release manifests (deploy/ subsystem; no reference
    counterpart — the reference redeploys whatever instance is latest
    with no way back)."""

    @abc.abstractmethod
    def insert(self, release: Release) -> str:
        """Persist; assigns `id` (when empty) and the next `version` for
        the release's (engine_id, engine_version, engine_variant).
        Returns the id."""

    @abc.abstractmethod
    def get(self, release_id: str) -> Optional[Release]: ...

    @abc.abstractmethod
    def get_all(self) -> List[Release]: ...

    @abc.abstractmethod
    def get_for_variant(self, engine_id: str, engine_version: str,
                        engine_variant: str) -> List[Release]:
        """All releases of one variant, newest version first."""

    @abc.abstractmethod
    def update(self, release: Release) -> None: ...

    @abc.abstractmethod
    def delete(self, release_id: str) -> None: ...

    # -- lifecycle conveniences (shared across backends) ---------------------
    def get_by_version(self, engine_id: str, engine_version: str,
                       engine_variant: str, version: int
                       ) -> Optional[Release]:
        for r in self.get_for_variant(engine_id, engine_version,
                                      engine_variant):
            if r.version == version:
                return r
        return None

    def latest(self, engine_id: str, engine_version: str,
               engine_variant: str,
               status: Optional[str] = None) -> Optional[Release]:
        """Newest release of the variant, optionally filtered by status."""
        for r in self.get_for_variant(engine_id, engine_version,
                                      engine_variant):
            if status is None or r.status == status:
                return r
        return None

    def set_status(self, release_id: str, status: str,
                   reason: str = "") -> Optional[Release]:
        """Transition a release's status, appending to its history
        lineage. Returns the updated release (None when unknown).

        Idempotent per status: re-asserting the release's CURRENT status
        is a no-op (no duplicate history entry, no write) — the
        orchestrator's crash recovery re-runs half-done transitions, and
        "promote again" must never record a second promote. Kill points
        bracket the durable write (``releases:set-status:pre`` /
        ``releases:set-status:committed``) so chaos tests can die
        mid-registry-commit on either side of it."""
        from predictionio_tpu.storage.faults import maybe_kill

        if status not in RELEASE_STATUSES:
            raise ValueError(f"unknown release status {status!r}")
        release = self.get(release_id)
        if release is None:
            return None
        if release.status == status:
            return release
        maybe_kill("releases:set-status:pre")
        release.status = status
        release.history = list(release.history) + [{
            "status": status,
            "timeMs": int(_utcnow().timestamp() * 1000),
            "reason": reason,
        }]
        self.update(release)
        maybe_kill("releases:set-status:committed")
        return release


# ---------------------------------------------------------------------------
# Event store interface
# ---------------------------------------------------------------------------

class EventStore(abc.ABC):
    """Event CRUD + query + aggregation, per (app_id, channel_id) namespace.

    LEvents trait parity (LEvents.scala:40-513). All methods synchronous; the
    REST layer offloads to a thread pool. `find_columnar` is the training-path
    analog of PEvents.find, returning a pyarrow.Table.
    """

    @abc.abstractmethod
    def init_channel(self, app_id: int, channel_id: Optional[int] = None) -> bool:
        """Initialize the namespace (LEvents.init:53)."""

    @abc.abstractmethod
    def remove_channel(self, app_id: int, channel_id: Optional[int] = None) -> bool:
        """Remove the namespace and all its events (LEvents.remove:63)."""

    @abc.abstractmethod
    def close(self) -> None: ...

    @abc.abstractmethod
    def insert(self, event: Event, app_id: int,
               channel_id: Optional[int] = None) -> str:
        """Insert one event, returning its id (LEvents.futureInsert:90)."""

    def insert_batch(self, events: Sequence[Event], app_id: int,
                     channel_id: Optional[int] = None) -> List[str]:
        """LEvents.futureInsertBatch:106 — override for bulk backends."""
        return [self.insert(e, app_id, channel_id) for e in events]

    def insert_batch_idempotent(self, events: Sequence[Event], app_id: int,
                                channel_id: Optional[int] = None
                                ) -> List[str]:
        """Like insert_batch, but events whose (pre-assigned) id is already
        persisted are skipped instead of duplicated or rejected — the
        retry contract of the group-commit flush path
        (data/write_buffer.py): after an AMBIGUOUS failure (fault fired
        after the backend may have committed) the retry must neither lose
        nor double-write. Every event must carry an event_id. Returns the
        ids in input order. Backends override with a native upsert-ignore
        (sqlite INSERT OR IGNORE, postgres ON CONFLICT DO NOTHING); this
        default probes with get() per event — correct everywhere, slow,
        and only ever on the retry path."""
        missing = []
        for e in events:
            if not e.event_id:
                raise StorageError(
                    "insert_batch_idempotent requires pre-assigned event ids")
            if self.get(e.event_id, app_id, channel_id) is None:
                missing.append(e)
        if missing:
            self.insert_batch(missing, app_id, channel_id)
        return [e.event_id for e in events]

    def compact(self, app_id: int, channel_id: Optional[int] = None,
                ttl_days: Optional[float] = None) -> Dict[str, int]:
        """Maintenance sweep: fold deletes into storage, merge small
        physical units, and (when ``ttl_days`` is given) drop events with
        ``event_time`` older than the retention window. Returns counter
        stats (keys vary by backend; ``removed_rows`` is always present).
        Runnable via ``pio compact``. The default covers retention only,
        via the row API — correct for every backend; bulk backends
        override (sqlite/postgres: one DELETE; parquet: crash-safe
        fragment rewrite, storage/parquet_events.py)."""
        removed = 0
        if ttl_days is not None:
            cutoff = _utcnow() - _dt.timedelta(days=ttl_days)
            expired = [e.event_id for e in self.find(
                app_id, channel_id, until_time=cutoff) if e.event_id]
            for eid in expired:
                if self.delete(eid, app_id, channel_id):
                    removed += 1
        return {"removed_rows": removed}

    @abc.abstractmethod
    def get(self, event_id: str, app_id: int,
            channel_id: Optional[int] = None) -> Optional[Event]: ...

    @abc.abstractmethod
    def delete(self, event_id: str, app_id: int,
               channel_id: Optional[int] = None) -> bool: ...

    @abc.abstractmethod
    def find(
        self,
        app_id: int,
        channel_id: Optional[int] = None,
        start_time: Optional[_dt.datetime] = None,
        until_time: Optional[_dt.datetime] = None,
        entity_type: Optional[str] = None,
        entity_id: Optional[str] = None,
        event_names: Optional[Sequence[str]] = None,
        target_entity_type=UNFILTERED,
        target_entity_id=UNFILTERED,
        limit: Optional[int] = None,
        reversed_order: bool = False,
    ) -> Iterator[Event]:
        """LEvents.futureFind:188 — time range [start, until), optional
        filters; limit=None -> all, limit=-1 -> all (reference parity);
        reversed_order returns latest first (only valid with entityType+entityId
        in the reference; the rebuild allows it everywhere)."""

    def aggregate_properties(
        self,
        app_id: int,
        entity_type: str,
        channel_id: Optional[int] = None,
        start_time: Optional[_dt.datetime] = None,
        until_time: Optional[_dt.datetime] = None,
        required: Optional[Sequence[str]] = None,
    ) -> Dict[str, PropertyMap]:
        """LEvents.futureAggregateProperties:215 — fold special events.

        Backed by the backend's columnar scan + the vectorized sort/
        segment fold (data/columnar.aggregate_properties_table), so every
        backend's training read skips per-Event materialization; the
        row-at-a-time fold (data/aggregator.py) remains the serving-path
        and contract-spec reference implementation.
        """
        from predictionio_tpu.data.columnar import aggregate_properties_table

        table = self.find_columnar(
            app_id=app_id,
            channel_id=channel_id,
            ordered=False,      # the fold sorts per entity itself
            start_time=start_time,
            until_time=until_time,
            entity_type=entity_type,
            event_names=list(_SPECIAL),
            columns=("event", "entity_id", "properties", "event_time_ms"),
        )
        return aggregate_properties_table(table, required=required)

    def find_columnar(self, app_id: int, channel_id: Optional[int] = None,
                      ordered: bool = True, **filters):
        """Training-path read: events as a pyarrow.Table (PEvents.find analog).

        ``ordered=False`` is a hint that the caller (a training read whose
        math is permutation-invariant — the JdbcRDD-partition contract)
        accepts ARBITRARY row order; backends may then skip the time sort.
        The default keeps the row path's chronological guarantee (exports,
        dumps). ``shard=(index, count[, snapshot])`` restricts the scan
        to one of `count` disjoint row partitions (the multi-host
        partitioned training read); multi-process readers must agree on
        one `read_snapshot()` token (third element) so concurrent ingest
        cannot skew the partition bounds between them. Backends that
        cannot partition must refuse rather than silently hand every
        process the full set. Default
        implementation materializes through `find`; columnar backends
        override with a direct scan.
        """
        if filters.get("shard") is not None:
            raise StorageError(
                f"{type(self).__name__} does not support sharded "
                "(partitioned) reads")
        filters.pop("shard", None)
        columns = filters.pop("columns", None)
        from predictionio_tpu.data.columnar import (
            events_to_table, projected_schema,
        )
        table = events_to_table(self.find(app_id, channel_id, **filters))
        return (table if columns is None
                else table.select(projected_schema(columns).names))

    def snapshot_digest(self, app_id: int,
                        channel_id: Optional[int] = None) -> Optional[str]:
        """Durable fingerprint of the namespace's current contents, or
        None when the backend cannot produce one. Equal digests mean a
        repeated training scan would return the same rows, in this
        process or another: the deploy orchestrator compares it from tick
        to tick, and the ingest-side scan cache (data/ingest.py) keys
        with it where the backend has no `change_token`. Backends include
        enough state (row window + count, fragment + tombstone lists)
        that both appends and deletes change the digest."""
        return None

    def change_token(self, app_id: int,
                     channel_id: Optional[int] = None):
        """O(1), process-local stand-in for `snapshot_digest`, or None
        when the backend has none: a hashable value that differs from
        every token this process took of the namespace before a commit
        that came since, whoever committed. It need tell apart only the
        states THIS process can have seen (it keys a cache that lives and
        dies with the process), so it may read counters of the connection
        where the digest reads the table. A token that cannot be compared
        with an earlier one (another thread's connection, a store opened
        again) must differ from it."""
        return None


def shard_window(lo_all: int, hi_all: int, shard) -> "tuple[int, int]":
    """One of `count` near-equal [lo, hi) sub-windows of a numeric
    snapshot range — the shared partition arithmetic for range-sharded
    backends (sqlite rowids, postgres eventTimes). The last window clamps
    to the snapshot end so values arriving after the snapshot can never
    leak into it."""
    idx, count = shard[0], shard[1]
    if not (0 <= idx < count):
        raise StorageError(f"bad shard {shard}")
    span = -(-(hi_all - lo_all) // count)
    return (lo_all + idx * span,
            min(lo_all + (idx + 1) * span, hi_all))


_SPECIAL = ("$set", "$unset", "$delete")
