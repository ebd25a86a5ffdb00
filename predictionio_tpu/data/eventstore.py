"""App-name-facing event store facades.

Parity with the reference's engine-facing facades:
  * `find_by_entity` / `find` <- LEventStore (data/.../store/LEventStore.scala:48-265),
    the serving-time path
  * `find_columnar` / `aggregate_properties` <- PEventStore
    (data/.../store/PEventStore.scala:35-121), the training path
  * app-name -> (app_id, channel_id) resolution <- store/Common.scala:25-60
"""

from __future__ import annotations

import datetime as _dt
import threading
from typing import Dict, Iterator, Optional, Sequence, Tuple

from predictionio_tpu.data.datamap import PropertyMap
from predictionio_tpu.data.event import Event
from predictionio_tpu.storage.base import UNFILTERED, StorageError
from predictionio_tpu.storage.registry import Storage

_channel_cache: Dict[Tuple[str, Optional[str]], Tuple[int, Optional[int]]] = {}
#: guards _channel_cache: concurrent first-touch resolves from the query
#: server's batcher worker threads would otherwise race the dict fill
_channel_cache_lock = threading.Lock()


def resolve_app(app_name: str, channel_name: Optional[str] = None
                ) -> Tuple[int, Optional[int]]:
    """app name (+ optional channel name) -> (app_id, channel_id).

    Cached, like store/Common.scala:25-60. Thread-safe: the metadata
    lookup runs outside the lock (it can hit storage), so two threads may
    race to resolve the same fresh key — both compute the same value and
    the second write is a no-op.
    """
    key = (app_name, channel_name)
    with _channel_cache_lock:
        if key in _channel_cache:
            return _channel_cache[key]
    app = Storage.get_meta_data_apps().get_by_name(app_name)
    if app is None:
        raise StorageError(f"Invalid app name {app_name}")
    channel_id = None
    if channel_name is not None:
        channels = Storage.get_meta_data_channels().get_by_appid(app.id)
        matched = [c for c in channels if c.name == channel_name]
        if not matched:
            raise StorageError(
                f"Invalid channel name {channel_name} for app {app_name}")
        channel_id = matched[0].id
    with _channel_cache_lock:
        _channel_cache[key] = (app.id, channel_id)
    return app.id, channel_id


def clear_cache() -> None:
    with _channel_cache_lock:
        _channel_cache.clear()
    from predictionio_tpu.data.ingest import clear_scan_cache

    clear_scan_cache()


def _of_store(method: str, app_name: str, channel_name: Optional[str]):
    """`method(app_id, channel_id)` of the configured event store, or
    None where the backend has no such method."""
    app_id, channel_id = resolve_app(app_name, channel_name)
    fn = getattr(Storage.get_events(), method, None)
    return fn(app_id, channel_id) if fn is not None else None


class EventStoreClient:
    """Unified facade over the configured event store, by app name."""

    @staticmethod
    def find(
        app_name: str,
        channel_name: Optional[str] = None,
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
        """PEventStore.find:59 / LEventStore.find:197 parity."""
        app_id, channel_id = resolve_app(app_name, channel_name)
        return Storage.get_events().find(
            app_id=app_id, channel_id=channel_id,
            start_time=start_time, until_time=until_time,
            entity_type=entity_type, entity_id=entity_id,
            event_names=event_names,
            target_entity_type=target_entity_type,
            target_entity_id=target_entity_id,
            limit=limit, reversed_order=reversed_order)

    @staticmethod
    def find_by_entity(
        app_name: str,
        entity_type: str,
        entity_id: str,
        channel_name: Optional[str] = None,
        event_names: Optional[Sequence[str]] = None,
        target_entity_type=UNFILTERED,
        target_entity_id=UNFILTERED,
        start_time: Optional[_dt.datetime] = None,
        until_time: Optional[_dt.datetime] = None,
        limit: Optional[int] = None,
        latest: bool = True,
    ) -> Iterator[Event]:
        """Serving-time entity lookup (LEventStore.findByEntity:76)."""
        return EventStoreClient.find(
            app_name=app_name, channel_name=channel_name,
            start_time=start_time, until_time=until_time,
            entity_type=entity_type, entity_id=entity_id,
            event_names=event_names,
            target_entity_type=target_entity_type,
            target_entity_id=target_entity_id,
            limit=limit, reversed_order=latest)

    @staticmethod
    def aggregate_properties(
        app_name: str,
        entity_type: str,
        channel_name: Optional[str] = None,
        start_time: Optional[_dt.datetime] = None,
        until_time: Optional[_dt.datetime] = None,
        required: Optional[Sequence[str]] = None,
    ) -> Dict[str, PropertyMap]:
        """PEventStore.aggregateProperties:87 parity."""
        app_id, channel_id = resolve_app(app_name, channel_name)
        return Storage.get_events().aggregate_properties(
            app_id=app_id, channel_id=channel_id, entity_type=entity_type,
            start_time=start_time, until_time=until_time, required=required)

    @staticmethod
    def find_columnar(app_name: str, channel_name: Optional[str] = None,
                      **filters):
        """Training-path columnar read (PEventStore.find -> pyarrow.Table)."""
        app_id, channel_id = resolve_app(app_name, channel_name)
        return Storage.get_events().find_columnar(app_id, channel_id, **filters)

    @staticmethod
    def snapshot_digest(app_name: str, channel_name: Optional[str] = None):
        """Durable content fingerprint of the app's event namespace (None
        when the backend cannot produce one): equal digests promise an
        identical rescan, across processes. The deploy orchestrator's
        change detector, and the ingest scan cache's key on a backend
        without a `change_token`."""
        return _of_store("snapshot_digest", app_name, channel_name)

    @staticmethod
    def change_token(app_name: str, channel_name: Optional[str] = None):
        """The backend's O(1), process-local change token of the app's
        event namespace (`EventStore.change_token`), or None when it has
        none — the ingest scan cache's key (data/ingest.py)."""
        return _of_store("change_token", app_name, channel_name)

    @staticmethod
    def read_snapshot(app_name: str, channel_name: Optional[str] = None):
        """Partitioned-read snapshot token for the configured backend
        (sqlite rowid window / parquet fragment list), or None when the
        backend cannot partition. Multi-host trainers capture this ONCE,
        broadcast it, and pass shard=(index, count, snapshot) to
        find_columnar so every process reads the same stable set."""
        return _of_store("read_snapshot", app_name, channel_name)


# short aliases mirroring the reference object names
PEventStore = EventStoreClient
LEventStore = EventStoreClient
