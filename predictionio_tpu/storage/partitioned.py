"""Partitioned event store: P independent stores behind one EventStore.

Events hash by ``(app, channel, entity)`` into one of ``P`` partitions
(:func:`partition_of` — a STABLE crc32, never Python's salted ``hash``),
each partition a full backend store with its own fragment set, sqlite
file, and compaction. That gives ingest P independent commit streams
(the write buffer runs one group-commit lane per partition,
data/write_buffer.py) and gives training reads P independently
scannable slices (ROADMAP item 3; the parallel-and-stream training
split of arXiv:2111.00032 wants exactly this partition parallelism on
the heavy-offline path).

Layout is governed by a tiny partition-map control file committed
through the logstore substrate: ``{"count": P, "gen": G}``. Partition
data lives under generation-qualified names (``…-g<G>-p<k>``); data
whose generation differs from the committed map is garbage by
definition and is collected on open. That makes :meth:`reshard`
crash-safe with the same manifest discipline parquet compaction uses:

1. **stage** — copy every event into the new generation's partitions
   (idempotent inserts, original event ids), old map still committed;
   a crash leaves invisible staging garbage (kill ``reshard:staged``).
2. **commit** — atomically replace the partition map; this single
   rename is THE cutover (kill ``reshard:committed``).
3. **gc** — destroy non-current generations; a crash in between leaves
   only invisible old-generation data that the next open collects
   (kill ``reshard:old-removed``).

Readers only ever open the committed generation, so at every kill
point they see exactly one complete copy of every event — exactly-once
across a partition-count change. Like ``compact()``, resharding is a
single-operator maintenance op: run it with no concurrent writers.

The shard protocol maps reader shards onto partitions
(:func:`shard_partitions`): with ``count <= P`` shards each scan whole
partitions; with ``count > P`` shards sub-shard within their partition
via the backend's own range/fragment sharding. Snapshots compose: the
partitioned snapshot is the per-partition snapshot vector plus the
partition count, and a reshard between capture and read fails loudly
instead of skewing the partitions.
"""

from __future__ import annotations

import datetime as _dt
import heapq
import itertools
import logging
import os
import re
import zlib
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

from predictionio_tpu.data.event import Event
from predictionio_tpu.storage import base, logstore
from predictionio_tpu.storage.base import UNFILTERED, StorageError
from predictionio_tpu.storage.faults import maybe_kill

log = logging.getLogger("pio.storage")

#: events copied per idempotent insert during a reshard stage
RESHARD_BATCH = 2048

#: partition-map control file name (committed via the logstore substrate)
MAP_NAME = "_pio_partitions.json"

_PART_RE = re.compile(r"-g(\d+)-p(\d+)$")


def partition_of(app_id: int, channel_id: Optional[int],
                 entity_id: Optional[str], count: int) -> int:
    """The one routing function: ``(app, channel, entity) -> partition``.

    crc32 of a canonical key string — stable across processes, restarts
    and Python versions (``hash()`` is per-process salted and would
    scatter a restart's writes across different partitions than its
    reads). Events without an entity id hash with an empty key."""
    key = f"{app_id}:{channel_id or 0}:{entity_id or ''}"
    return zlib.crc32(key.encode()) % count


def shard_partitions(shard_idx: int, shard_count: int, partitions: int
                     ) -> List[Tuple[int, Optional[Tuple[int, int]]]]:
    """Which ``(partition, sub_shard)`` pieces reader shard ``shard_idx``
    of ``shard_count`` scans, over ``partitions`` partitions.

    * ``shard_count <= partitions``: shard i reads every partition p
      with ``p % shard_count == i`` in full (``sub_shard=None``).
    * ``shard_count > partitions``: shard i reads only partition
      ``i % partitions``, sub-sharded among the ``k_p`` shards mapped
      to that partition via the backend's own shard protocol.

    Either way the pieces are disjoint and complete: every partition is
    covered exactly once across all shards."""
    if not (0 <= shard_idx < shard_count):
        raise StorageError(f"bad shard ({shard_idx}, {shard_count})")
    if shard_count <= partitions:
        return [(p, None) for p in range(partitions)
                if p % shard_count == shard_idx]
    p = shard_idx % partitions
    k_p = len(range(p, shard_count, partitions))
    return [(p, (shard_idx // partitions, k_p))]


# ---------------------------------------------------------------------------
# partition layouts (how one backend materializes generation/partition k)
# ---------------------------------------------------------------------------

class SqlitePartitions:
    """Sqlite layout: one DB file per (generation, partition) beside the
    configured path — ``pio-g<G>-p<k>.db`` for ``pio.db`` — so each
    partition has its own writer lock and WAL (the whole point: sqlite
    serializes writers PER FILE). ``:memory:`` keeps an in-process table
    of clients (tests/dev)."""

    def __init__(self, path: str):
        self.path = path
        self.memory = path == ":memory:"
        if self.memory:
            self._mem_clients: Dict[Tuple[int, int], object] = {}
            self._mem_map: Optional[dict] = None
        else:
            self._dir = os.path.dirname(os.path.abspath(path))
            stem = os.path.basename(path)
            self._stem, self._ext = os.path.splitext(stem)
            os.makedirs(self._dir, exist_ok=True)

    def _part_path(self, gen: int, k: int) -> str:
        return os.path.join(self._dir,
                            f"{self._stem}-g{gen}-p{k}{self._ext}")

    def open(self, gen: int, k: int) -> base.EventStore:
        from predictionio_tpu.storage.sqlite_backend import (
            SqliteClient, SqliteEvents)

        if self.memory:
            client = self._mem_clients.get((gen, k))
            if client is None:
                client = self._mem_clients[(gen, k)] = SqliteClient(":memory:")
            return SqliteEvents(client)
        return SqliteEvents(SqliteClient(self._part_path(gen, k)))

    def destroy(self, gen: int, k: int) -> None:
        if self.memory:
            client = self._mem_clients.pop((gen, k), None)
            if client is not None:
                client.close()
            return
        for suffix in ("", "-wal", "-shm"):
            try:
                os.unlink(self._part_path(gen, k) + suffix)
            except OSError:
                pass

    def parts(self) -> List[Tuple[int, int]]:
        if self.memory:
            return sorted(self._mem_clients)
        found = []
        for name in os.listdir(self._dir):
            s, ext = os.path.splitext(name)
            m = _PART_RE.search(s)
            if m and ext == self._ext and s[:m.start()] == self._stem:
                found.append((int(m.group(1)), int(m.group(2))))
        return sorted(found)

    def map_read(self) -> Optional[dict]:
        if self.memory:
            return self._mem_map
        return logstore.read_json(
            os.path.join(self._dir, f"{self._stem}.{MAP_NAME}"))

    def map_commit(self, doc: dict) -> None:
        if self.memory:
            self._mem_map = dict(doc)
            return
        logstore.commit_json(self._dir, f"{self._stem}.{MAP_NAME}", doc)

    def close(self) -> None:
        if self.memory:
            for client in self._mem_clients.values():
                client.close()


class ParquetPartitions:
    """Parquet layout: one fragment root per (generation, partition) —
    ``<root>/part-g<G>-p<k>/`` — each with its own fragment set,
    manifests and compaction; the partition map commits at the top
    root."""

    def __init__(self, client):
        self.client = client    # ParquetEventsClient (fs + root)

    def _part_root(self, gen: int, k: int) -> str:
        return f"{self.client.root}/part-g{gen}-p{k}"

    def open(self, gen: int, k: int) -> base.EventStore:
        from predictionio_tpu.storage.parquet_events import (
            ParquetEvents, ParquetEventsClient)

        sub = ParquetEventsClient.__new__(ParquetEventsClient)
        sub.url = f"{self.client.url}/part-g{gen}-p{k}"
        sub.fs = self.client.fs
        sub.root = self._part_root(gen, k)
        sub.fs.makedirs(sub.root, exist_ok=True)
        return ParquetEvents(sub)

    def destroy(self, gen: int, k: int) -> None:
        root = self._part_root(gen, k)
        if self.client.fs.exists(root):
            self.client.fs.rm(root, recursive=True)

    def parts(self) -> List[Tuple[int, int]]:
        try:
            names = self.client.fs.ls(self.client.root, detail=False)
        except FileNotFoundError:
            return []
        found = []
        for name in names:
            m = _PART_RE.search(name.rstrip("/").rsplit("/", 1)[-1])
            if m:
                found.append((int(m.group(1)), int(m.group(2))))
        return sorted(found)

    def map_read(self) -> Optional[dict]:
        return logstore.fs_read_json(
            self.client.fs, f"{self.client.root}/{MAP_NAME}")

    def map_commit(self, doc: dict) -> None:
        import json

        logstore.fs_commit_bytes(self.client.fs,
                                 f"{self.client.root}/{MAP_NAME}",
                                 json.dumps(doc, sort_keys=True).encode())

    def close(self) -> None:
        self.client.close()


# ---------------------------------------------------------------------------
# the store
# ---------------------------------------------------------------------------

class PartitionedEvents(base.EventStore):
    """P backend stores behind one EventStore, routed by entity hash.

    Construction reads (or initializes) the committed partition map and
    collects any generation that is not the committed one — the
    roll-forward half of the reshard discipline (module docstring)."""

    def __init__(self, layout, initial_count: int = 1):
        if initial_count < 1:
            raise StorageError(f"bad partition count {initial_count}")
        self.layout = layout
        doc = layout.map_read()
        if doc is None:
            doc = {"count": int(initial_count), "gen": 0}
            layout.map_commit(doc)
        self._count = int(doc["count"])
        self._gen = int(doc["gen"])
        self._recover()
        self._stores = [layout.open(self._gen, k)
                        for k in range(self._count)]

    def _recover(self) -> None:
        """Collect partition data whose generation is not the committed
        one: staging from a reshard that died before commit, or old
        generations from one that died after (both invisible to
        readers — the map is the only source of truth)."""
        for gen, k in self.layout.parts():
            if gen != self._gen or k >= self._count:
                self.layout.destroy(gen, k)

    # -- introspection ------------------------------------------------------
    @property
    def partition_count(self) -> int:
        return self._count

    @property
    def generation(self) -> int:
        return self._gen

    def partition_store(self, k: int) -> base.EventStore:
        return self._stores[k]

    def _route(self, app_id: int, channel_id: Optional[int],
               entity_id: Optional[str]) -> base.EventStore:
        return self._stores[
            partition_of(app_id, channel_id, entity_id, self._count)]

    # -- namespace lifecycle ------------------------------------------------
    def init_channel(self, app_id: int,
                     channel_id: Optional[int] = None) -> bool:
        return all([s.init_channel(app_id, channel_id)
                    for s in self._stores])

    def remove_channel(self, app_id: int,
                       channel_id: Optional[int] = None) -> bool:
        return all([s.remove_channel(app_id, channel_id)
                    for s in self._stores])

    def close(self) -> None:
        for s in self._stores:
            s.close()
        self.layout.close()

    # -- writes -------------------------------------------------------------
    def insert(self, event: Event, app_id: int,
               channel_id: Optional[int] = None) -> str:
        return self._route(app_id, channel_id, event.entity_id).insert(
            event, app_id, channel_id)

    def _grouped(self, events: Sequence[Event], app_id: int,
                 channel_id: Optional[int]
                 ) -> Dict[int, Tuple[List[int], List[Event]]]:
        groups: Dict[int, Tuple[List[int], List[Event]]] = {}
        for i, e in enumerate(events):
            p = partition_of(app_id, channel_id, e.entity_id, self._count)
            idxs, evs = groups.setdefault(p, ([], []))
            idxs.append(i)
            evs.append(e)
        return groups

    def _insert_grouped(self, method: str, events: Sequence[Event],
                        app_id: int, channel_id: Optional[int]
                        ) -> List[str]:
        groups = self._grouped(events, app_id, channel_id)
        ids: List[Optional[str]] = [None] * len(events)
        for p, (idxs, evs) in groups.items():
            for i, eid in zip(idxs,
                              getattr(self._stores[p], method)(
                                  evs, app_id, channel_id)):
                ids[i] = eid
        return ids  # type: ignore[return-value]

    def insert_batch(self, events: Sequence[Event], app_id: int,
                     channel_id: Optional[int] = None) -> List[str]:
        return self._insert_grouped("insert_batch", events, app_id,
                                    channel_id)

    def insert_batch_idempotent(self, events: Sequence[Event], app_id: int,
                                channel_id: Optional[int] = None
                                ) -> List[str]:
        return self._insert_grouped("insert_batch_idempotent", events,
                                    app_id, channel_id)

    # -- point reads / deletes ----------------------------------------------
    # id-only lookups carry no entity, so they probe every partition; an
    # id exists in at most one, so the first hit wins.
    def get(self, event_id: str, app_id: int,
            channel_id: Optional[int] = None) -> Optional[Event]:
        for s in self._stores:
            e = s.get(event_id, app_id, channel_id)
            if e is not None:
                return e
        return None

    def delete(self, event_id: str, app_id: int,
               channel_id: Optional[int] = None) -> bool:
        for s in self._stores:
            if s.delete(event_id, app_id, channel_id):
                return True
        return False

    # -- maintenance --------------------------------------------------------
    def compact(self, app_id: int, channel_id: Optional[int] = None,
                ttl_days: Optional[float] = None) -> Dict[str, int]:
        total: Dict[str, int] = {}
        for s in self._stores:
            for key, n in s.compact(app_id, channel_id,
                                    ttl_days=ttl_days).items():
                total[key] = total.get(key, 0) + n
        return total

    # -- queries ------------------------------------------------------------
    def find(self, app_id: int, channel_id: Optional[int] = None,
             **filters) -> Iterator[Event]:
        entity_id = filters.get("entity_id")
        if entity_id is not None:
            yield from self._route(app_id, channel_id, entity_id).find(
                app_id, channel_id, **filters)
            return
        reversed_order = bool(filters.get("reversed_order", False))
        limit = filters.pop("limit", None)
        # per-partition streams are each time-ordered; a lazy k-way merge
        # keeps the global chronological contract without materializing
        streams = [s.find(app_id, channel_id, **filters)
                   for s in self._stores]
        merged = heapq.merge(*streams, key=lambda e: e.event_time,
                             reverse=reversed_order)
        if limit is not None and limit >= 0:
            merged = itertools.islice(merged, limit)
        yield from merged

    def _shard_pieces(self, shard
                      ) -> List[Tuple[int, Optional[tuple]]]:
        """Resolve the shard protocol onto (partition, inner_shard)
        scan pieces, validating any held composite snapshot."""
        snap = shard[2] if len(shard) > 2 else None
        if snap is not None:
            if not (isinstance(snap, (list, tuple)) and len(snap) == 3
                    and snap[0] == "pmap"):
                raise StorageError(
                    "shard snapshot was not captured from this "
                    "partitioned store; capture read_snapshot() here")
            if int(snap[1]) != self._count:
                raise StorageError(
                    f"partition count changed under a held snapshot "
                    f"({snap[1]} -> {self._count}, a reshard ran); "
                    "capture a fresh read_snapshot() and retry")
        pieces = []
        for p, sub in shard_partitions(shard[0], shard[1], self._count):
            psnap = snap[2][p] if snap is not None else None
            if sub is not None:
                inner = (sub[0], sub[1], psnap) if psnap is not None else sub
            else:
                # a whole partition under a held snapshot reads as the
                # trivial 1-shard of that snapshot
                inner = (0, 1, psnap) if psnap is not None else None
            pieces.append((p, inner))
        return pieces

    def find_columnar(self, app_id: int, channel_id: Optional[int] = None,
                      ordered: bool = True, **filters):
        import pyarrow as pa

        columns = filters.pop("columns", None)
        shard = filters.pop("shard", None)
        limit = filters.get("limit")
        reversed_order = bool(filters.get("reversed_order", False))
        entity_id = filters.get("entity_id")
        if shard is None and entity_id is not None:
            return self._route(app_id, channel_id, entity_id).find_columnar(
                app_id, channel_id, ordered=ordered, columns=columns,
                **filters)
        if shard is not None:
            pieces = self._shard_pieces(shard)
        else:
            pieces = [(p, None) for p in range(self._count)]
        want_limit = limit is not None and limit >= 0
        sort_needed = ordered or reversed_order or want_limit
        inner_columns = columns
        if sort_needed and columns is not None \
                and "event_time_ms" not in columns:
            # the global merge sorts on event_time_ms; fetch it and drop
            # it again after the sort
            inner_columns = list(columns) + ["event_time_ms"]

        from predictionio_tpu.obs.tracing import capture_context, carried

        ctx = capture_context()

        def scan_one(piece):
            p, inner_shard = piece
            with carried(ctx, "partition_scan", record=False):
                return self._stores[p].find_columnar(
                    app_id, channel_id,
                    ordered=False if sort_needed else ordered,
                    columns=inner_columns, shard=inner_shard, **filters)

        if len(pieces) == 1:
            tables = [scan_one(pieces[0])]
        else:
            # concurrent partition scans: each partition is an
            # independent file/DB, so the IO overlaps
            with ThreadPoolExecutor(max_workers=len(pieces)) as pool:
                tables = list(pool.map(scan_one, pieces))
        t = pa.concat_tables(tables)
        if sort_needed and t.num_rows:
            t = t.sort_by([(
                "event_time_ms",
                "descending" if reversed_order else "ascending")])
        if want_limit:
            t = t.slice(0, limit)
        if columns is not None and inner_columns is not columns:
            t = t.select(list(columns))
        return t

    # -- snapshots -----------------------------------------------------------
    def read_snapshot(self, app_id: int,
                      channel_id: Optional[int] = None):
        """Composite snapshot: the per-partition snapshot vector tagged
        with the partition count it was captured under. A reshard
        between capture and read changes the count and the sharded read
        refuses (re-snapshot and retry) instead of skewing."""
        return ("pmap", self._count,
                tuple(s.read_snapshot(app_id, channel_id)
                      for s in self._stores))

    def snapshot_digest(self, app_id: int,
                        channel_id: Optional[int] = None) -> Optional[str]:
        digests = [s.snapshot_digest(app_id, channel_id)
                   for s in self._stores]
        if any(d is None for d in digests):
            return None
        return f"pmap:{self._count}:" + "|".join(digests)

    def change_token(self, app_id: int,
                     channel_id: Optional[int] = None):
        """The partitions' tokens under the map they were taken under
        (a reshard opens new stores, whose tokens compare with none of
        the old); None as soon as one partition has none."""
        tokens = tuple(s.change_token(app_id, channel_id)
                       for s in self._stores)
        if any(t is None for t in tokens):
            return None
        return ("pmap", self._count, self._gen, tokens)

    # -- resharding ----------------------------------------------------------
    def reshard(self, new_count: int,
                apps: Iterable[Tuple[int, Optional[int]]]) -> Dict[str, int]:
        """Change the partition count, exactly-once at every kill point.

        ``apps`` is the (app_id, channel_id) namespaces to carry over
        (the CLI enumerates them from metadata). Offline maintenance op:
        run with no concurrent writers, like ``compact()``. Stages a
        full copy into generation G+1 (idempotent inserts, original
        event ids — a retried run re-converges instead of duplicating),
        commits the partition map (THE cutover), then collects the old
        generation; `_recover` rolls either crash half forward."""
        if new_count < 1:
            raise StorageError(f"bad partition count {new_count}")
        old_count, old_gen = self._count, self._gen
        if new_count == old_count:
            return {"copied": 0, "count": old_count, "gen": old_gen}
        new_gen = old_gen + 1
        # a previous attempt may have died mid-stage: its staging is
        # garbage of OUR new generation — restart the copy from scratch
        for gen, k in self.layout.parts():
            if gen == new_gen:
                self.layout.destroy(gen, k)
        new_stores = [self.layout.open(new_gen, k)
                      for k in range(new_count)]
        copied = 0
        for app_id, channel_id in apps:
            for s in new_stores:
                s.init_channel(app_id, channel_id)
            for old in self._stores:
                pending: Dict[int, List[Event]] = {}
                for e in old.find(app_id, channel_id):
                    p = partition_of(app_id, channel_id, e.entity_id,
                                     new_count)
                    batch = pending.setdefault(p, [])
                    batch.append(e)
                    if len(batch) >= RESHARD_BATCH:
                        new_stores[p].insert_batch_idempotent(
                            pending.pop(p), app_id, channel_id)
                        copied += len(batch)
                for p, batch in pending.items():
                    new_stores[p].insert_batch_idempotent(
                        batch, app_id, channel_id)
                    copied += len(batch)
        maybe_kill("reshard:staged")
        self.layout.map_commit({"count": new_count, "gen": new_gen})
        maybe_kill("reshard:committed")
        # swap the live view before GC so a crash mid-collection still
        # leaves this object serving the committed generation
        old_stores, self._stores = self._stores, new_stores
        self._count, self._gen = new_count, new_gen
        for s in old_stores:
            s.close()
        for gen, k in self.layout.parts():
            if gen != new_gen:
                self.layout.destroy(gen, k)
        maybe_kill("reshard:old-removed")
        return {"copied": copied, "count": new_count, "gen": new_gen,
                "old_count": old_count}


def maybe_partitioned(store, layout_factory, requested: int):
    """Wrap ``store`` in a :class:`PartitionedEvents` when partitioning
    is requested (``PIO_INGEST_PARTITIONS`` > 1) OR a committed
    partition map already exists — the map is authoritative, so a
    store partitioned once keeps reading its partitions even when the
    knob is unset (changing the count takes a ``pio reshard``, not an
    env edit). Returns ``store`` unchanged when unpartitioned."""
    layout = layout_factory()
    existing = layout.map_read()
    if requested <= 1 and existing is None:
        layout.close()
        return store
    if existing is not None and requested > 1 \
            and int(existing["count"]) != requested:
        log.warning(
            "PIO_INGEST_PARTITIONS=%d but the committed partition map "
            "says %d; the map wins — run `pio reshard --partitions %d` "
            "to change it", requested, int(existing["count"]), requested)
    return PartitionedEvents(layout, initial_count=max(requested, 1))
