"""Columnar training-ingest pipeline shared by every engine DataSource.

The event→tensor hot path of `pio train` / `pio eval`: one columnar scan
(`EventStoreClient.find_columnar` → pyarrow table), vectorized column
extraction and (user, item) aggregation on flat NumPy arrays, and
`assign_indices`-based id interning — no per-`Event` Python objects
anywhere between the store and the model tensors (the RDD-scan
bottleneck the reference pays per engine, DataSource.scala's
`PEventStore.find.map` chains).

Three concerns live here so the six engines share one implementation:

* **shard/snapshot protocol** — on a multi-process runtime a sharded
  scan partitions ONE collectively-agreed `read_snapshot()` window
  exactly like the reference's per-executor JdbcRDD slices
  (JDBCPEvents.scala:89-101); engines whose algorithms re-key rows to
  their owners (recommendation's distributed ALS) opt in with
  ``sharded=True``, everything else reads replicated.
* **scan cache** — process-local, so the repeated folds of `pio eval`
  (k-fold re-reads) and a second algorithm of one engine skip the
  rescan when the store hasn't changed. Keyed by what the backend
  offers to identify its state (`_store_state`): its O(1)
  ``change_token()`` where it has one (sqlite: counters of the
  connection, no statement over the event table), else its durable
  ``snapshot_digest()``. Either is taken BEFORE the scan: states only
  move forward and every commit moves the key, so a later read that
  finds the same key knows nothing was committed since it was taken,
  the scan included; taken after the scan it could describe a newer
  state than the table scanned. Disable with ``PIO_INGEST_CACHE=0``.
* **`pio_ingest_*` metrics** — rows scanned and decoded, rows/s, cache
  hit/miss and key-kind counters on the process registry, plus
  ``ingest_digest`` /
  ``ingest_scan`` / ``ingest_decode`` / ``ingest_intern`` /
  ``ingest_assemble`` spans,
  which reach the span histogram of the job they run under
  (OBSERVABILITY.md inventory).
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import threading
import time
from typing import Optional, Tuple

import numpy as np

from predictionio_tpu.obs.registry import MetricsRegistry, default_registry
from predictionio_tpu.obs.tracing import span

#: scans cached per process; small — each entry is one app's filtered
#: training read (the k-fold reuse window, not a general query cache)
_CACHE_MAX = 8

_scan_cache: dict = {}
_scan_lock = threading.Lock()


def clear_scan_cache() -> None:
    with _scan_lock:
        _scan_cache.clear()


def _cache_enabled() -> bool:
    return os.environ.get("PIO_INGEST_CACHE", "1") != "0"


def _registry() -> MetricsRegistry:
    return default_registry()


def _count_rows(app_name: str, n: int, seconds: float) -> None:
    reg = _registry()
    reg.counter("pio_ingest_rows_total",
                "Event rows delivered to training reads by the columnar "
                "ingest path", labelnames=("app",)).inc(n, app=app_name)
    if seconds > 0:
        reg.gauge("pio_ingest_rows_per_second",
                  "Throughput of the most recent columnar training scan",
                  labelnames=("app",)).set(n / seconds, app=app_name)


def _count_cache(app_name: str, hit: bool) -> None:
    name = ("pio_ingest_cache_hits_total" if hit
            else "pio_ingest_cache_misses_total")
    verb = "hits" if hit else "misses"
    _registry().counter(
        name, f"Ingest scan-cache {verb} (keyed by the store's change "
        "token or snapshot digest)",
        labelnames=("app",)).inc(app=app_name)


def _cache_get(app_name: str, key):
    """Lookup + hit/miss accounting — shared by both cache entry points
    (training_scan tables and aggregate_scan property dicts)."""
    with _scan_lock:
        hit = _scan_cache.get(key)
    _count_cache(app_name, hit is not None)
    return hit


def _cache_put(key, value) -> None:
    """Size-capped FIFO insert — shared eviction policy."""
    with _scan_lock:
        if len(_scan_cache) >= _CACHE_MAX and key not in _scan_cache:
            _scan_cache.pop(next(iter(_scan_cache)))
        _scan_cache[key] = value


def _store_state(app_name: str, channel_name: Optional[str]):
    """What identifies the store's state in a scan-cache key, taken
    before the scan under an ``ingest_digest`` span: ``("token", t)``
    with the backend's O(1) change token where it has one (sqlite), else
    ``("digest", d)`` with its snapshot digest (postgres, parquet: a
    statement or a listing a read pays whether or not the cache then
    hits), else None: the read is not cached. Which of the two is what
    the backend offers; nobody sets it."""
    from predictionio_tpu.data.eventstore import EventStoreClient

    with span("ingest_digest"):
        kind, state = "token", EventStoreClient.change_token(
            app_name, channel_name)
        if state is None:
            kind, state = "digest", EventStoreClient.snapshot_digest(
                app_name, channel_name)
    if state is None:
        return None
    _registry().counter(
        "pio_ingest_cache_key_total",
        "Training reads that keyed the ingest scan cache, by what "
        "identified the store's state: its O(1) change token or its "
        "snapshot digest", labelnames=("kind",)).inc(kind=kind)
    return kind, state


@dataclasses.dataclass
class TrainingScan:
    """One columnar training read.

    ``table`` holds the EVENT_SCHEMA columns; ``shard`` is the partition
    tuple the scan used (None = unsharded); ``replicated`` is True when a
    multi-process run wanted shards but the backend cannot partition —
    every process then holds the FULL set and the caller must keep a
    disjoint slice (`local_slice`) before feeding a distributed build.
    """

    table: "object"
    shard: Optional[tuple] = None
    replicated: bool = False

    def local_slice(self, arrays: Tuple[np.ndarray, ...]
                    ) -> Tuple[np.ndarray, ...]:
        """Strided disjoint slice for the replicated-fallback case; the
        identity otherwise (sharded or single-process reads are already
        local)."""
        if not self.replicated:
            return arrays
        import jax

        p, np_ = jax.process_index(), jax.process_count()
        return tuple(a[p::np_] for a in arrays)


def training_scan(app_name: str, channel_name: Optional[str] = None, *,
                  sharded: bool = False, cache: bool = True,
                  **filters) -> TrainingScan:
    """The shared columnar training read: filtered, optionally sharded,
    cached by the store's state (`_store_state`), instrumented.

    ``filters`` go straight to ``find_columnar`` (entity_type,
    event_names, target_entity_type, ...); ``ordered=False`` is applied
    unless the caller overrides it — training math is either
    permutation-invariant or re-sorts locally.

    ``sharded=True`` opts into the multi-process shard/snapshot protocol
    (the recommendation engine's distributed-ALS read): process 0
    captures ``read_snapshot()`` once, broadcasts it, and every process
    scans only its partition of that window. Engines whose algorithms do
    NOT exchange rows by owner must keep the default replicated read.

    On a partitioned event store (`PIO_INGEST_PARTITIONS`,
    storage/partitioned.py) both paths gain partition parallelism for
    free at the store layer: the unsharded scan fans per-partition
    reads across a thread pool and merges time-ordered, and the
    sharded read maps reader shards onto store partitions
    (`shard_partitions`) under a composite snapshot — a reshard
    between capture and read fails loudly instead of skewing.
    """
    from predictionio_tpu.data.eventstore import EventStoreClient

    filters.setdefault("ordered", False)
    shard = None
    replicated = False
    if sharded:
        import jax

        if jax.process_count() > 1:
            from predictionio_tpu.parallel.shuffle import allgather_object

            # ONE process captures the snapshot; everyone partitions the
            # SAME window — independently computed bounds skew under
            # concurrent ingest and the partitions gap/overlap
            snap = allgather_object(
                EventStoreClient.read_snapshot(app_name, channel_name)
                if jax.process_index() == 0 else None)[0]
            if snap is not None:
                shard = (jax.process_index(), jax.process_count(), snap)
            else:
                # backend cannot partition: full read on every process,
                # caller keeps a disjoint strided slice (local_slice)
                replicated = True

    key = None
    if cache and _cache_enabled():
        state = _store_state(app_name, channel_name)
        if state is not None:
            key = (app_name, channel_name, state,
                   shard[:2] if shard else None,
                   tuple(sorted(
                       (k, tuple(v) if isinstance(v, list) else v)
                       for k, v in filters.items())))
            hit = _cache_get(app_name, key)
            if hit is not None:
                return TrainingScan(table=hit, shard=shard,
                                    replicated=replicated)

    t0 = time.perf_counter()
    with span("ingest_scan"):
        table = EventStoreClient.find_columnar(
            app_name=app_name, channel_name=channel_name, shard=shard,
            **filters)
    _count_rows(app_name, table.num_rows, time.perf_counter() - t0)
    if key is not None:
        _cache_put(key, table)
    return TrainingScan(table=table, shard=shard, replicated=replicated)


def aggregate_scan(app_name: str, entity_type: str,
                   channel_name: Optional[str] = None, *,
                   required=None, cache: bool = True):
    """Entity properties for training reads: the columnar
    ``aggregate_properties`` fold behind the same cache as
    `training_scan`, under an ``ingest_aggregate`` span. Returns
    ``{entity_id: PropertyMap}`` (a fresh dict per call; the immutable
    PropertyMaps are shared with the cache)."""
    from predictionio_tpu.data.eventstore import EventStoreClient

    key = None
    if cache and _cache_enabled():
        state = _store_state(app_name, channel_name)
        if state is not None:
            key = ("aggregate", app_name, channel_name, entity_type,
                   tuple(required) if required else None, state)
            hit = _cache_get(app_name, key)
            if hit is not None:
                return dict(hit)
    with span("ingest_aggregate"):
        out = EventStoreClient.aggregate_properties(
            app_name, entity_type, channel_name=channel_name,
            required=required)
    if key is not None:
        _cache_put(key, out)
        return dict(out)
    return out


@contextlib.contextmanager
def decoding(app_name: str, table):
    """The ``ingest_decode`` span of a training read: wrap the block that
    turns a scanned table into NumPy columns (`event_columns`,
    `columnar.property_column`, `TrainingScan.local_slice`); counts the
    table's rows into ``pio_ingest_decoded_rows_total``."""
    with span("ingest_decode"):
        yield
    _registry().counter(
        "pio_ingest_decoded_rows_total",
        "Event rows a training read decoded from a scanned table into "
        "NumPy columns (a scan-cache hit decodes without scanning)",
        labelnames=("app",)).inc(table.num_rows, app=app_name)


def event_columns(table, *names) -> Tuple[np.ndarray, ...]:
    """Named EVENT_SCHEMA columns as NumPy arrays (object for strings,
    int64 for the *_ms times) — the zero-Event handoff from Arrow.

    String columns decode through `columnar.string_column`'s dictionary
    trick — O(distinct) Python-string churn instead of O(rows), which is
    the difference on id columns whose cardinality is thousands against
    millions of rows. Nulls decode to None (absent target ids)."""
    from predictionio_tpu.data.columnar import string_column

    out = []
    for name in names:
        if name.endswith("_ms"):
            out.append(np.asarray(
                table.column(name).to_numpy(zero_copy_only=False),
                dtype=np.int64))
            continue
        out.append(string_column(table, name))
    return tuple(out)


def intern_pairs(users: np.ndarray, items: np.ndarray):
    """Vectorized id interning for an interaction table: (user_vocab,
    user_codes, item_vocab, item_codes) via `assign_indices` — the BiMap
    build without per-row dict hits, under an ``ingest_intern`` span."""
    from predictionio_tpu.data.bimap import assign_indices

    with span("ingest_intern"):
        user_vocab, user_codes = assign_indices(users)
        item_vocab, item_codes = assign_indices(items)
    return user_vocab, user_codes, item_vocab, item_codes


def pair_counts(users: np.ndarray, items: np.ndarray,
                weights: Optional[np.ndarray] = None):
    """Aggregate duplicate (user, item) rows: distinct pairs plus the sum
    of ``weights`` (default 1.0 each) per pair — the vectorized analog of
    the engines' ``counts[(u, i)] += w`` fold. Returns (users', items',
    sums) with first-occurrence order of pairs NOT preserved (sorted by
    interned codes); downstream factorization is permutation-invariant.
    """
    if len(users) == 0:
        return (np.empty(0, object), np.empty(0, object),
                np.empty(0, np.float32))
    with span("ingest_assemble"):
        user_vocab, ucodes, item_vocab, icodes = (
            intern_pairs(users, items))
        combined = ucodes.astype(np.int64) * len(item_vocab) + icodes
        uniq, inv = np.unique(combined, return_inverse=True)
        w = (np.ones(len(users), np.float32) if weights is None
             else np.asarray(weights, np.float32))
        sums = np.bincount(inv, weights=w,
                           minlength=len(uniq)).astype(np.float32)
        u_out = user_vocab[(uniq // len(item_vocab)).astype(np.int64)]
        i_out = item_vocab[(uniq % len(item_vocab)).astype(np.int64)]
    return u_out, i_out, sums


def latest_per_pair(users: np.ndarray, items: np.ndarray,
                    times: np.ndarray, values: np.ndarray):
    """Latest-wins per (user, item) by event time — the vectorized analog
    of the like/dislike ``if e.t > latest[key].t`` fold, including its
    tie rule (equal timestamps keep the FIRST event in scan order; the
    descending position tiebreak below reproduces the strict ``>``).
    Returns (users', items', values') for the distinct pairs."""
    if len(users) == 0:
        return users, items, values
    with span("ingest_assemble"):
        user_vocab, ucodes, item_vocab, icodes = (
            intern_pairs(users, items))
        combined = ucodes.astype(np.int64) * len(item_vocab) + icodes
        order = np.lexsort((np.arange(len(users))[::-1], times, combined))
        cs = combined[order]
        is_last = np.r_[cs[1:] != cs[:-1], True]
        winners = order[is_last]
    return users[winners], items[winners], values[winners]


def sessions_by_entity(users: np.ndarray, items: np.ndarray,
                       times: np.ndarray):
    """Group an interaction scan into per-user time-ordered item
    sequences: ONE lexsort + segment split instead of a per-event dict
    append — the sessionrec DataSource assembly. Returns sessions in
    sorted-user order (the row path's ``sorted(by_user)`` contract)."""
    if len(users) == 0:
        return []
    with span("ingest_assemble"):
        from predictionio_tpu.data.bimap import assign_indices

        _, codes = assign_indices(users)
        order = np.lexsort((np.arange(len(users)), times, codes))
        codes_s = codes[order]
        items_s = items[order]
        starts = np.flatnonzero(np.r_[True, codes_s[1:] != codes_s[:-1]])
        bounds = np.r_[starts, len(codes_s)]
        return [items_s[bounds[i]:bounds[i + 1]].tolist()
                for i in range(len(starts))]
