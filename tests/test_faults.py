"""Chaos suite for the hardened ingest write path (tier-1: CPU, fast).

Proves the ISSUE 6 acceptance bar end to end at test scale: zero event
loss and zero duplication across injected storage faults (error rate,
added latency, fail-N-then-recover, ambiguous post-commit failures, flush
timeouts, kill-mid-compaction), explicit 429 shedding once the ingest
queue bound is hit, and drain-on-shutdown. Storage-level chaos runs
against real sqlite + parquet backends; HTTP-level chaos drives the full
event server.
"""

import asyncio
import datetime as dt
import threading
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

pytestmark = pytest.mark.anyio

from predictionio_tpu.data.event import Event, UTC
from predictionio_tpu.data.write_buffer import BufferFull, WriteBuffer
from predictionio_tpu.obs.registry import MetricsRegistry
from predictionio_tpu.storage import faults
from predictionio_tpu.storage import base as storage_base
from predictionio_tpu.storage.base import StorageError
from predictionio_tpu.storage.faults import CrashError, FaultyEvents
from predictionio_tpu.storage.parquet_events import (
    ParquetEvents, ParquetEventsClient,
)
from predictionio_tpu.storage.sqlite_backend import SqliteClient, SqliteEvents

APP = 7


def ev(i, *, t=None, name="view"):
    return Event(
        event=name, entity_type="user", entity_id=f"u{i}",
        target_entity_type="item", target_entity_id=f"i{i}",
        event_time=t or (dt.datetime(2026, 1, 1, tzinfo=UTC)
                         + dt.timedelta(seconds=i)))


def stored_ids(store):
    return [e.event_id for e in store.find(APP)]


@pytest.fixture
def sqlite_store(tmp_path):
    client = SqliteClient(str(tmp_path / "ev.db"))
    store = SqliteEvents(client)
    store.init_channel(APP)
    yield store
    client.close()


@pytest.fixture
def parquet_store(tmp_path):
    store = ParquetEvents(ParquetEventsClient(str(tmp_path / "events")))
    store.init_channel(APP)
    return store


@pytest.fixture(autouse=True)
def _disarm_kill_points():
    yield
    faults.set_kill_points([])


class Gated:
    """Blocks every write until .gate is set (deterministic full queues)."""

    def __init__(self, inner):
        self.inner = inner
        self.gate = threading.Event()

    def insert_batch(self, events, app_id, channel_id=None):
        assert self.gate.wait(10), "test gate never released"
        return self.inner.insert_batch(events, app_id, channel_id)

    def insert_batch_idempotent(self, events, app_id, channel_id=None):
        assert self.gate.wait(10), "test gate never released"
        return self.inner.insert_batch_idempotent(events, app_id, channel_id)

    def __getattr__(self, name):
        return getattr(self.inner, name)


# ---------------------------------------------------------------------------
# WriteBuffer: group commit, retries, shedding, drain
# ---------------------------------------------------------------------------

def test_group_commit_coalesces_concurrent_submits(sqlite_store):
    reg = MetricsRegistry()
    buf = WriteBuffer(store_fn=lambda: sqlite_store, flush_max=512,
                      linger_s=0.05, registry=reg)
    futures = [buf.submit([ev(i)], APP) for i in range(200)]
    ids = [f.result(timeout=10)[0] for f in futures]
    buf.stop()
    assert len(set(ids)) == 200
    assert sorted(stored_ids(sqlite_store)) == sorted(ids)
    # the whole burst must land in FEW flushes, not 200 transactions
    assert reg.get("pio_ingest_flush_size").total_count() <= 20


@pytest.mark.parametrize("backend", ["sqlite", "parquet"])
def test_concurrent_per_request_inserts_land_exactly_once(
        backend, sqlite_store, parquet_store):
    """The unbuffered write path: one `insert` a request from several
    request threads at once (the event server's executor with the write
    buffer off). Every event is stored once, under the id its caller
    was given."""
    store = sqlite_store if backend == "sqlite" else parquet_store
    clients, per = 8, 12
    ids, errors = [[] for _ in range(clients)], []
    start = threading.Barrier(clients)

    def client(c):
        try:
            start.wait(10)
            for k in range(per):
                ids[c].append(store.insert(ev(c * per + k), APP))
        except Exception as e:      # surfaced below, on the test thread
            errors.append(e)

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads) and not errors, errors
    given = [i for mine in ids for i in mine]
    assert len(set(given)) == clients * per
    assert sorted(stored_ids(store)) == sorted(given)


def test_retry_fail_n_then_recover_no_loss_no_dup(sqlite_store):
    reg = MetricsRegistry()
    faulty = FaultyEvents(sqlite_store, fail_n=3, when="before")
    buf = WriteBuffer(store_fn=lambda: faulty, retries=5, backoff_s=0.001,
                      backoff_cap_s=0.002, linger_s=0.01, registry=reg)
    futures = [buf.submit([ev(i)], APP) for i in range(50)]
    for f in futures:
        f.result(timeout=10)
    buf.stop()
    assert faulty.faults_fired == 3
    assert reg.get("pio_ingest_retry_total").value() >= 1
    assert len(stored_ids(sqlite_store)) == 50
    assert len(set(stored_ids(sqlite_store))) == 50


@pytest.mark.parametrize("backend", ["sqlite", "parquet"])
def test_ambiguous_post_commit_fault_does_not_duplicate(
        backend, sqlite_store, parquet_store):
    """when='after' commits the write and THEN faults — the retry must
    dedup on the pre-assigned ids instead of double-writing."""
    store = sqlite_store if backend == "sqlite" else parquet_store
    faulty = FaultyEvents(store, fail_n=2, when="after")
    buf = WriteBuffer(store_fn=lambda: faulty, retries=4, backoff_s=0.001,
                      backoff_cap_s=0.002, linger_s=0.01)
    futures = [buf.submit([ev(i)], APP) for i in range(30)]
    ids = [f.result(timeout=10)[0] for f in futures]
    buf.stop()
    assert faulty.faults_fired == 2
    assert sorted(stored_ids(store)) == sorted(ids)       # no loss
    assert len(stored_ids(store)) == 30                   # no duplication


def test_random_error_rate_and_latency_chaos(sqlite_store):
    """Sustained random faults + added latency: every ack'd event stored
    exactly once."""
    faulty = FaultyEvents(sqlite_store, error_rate=0.3, latency_s=0.002,
                          seed=42)
    buf = WriteBuffer(store_fn=lambda: faulty, retries=8, backoff_s=0.001,
                      backoff_cap_s=0.005, linger_s=0.005, flush_max=16)
    futures = [buf.submit([ev(i)], APP) for i in range(60)]
    ids = [f.result(timeout=30)[0] for f in futures]
    buf.stop()
    assert faulty.faults_fired > 0, "chaos did not fire; test is vacuous"
    assert sorted(stored_ids(sqlite_store)) == sorted(ids)
    assert len(stored_ids(sqlite_store)) == 60


def test_raw_backend_exception_is_retried(sqlite_store):
    """Transient faults surface as raw driver/fs errors too (psycopg
    OperationalError, fsspec OSError) — the retry loop must not be
    limited to StorageError."""
    class RawFault:
        def __init__(self, inner):
            self.inner = inner
            self.fails = 2

        def insert_batch(self, events, app_id, channel_id=None):
            if self.fails:
                self.fails -= 1
                raise OSError("transient fs blip")
            return self.inner.insert_batch(events, app_id, channel_id)

        def insert_batch_idempotent(self, events, app_id, channel_id=None):
            if self.fails:
                self.fails -= 1
                raise OSError("transient fs blip")
            return self.inner.insert_batch_idempotent(
                events, app_id, channel_id)

        def __getattr__(self, name):
            return getattr(self.inner, name)

    raw = RawFault(sqlite_store)
    buf = WriteBuffer(store_fn=lambda: raw, retries=4,
                      backoff_s=0.001, backoff_cap_s=0.002, linger_s=0.0)
    ids = buf.submit([ev(0)], APP).result(timeout=10)
    buf.stop()
    assert stored_ids(sqlite_store) == ids


def test_flush_timeout_hung_backend_recovers(sqlite_store):
    class SlowOnce:
        def __init__(self, inner):
            self.inner = inner
            self.calls = 0

        def insert_batch(self, events, app_id, channel_id=None):
            self.calls += 1
            if self.calls == 1:
                time.sleep(0.5)     # hang past the flush timeout
            return self.inner.insert_batch(events, app_id, channel_id)

        def __getattr__(self, name):
            return getattr(self.inner, name)

    slow = SlowOnce(sqlite_store)
    # timeout 0.3 + one grace period: the hung attempt resolves at 0.5,
    # inside the grace window, and its outcome is ADOPTED (no concurrent
    # retry that could double-write)
    buf = WriteBuffer(store_fn=lambda: slow, retries=3, backoff_s=0.001,
                      backoff_cap_s=0.002, linger_s=0.0,
                      flush_timeout_s=0.3)
    ids = buf.submit([ev(0), ev(1)], APP).result(timeout=10)
    buf.stop()
    assert slow.calls == 1            # adopted, not retried
    assert sorted(stored_ids(sqlite_store)) == sorted(ids)
    assert len(stored_ids(sqlite_store)) == 2


def test_flush_hung_past_grace_fails_without_retry(sqlite_store):
    """A write still hanging after timeout + grace fails the batch with
    NO retry: a concurrent retry could duplicate on backends whose
    idempotent insert is a non-atomic scan-then-write (parquet)."""
    class Hung:
        def __init__(self, inner):
            self.inner = inner
            self.calls = 0

        def insert_batch(self, events, app_id, channel_id=None):
            self.calls += 1
            time.sleep(1.0)       # far past timeout (0.1) + grace (0.1)
            return self.inner.insert_batch(events, app_id, channel_id)

        def __getattr__(self, name):
            return getattr(self.inner, name)

    hung = Hung(sqlite_store)
    buf = WriteBuffer(store_fn=lambda: hung, retries=3, backoff_s=0.001,
                      linger_s=0.0, flush_timeout_s=0.1)
    fut = buf.submit([ev(0)], APP)
    with pytest.raises(StorageError, match="hung past"):
        fut.result(timeout=10)
    buf.stop()
    time.sleep(1.1)               # let the abandoned write land
    assert hung.calls == 1        # never retried concurrently
    assert len(stored_ids(sqlite_store)) == 1   # landed once, not twice


def test_exhausted_retries_fail_the_caller(sqlite_store):
    faulty = FaultyEvents(sqlite_store, fail_n=100, when="before")
    buf = WriteBuffer(store_fn=lambda: faulty, retries=1, backoff_s=0.001,
                      backoff_cap_s=0.002, linger_s=0.0)
    fut = buf.submit([ev(0)], APP)
    with pytest.raises(StorageError, match="injected fault"):
        fut.result(timeout=10)
    buf.stop()
    assert stored_ids(sqlite_store) == []


def test_bounded_queue_sheds_with_retry_after(sqlite_store):
    reg = MetricsRegistry()
    gated = Gated(sqlite_store)
    buf = WriteBuffer(store_fn=lambda: gated, queue_max=2, linger_s=0.0,
                      registry=reg)
    f1 = buf.submit([ev(0)], APP)
    f2 = buf.submit([ev(1)], APP)
    with pytest.raises(BufferFull) as exc:
        buf.submit([ev(2)], APP)
    assert exc.value.retry_after >= 1
    assert reg.get("pio_ingest_shed_total").value() == 1
    gated.gate.set()
    assert f1.result(timeout=10) and f2.result(timeout=10)
    buf.stop()
    assert len(stored_ids(sqlite_store)) == 2


def test_stop_drains_buffered_events(sqlite_store):
    # long linger + huge flush bound: everything sits buffered until stop
    buf = WriteBuffer(store_fn=lambda: sqlite_store, linger_s=30.0,
                      flush_max=100_000)
    futures = [buf.submit([ev(i)], APP) for i in range(20)]
    t0 = time.monotonic()
    buf.stop(drain=True)
    assert time.monotonic() - t0 < 10, "drain must cut the linger short"
    for f in futures:
        assert f.result(timeout=0.1)
    assert len(stored_ids(sqlite_store)) == 20
    with pytest.raises(StorageError, match="shut down"):
        buf.submit([ev(99)], APP)


def test_stop_without_drain_fails_queued(sqlite_store):
    gated = Gated(sqlite_store)
    buf = WriteBuffer(store_fn=lambda: gated, linger_s=0.0)
    f1 = buf.submit([ev(0)], APP)
    time.sleep(0.05)                       # worker now blocked flushing f1
    f2 = buf.submit([ev(1)], APP)          # still queued
    threading.Thread(target=buf.stop,
                     kwargs={"drain": False, "timeout_s": 5}).start()
    with pytest.raises(StorageError, match="stopped before flush"):
        f2.result(timeout=5)
    gated.gate.set()
    assert f1.result(timeout=10)
    assert len(stored_ids(sqlite_store)) == 1


# ---------------------------------------------------------------------------
# Fault injector units + registry gate
# ---------------------------------------------------------------------------

def test_faulty_events_delegates_unfaulted_ops(sqlite_store):
    faulty = FaultyEvents(sqlite_store, fail_n=100)
    sqlite_store.insert(ev(0), APP)
    assert len(list(faulty.find(APP))) == 1        # reads untouched
    with pytest.raises(StorageError, match="injected fault in insert"):
        faulty.insert(ev(1), APP)


def test_faulty_events_error_rate_certain():
    class Null:
        def insert(self, *a, **k):
            return "id"

    faulty = FaultyEvents(Null(), error_rate=1.0, seed=1)
    with pytest.raises(StorageError):
        faulty.insert(ev(0), APP)


def test_fault_env_gate_wraps_event_store(tmp_path, monkeypatch):
    from predictionio_tpu.storage.registry import Storage

    monkeypatch.setenv("PIO_FAULT_FAIL_N", "2")
    monkeypatch.setenv("PIO_FAULT_SEED", "3")
    Storage.configure({
        "sources": {"DB": {"TYPE": "sqlite",
                           "PATH": str(tmp_path / "chaos.db")}},
        "repositories": {
            r: {"NAME": "pio", "SOURCE": "DB"}
            for r in ("METADATA", "EVENTDATA", "MODELDATA")},
    })
    try:
        store = Storage.get_events()
        assert isinstance(store, FaultyEvents)
        store.init_channel(APP)
        for _ in range(2):
            with pytest.raises(StorageError, match="injected fault"):
                store.insert(ev(0), APP)
        assert store.insert(ev(0), APP)    # fail-N exhausted: recovered
    finally:
        Storage.reset()


def test_kill_points_seed_from_env(monkeypatch):
    monkeypatch.setenv("PIO_FAULT_KILL", "compact:committed")
    faults._kill_points = None             # force re-seed from env
    assert "compact:committed" in faults.armed_kill_points()
    with pytest.raises(CrashError):
        faults.maybe_kill("compact:committed")
    faults.maybe_kill("compact:committed")  # fired once; disarmed


# ---------------------------------------------------------------------------
# Idempotent inserts (the retry primitive)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["sqlite", "parquet"])
def test_insert_batch_idempotent_exactly_once(
        backend, sqlite_store, parquet_store):
    store = sqlite_store if backend == "sqlite" else parquet_store
    import dataclasses as dc
    events = [dc.replace(ev(i), event_id=f"fixed{i}") for i in range(5)]
    store.insert_batch(events[:3], APP)          # partial first attempt
    ids = store.insert_batch_idempotent(events, APP)
    ids2 = store.insert_batch_idempotent(events, APP)
    assert ids == ids2 == [f"fixed{i}" for i in range(5)]
    assert sorted(stored_ids(store)) == sorted(ids)


def test_insert_batch_idempotent_requires_ids(sqlite_store):
    with pytest.raises(StorageError, match="pre-assigned"):
        sqlite_store.insert_batch_idempotent([ev(0)], APP)


def test_base_default_idempotent_insert(sqlite_store):
    """The SPI default (get-probe + insert_batch) against a real backend."""
    import dataclasses as dc
    events = [dc.replace(ev(i), event_id=f"base{i}") for i in range(4)]
    sqlite_store.insert_batch(events[:2], APP)
    ids = storage_base.EventStore.insert_batch_idempotent(
        sqlite_store, events, APP)
    assert ids == [f"base{i}" for i in range(4)]
    assert len(stored_ids(sqlite_store)) == 4


# ---------------------------------------------------------------------------
# Crash-safe compaction + retention
# ---------------------------------------------------------------------------

def _seed_fragments(store, n_frags=5, per_frag=10, deletes=7):
    i = 0
    for _ in range(n_frags):
        store.insert_batch([ev(i + j) for j in range(per_frag)], APP)
        i += per_frag
    all_ids = stored_ids(store)
    for eid in all_ids[:deletes]:
        assert store.delete(eid, APP)
    return sorted(all_ids[deletes:])


def _junk(store):
    ns = store._ns(APP, None)
    fs = store.client.fs
    return (fs.glob(f"{ns}/merging-*") + fs.glob(f"{ns}/compact-*")
            + fs.glob(f"{ns}/tmp-*"))


def test_compact_merges_fragments_and_folds_tombstones(parquet_store):
    live = _seed_fragments(parquet_store)
    ns = parquet_store._ns(APP, None)
    assert len(parquet_store._fragments(ns)) == 5
    stats = parquet_store.compact(APP)
    assert stats["fragments_before"] == 5
    assert stats["fragments_after"] == 1
    assert stats["tombstones_folded"] == 7
    assert stats["removed_rows"] == 7
    assert sorted(stored_ids(parquet_store)) == live
    assert parquet_store.client.fs.glob(f"{ns}/tomb-*") == []
    assert _junk(parquet_store) == []
    # idempotent: a second run is a no-op
    stats2 = parquet_store.compact(APP)
    assert stats2["fragments_after"] == 1
    assert stats2["removed_rows"] == 0
    assert sorted(stored_ids(parquet_store)) == live


def test_compact_ttl_retention(parquet_store):
    now = dt.datetime.now(tz=UTC)
    old = [ev(i, t=now - dt.timedelta(days=30)) for i in range(5)]
    new = [ev(100 + i, t=now) for i in range(5)]
    parquet_store.insert_batch(old, APP)
    new_ids = parquet_store.insert_batch(new, APP)
    stats = parquet_store.compact(APP, ttl_days=7)
    assert stats["expired_rows"] == 5
    assert sorted(stored_ids(parquet_store)) == sorted(new_ids)


def test_sqlite_compact_ttl_retention(sqlite_store):
    now = dt.datetime.now(tz=UTC)
    sqlite_store.insert_batch(
        [ev(i, t=now - dt.timedelta(days=30)) for i in range(4)], APP)
    keep = sqlite_store.insert_batch([ev(10, t=now)], APP)
    stats = sqlite_store.compact(APP, ttl_days=7)
    assert stats["removed_rows"] == 4
    assert stored_ids(sqlite_store) == keep


def test_base_default_compact_ttl(sqlite_store):
    now = dt.datetime.now(tz=UTC)
    sqlite_store.insert_batch(
        [ev(i, t=now - dt.timedelta(days=30)) for i in range(3)], APP)
    keep = sqlite_store.insert_batch([ev(10, t=now)], APP)
    stats = storage_base.EventStore.compact(sqlite_store, APP, ttl_days=7)
    assert stats["removed_rows"] == 3
    assert stored_ids(sqlite_store) == keep


@pytest.mark.parametrize("kill_point", [
    "compact:pending-written",      # before the manifest commit
    "compact:committed",            # after commit, before any finish step
    "compact:renamed",              # merged renamed, old still present
    "compact:old-removed",          # old gone, tombstones + manifest left
    "compact:gen-bumped",           # generation bumped, manifest left
])
def test_kill_mid_compaction_no_loss_no_dup(parquet_store, kill_point):
    live = _seed_fragments(parquet_store)
    faults.set_kill_points([kill_point])
    with pytest.raises(CrashError):
        parquet_store.compact(APP)
    # crashed at ANY point: readers still see exactly the live set
    assert sorted(stored_ids(parquet_store)) == live
    assert sorted(set(stored_ids(parquet_store))) == live   # no dup rows
    # recovery: the next compact rolls forward / GCs and converges
    stats = parquet_store.compact(APP)
    assert sorted(stored_ids(parquet_store)) == live
    assert stats["fragments_after"] == 1
    assert _junk(parquet_store) == []
    ns = parquet_store._ns(APP, None)
    assert parquet_store.client.fs.glob(f"{ns}/tomb-*") == []


def test_concurrent_reader_sees_consistent_rows_during_compaction(
        parquet_store):
    """Satellite: a reader re-reading while compaction rewrites fragments
    underneath it must always see exactly the live rows — never a
    partial, duplicated, or resurrected view."""
    live = _seed_fragments(parquet_store, n_frags=24, per_frag=4, deletes=9)
    errors, stop = [], threading.Event()

    def reader():
        while not stop.is_set():
            try:
                got = sorted(
                    parquet_store.find_columnar(APP).column("event_id")
                    .to_pylist())
                if got != live:
                    errors.append(f"inconsistent read: {len(got)} rows")
            except Exception as e:  # noqa: BLE001
                errors.append(repr(e))

    threads = [threading.Thread(target=reader) for _ in range(2)]
    for t in threads:
        t.start()
    try:
        for _ in range(3):
            parquet_store.compact(APP)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
    assert errors == []
    assert sorted(stored_ids(parquet_store)) == live


def test_sharded_snapshot_invalidated_by_compaction(parquet_store):
    _seed_fragments(parquet_store, n_frags=4, per_frag=5, deletes=0)
    snap = parquet_store.read_snapshot(APP)
    parquet_store.compact(APP)
    with pytest.raises(StorageError, match="snapshot invalidated"):
        parquet_store.find_columnar(APP, shard=(0, 2, snap))


def test_idempotent_reinsert_of_deleted_id_writes(parquet_store):
    """The retry-path id scan must not count a tombstoned dead row as
    'already persisted' — that would ack a reinserted event that stays
    invisible forever."""
    import dataclasses as dc
    parquet_store.insert_batch([dc.replace(ev(0), event_id="rx")], APP)
    assert parquet_store.delete("rx", APP)
    parquet_store.insert_batch_idempotent(
        [dc.replace(ev(5), event_id="rx")], APP)
    got = parquet_store.get("rx", APP)
    assert got is not None and got.entity_id == "u5"


def test_reinsert_after_delete_append_only(parquet_store):
    """Reinserting a deleted id never rewrites fragments (the append-only
    invariant that makes inserts safe under concurrent compaction): the
    event is visible again exactly once via latest-wins dedup, and
    compaction folds the dead physical row away."""
    import dataclasses as dc
    t0 = dt.datetime(2026, 1, 1, tzinfo=UTC)
    first = dc.replace(ev(0), event_id="reused", creation_time=t0)
    parquet_store.insert_batch([first, ev(1)], APP)
    assert parquet_store.delete("reused", APP)
    assert parquet_store.get("reused", APP) is None
    second = dc.replace(ev(2), event_id="reused",
                        creation_time=t0 + dt.timedelta(seconds=5))
    parquet_store.insert_batch([second], APP)
    # visible again, once, and it is the NEW row
    got = parquet_store.get("reused", APP)
    assert got is not None and got.entity_id == "u2"
    ids = stored_ids(parquet_store)
    assert sorted(ids).count("reused") == 1 and len(ids) == 2
    stats = parquet_store.compact(APP)
    assert stats["fragments_after"] == 1
    ids = stored_ids(parquet_store)
    assert ids.count("reused") == 1 and len(ids) == 2
    assert parquet_store.get("reused", APP).entity_id == "u2"


def test_torn_fragment_write_never_visible(parquet_store, monkeypatch):
    parquet_store.insert_batch([ev(0)], APP)
    ns = parquet_store._ns(APP, None)
    before = parquet_store._fragments(ns)

    def boom(*a, **k):
        raise OSError("injected crash during rename")

    monkeypatch.setattr(parquet_store.client.fs, "mv", boom)
    with pytest.raises(OSError):
        parquet_store.insert_batch([ev(1)], APP)
    monkeypatch.undo()
    # the torn write left neither a visible fragment nor tmp garbage
    assert parquet_store._fragments(ns) == before
    assert _junk(parquet_store) == []
    assert len(stored_ids(parquet_store)) == 1


# ---------------------------------------------------------------------------
# HTTP-level chaos: the full event server under faults
# ---------------------------------------------------------------------------

@pytest.fixture()
def http_backend(tmp_path):
    from predictionio_tpu.storage import AccessKey, App, Storage

    Storage.configure({
        "sources": {"DB": {"TYPE": "sqlite",
                           "PATH": str(tmp_path / "es.db")}},
        "repositories": {
            r: {"NAME": "pio", "SOURCE": "DB"}
            for r in ("METADATA", "EVENTDATA", "MODELDATA")},
    })
    apps = Storage.get_meta_data_apps()
    app_id = apps.insert(App(id=0, name="chaosapp"))
    Storage.get_events().init_channel(app_id)
    key = Storage.get_meta_data_access_keys().insert(
        AccessKey(key="", appid=app_id, events=()))
    yield {"app_id": app_id, "key": key}
    Storage.reset()


EV = {"event": "view", "entityType": "user", "entityId": "u1",
      "targetEntityType": "item", "targetEntityId": "i1"}


async def _serve(server):
    client = TestClient(TestServer(server.app))
    await client.start_server()
    return client


async def test_http_429_shed_when_queue_full(http_backend):
    from predictionio_tpu.server.event_server import EventServer
    from predictionio_tpu.storage import Storage
    from predictionio_tpu.utils.server_config import IngestConfig

    server = EventServer(ingest=IngestConfig(queue_max=1, linger_s=0.0,
                                             retries=0))
    gated = Gated(Storage.get_events())
    server.buffer._store_fn = lambda: gated
    c = await _serve(server)
    try:
        url = f"/events.json?accessKey={http_backend['key']}"
        blocked = asyncio.ensure_future(c.post(url, json=EV))
        await asyncio.sleep(0.2)            # let it occupy the queue bound
        shed = await c.post(url, json=EV)
        assert shed.status == 429
        assert int(shed.headers["Retry-After"]) >= 1
        assert "full" in (await shed.json())["message"]
        assert server.registry.get("pio_ingest_shed_total").value() == 1
        gated.gate.set()
        assert (await blocked).status == 201
    finally:
        gated.gate.set()
        await c.close()


async def test_http_batch_per_event_503_on_storage_failure(http_backend):
    """Satellite: a failing insert_batch must not discard the per-event
    validation results already computed — failed inserts report 503
    apiece, the 400s survive."""
    from predictionio_tpu.server.event_server import EventServer
    from predictionio_tpu.storage import Storage
    from predictionio_tpu.utils.server_config import IngestConfig

    server = EventServer(ingest=IngestConfig(retries=0, linger_s=0.0,
                                             backoff_s=0.001))
    server.buffer._store_fn = lambda: FaultyEvents(
        Storage.get_events(), error_rate=1.0, seed=0)
    c = await _serve(server)
    try:
        batch = [dict(EV, entityId="ok1"),
                 {"event": "view", "entityType": "user"},   # no entityId
                 dict(EV, entityId="ok2")]
        resp = await c.post(
            f"/batch/events.json?accessKey={http_backend['key']}",
            json=batch)
        assert resp.status == 200
        results = await resp.json()
        assert [r["status"] for r in results] == [503, 400, 503]
        assert "injected fault" in results[0]["message"]
        single = await c.post(
            f"/events.json?accessKey={http_backend['key']}", json=EV)
        assert single.status == 503
    finally:
        await c.close()


async def test_http_batch_per_event_503_direct_path(http_backend,
                                                    monkeypatch):
    """Same per-event semantics with the buffer disabled (the pre-buffer
    direct write path keeps reference parity)."""
    from predictionio_tpu.server.event_server import EventServer
    from predictionio_tpu.storage import Storage
    from predictionio_tpu.utils.server_config import IngestConfig

    server = EventServer(ingest=IngestConfig(buffer=False))
    assert server.buffer is None
    faulty = FaultyEvents(Storage.get_events(), error_rate=1.0, seed=0)
    monkeypatch.setattr(Storage, "get_events", classmethod(
        lambda cls: faulty))
    c = await _serve(server)
    try:
        batch = [dict(EV, entityId="ok1"),
                 {"event": "view", "entityType": "user"},
                 dict(EV, entityId="ok2")]
        resp = await c.post(
            f"/batch/events.json?accessKey={http_backend['key']}",
            json=batch)
        assert resp.status == 200
        assert [r["status"] for r in await resp.json()] == [503, 400, 503]
    finally:
        await c.close()


async def test_http_max_events_per_batch_configurable(http_backend,
                                                      monkeypatch):
    from predictionio_tpu.server.event_server import EventServer
    from predictionio_tpu.utils.server_config import IngestConfig

    monkeypatch.setenv("PIO_MAX_EVENTS_PER_BATCH", "2")
    cfg = IngestConfig.from_env()
    assert cfg.max_events_per_batch == 2
    server = EventServer(ingest=cfg)
    c = await _serve(server)
    try:
        url = f"/batch/events.json?accessKey={http_backend['key']}"
        ok = await c.post(url, json=[dict(EV, entityId=f"u{i}")
                                     for i in range(2)])
        assert ok.status == 200
        over = await c.post(url, json=[dict(EV, entityId=f"u{i}")
                                       for i in range(3)])
        assert over.status == 400
        assert "2" in (await over.json())["message"]
    finally:
        await c.close()


async def test_http_shutdown_drains_buffer(http_backend):
    from predictionio_tpu.server.event_server import EventServer
    from predictionio_tpu.storage import Storage
    from predictionio_tpu.utils.server_config import IngestConfig

    server = EventServer(ingest=IngestConfig())
    c = await _serve(server)
    resp = await c.post(f"/events.json?accessKey={http_backend['key']}",
                        json=EV)
    assert resp.status == 201
    await c.close()    # triggers on_shutdown -> buffer.stop(drain=True)
    with pytest.raises(StorageError, match="shut down"):
        server.buffer.submit([ev(0)], http_backend["app_id"])
    assert len(list(Storage.get_events().find(http_backend["app_id"]))) == 1
