"""The sqlite training scan over rowid windows in reader processes
(storage/sqlite_scan.py, storage/sqlite_scan_reader.py).

The contract: a fanned-out `SqliteEvents.find_columnar` is the serial
scan's table row for row, taken at one instant, and every read the rule
does not admit takes the serial path untouched. The rule's constants are
patched down here (a reader per 20 rowids, 3 at most, 4 CPUs) so that
tables of a hundred rows fan out; nothing is read from the environment.
"""

import datetime as dt
import os
import sqlite3
import subprocess
import sys
import time

import pyarrow as pa
import pytest

from predictionio_tpu.data import DataMap, Event
from predictionio_tpu.obs.registry import default_registry
from predictionio_tpu.storage import UNFILTERED, StorageError, sqlite_scan
from predictionio_tpu.storage.sqlite_backend import (
    SqliteClient, SqliteEvents, event_table_name,
)

UTC = dt.timezone.utc
APP = 1
TABLE = event_table_name(APP, None)


def ms(t: int) -> dt.datetime:
    return dt.datetime.fromtimestamp(t / 1000, tz=UTC)


def _event(k: int) -> Event:
    """Row k of the fixture: ratings with JSON properties, views without,
    `$set`s without a target, ids outside ASCII, two time zones."""
    when = ms(1_400_000_000_000 + 1000 * ((k * 37) % 101))
    if k % 11 == 0:
        return Event(event="$set", entity_type="item", entity_id=f"i{k % 7}",
                     properties=DataMap({"categories": ["c", "é"]}),
                     event_time=when, creation_time=ms(1_500_000_000_000 + k))
    if k % 3 == 0:
        return Event(event="view", entity_type="user",
                     entity_id=f"用户{k % 13}",
                     target_entity_type="item", target_entity_id=f"i{k % 7}",
                     event_time=when.astimezone(
                         dt.timezone(dt.timedelta(hours=9))),
                     creation_time=ms(1_500_000_000_000 + k))
    return Event(event="rate", entity_type="user", entity_id=f"u{k % 13}",
                 target_entity_type="item", target_entity_id=f"ïd{k % 7}",
                 properties=DataMap({"rating": (k % 10) / 2}),
                 event_time=when, creation_time=ms(1_500_000_000_000 + k))


def _raw_insert(path: str, k: int, properties="") -> None:
    """One row through a connection of its own (another process's
    commit, as far as the store's connection can tell); `properties` an
    empty string, which `insert_batch` never writes and the scan still
    has to turn into null."""
    conn = sqlite3.connect(path)
    conn.execute(
        f"INSERT INTO {TABLE} VALUES (?,?,?,?,?,?,?,?,?,?,?,?,?)",
        (f"raw{k}", "rate", "user", f"raw-u{k}", "item", f"raw-i{k}",
         properties, 1_400_000_000_000 + k, 0, None, None,
         1_500_000_000_000 + k, 0))
    conn.commit()
    conn.close()


def _raw_exec(path: str, sql: str, params=()) -> None:
    conn = sqlite3.connect(path)
    conn.execute(sql, params)
    conn.commit()
    conn.close()


@pytest.fixture()
def small_rule(monkeypatch):
    """The fan-out rule at test size."""
    monkeypatch.setattr(sqlite_scan, "ROWIDS_PER_READER", 20)
    monkeypatch.setattr(sqlite_scan, "MAX_READERS", 3)
    monkeypatch.setattr(sqlite_scan, "usable_cpus", lambda: 4)


@pytest.fixture()
def store(tmp_path, small_rule, monkeypatch):
    """120 rows in a file-backed store, with a hole in the rowids; any
    temporary file a scan made would land in an empty directory the
    teardown looks into."""
    scratch = tmp_path / "tmp"
    scratch.mkdir()
    monkeypatch.setenv("TMPDIR", str(scratch))
    monkeypatch.setattr("tempfile.tempdir", None)
    s = SqliteEvents(SqliteClient(str(tmp_path / "db" / "pio.db")))
    s.init_channel(APP)
    s.insert_batch([_event(k) for k in range(116)], APP)
    for k in range(4):
        _raw_insert(s.client.path, k)
    _raw_exec(s.client.path,
              f"DELETE FROM {TABLE} WHERE rowid BETWEEN 50 AND 58")
    yield s
    s.close()
    assert _reader_processes() == []
    assert sorted(os.listdir(tmp_path / "db")) in (
        ["pio.db"], ["pio.db", "pio.db-shm", "pio.db-wal"])
    assert os.listdir(scratch) == []


def _reader_processes():
    """This process's children that run the reader, or wait unreaped."""
    me, out = str(os.getpid()), []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/stat") as f:
                stat = f.read()
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                cmdline = f.read()
        except OSError:
            continue
        state, ppid = stat.rsplit(")", 1)[1].split()[:2]
        if ppid == me and (b"sqlite_scan_reader" in cmdline
                           or (state == "Z" and not cmdline)):
            out.append((pid, state, cmdline))
    return out


class Readers:
    """What `pio_ingest_scan_readers` and its two counters gained."""

    def __init__(self):
        self.h = default_registry().histogram(
            "pio_ingest_scan_readers", buckets=(1, 2, 4, 8, 16))
        self.start = (self.h.count(), self.h.sum_())
        self.c0 = {k: self._counter(k) for k in
                   ("snapshot", "reader_failed", "retries")}

    @staticmethod
    def _counter(kind: str) -> float:
        reg = default_registry()
        if kind == "retries":
            return reg.counter(
                "pio_ingest_scan_snapshot_retries_total").value()
        return reg.counter("pio_ingest_scan_fallback_total",
                           labelnames=("reason",)).value(reason=kind)

    def used(self):
        """Readers of each scan since the start, when all used the same
        number; else (scans, sum)."""
        n = self.h.count() - self.start[0]
        total = self.h.sum_() - self.start[1]
        return total / n if n and total % n == 0 else (n, total)

    def gained(self, kind: str) -> float:
        return self._counter(kind) - self.c0[kind]


def serial(store, **kw) -> pa.Table:
    """The same read with the fan-out off: the answer to equal."""
    real = sqlite_scan.planned_readers
    sqlite_scan.planned_readers = lambda span: 1
    try:
        return store.find_columnar(APP, **kw)
    finally:
        sqlite_scan.planned_readers = real


def same(a: pa.Table, b: pa.Table) -> bool:
    return a.schema == b.schema and a.combine_chunks().equals(
        b.combine_chunks())


TRAIN = dict(ordered=False, entity_type="user",
             event_names=["rate", "buy"], target_entity_type="item")

#: every projection an engine's DataSource, fold-in, the serving cache or
#: aggregate_properties asks for, and the whole schema
PROJECTIONS = [
    None,
    ("event", "entity_id", "target_entity_id", "properties"),
    ("event", "entity_id", "target_entity_id"),
    ("entity_id", "target_entity_id", "event_time_ms"),
    ("event", "entity_id", "target_entity_id", "event_time_ms"),
    ("event", "entity_id", "properties", "event_time_ms"),
    ("event", "target_entity_id", "properties"),
    ("event_id", "event", "entity_id", "target_entity_type",
     "target_entity_id", "event_time_ms"),
    ("target_entity_id",),
    ("properties",),
    ("creation_time_ms", "event_time_ms"),
]


@pytest.mark.parametrize("columns", PROJECTIONS,
                         ids=lambda c: "all" if c is None else "+".join(c))
def test_fan_out_equals_serial_for_every_projection(store, columns):
    seen = Readers()
    got = store.find_columnar(APP, ordered=False, columns=columns)
    assert seen.used() == 3
    want = serial(store, ordered=False, columns=columns)
    assert same(got, want)
    assert got.num_rows == 111
    if columns is None or "properties" in columns:
        # 4 raw rows with '' and the views: null, as the serial scan
        assert got.column("properties").null_count >= 4
    if columns is None or "target_entity_id" in columns:
        assert got.column("target_entity_id").null_count > 0


#: the filters `_find_sql` takes that leave the read a whole-table scan
WIDE_FILTERS = {
    "none": {},
    "training_read": dict(entity_type="user", event_names=["rate", "buy"],
                          target_entity_type="item"),
    "entity_type": dict(entity_type="item"),
    "event_names": dict(event_names=["view", "$set"]),
    "no_target_type": dict(target_entity_type=None),
    "target_type": dict(target_entity_type="item"),
    "properties_fold": dict(entity_type="item",
                            event_names=["$set", "$unset", "$delete"]),
    "nothing_matches": dict(event_names=["no-such-event"]),
    "unfiltered_sentinels": dict(target_entity_type=UNFILTERED,
                                 target_entity_id=UNFILTERED),
}


@pytest.mark.parametrize("name", sorted(WIDE_FILTERS))
def test_fan_out_equals_serial_under_every_wide_filter(store, name):
    kw = dict(ordered=False, columns=(
        "event", "entity_id", "target_entity_id", "properties",
        "event_time_ms"), **WIDE_FILTERS[name])
    seen = Readers()
    got = store.find_columnar(APP, **kw)
    assert seen.used() == 3
    assert same(got, serial(store, **kw))
    if name == "nothing_matches":
        assert got.num_rows == 0 and got.schema.names == list(kw["columns"])


#: reads the rule keeps on the serial path, each with the reason
NARROW = {
    "ordered": dict(ordered=True),
    "default_order": dict(),
    "limit": dict(ordered=False, limit=5),
    "limit_all": dict(ordered=False, limit=-1, reversed_order=True),
    "reversed": dict(ordered=False, reversed_order=True),
    "entity_id": dict(ordered=False, entity_type="user", entity_id="u3"),
    "target_entity_id": dict(ordered=False, target_entity_id="i3"),
    "no_target_id": dict(ordered=False, target_entity_id=None),
    "start_time": dict(ordered=False, start_time=ms(1_400_000_050_000)),
    "until_time": dict(ordered=False, until_time=ms(1_400_000_050_000)),
}


@pytest.mark.parametrize("name", sorted(NARROW))
def test_narrow_reads_take_the_serial_path(store, name, monkeypatch):
    monkeypatch.setattr(
        sqlite_scan, "_Readers",
        lambda *a: pytest.fail("a narrow read started readers"))
    seen = Readers()
    got = store.find_columnar(APP, **NARROW[name])
    assert seen.used() == 1
    assert got.num_rows > 0
    assert _reader_processes() == []


def test_small_span_and_memory_store_take_the_serial_path(
        tmp_path, small_rule, monkeypatch):
    monkeypatch.setattr(
        sqlite_scan, "_Readers",
        lambda *a: pytest.fail("a small or in-memory read started readers"))
    small = SqliteEvents(SqliteClient(str(tmp_path / "small.db")))
    memory = SqliteEvents(SqliteClient(":memory:"))
    for s, n in ((small, 39), (memory, 200)):
        s.init_channel(APP)
        s.insert_batch([_event(k) for k in range(n)], APP)
        seen = Readers()
        assert s.find_columnar(APP, ordered=False).num_rows == n
        assert seen.used() == 1
        s.close()


def test_rule_counts_rowids_cpus_and_the_cap(monkeypatch):
    monkeypatch.setattr(sqlite_scan, "usable_cpus", lambda: 13)
    assert [sqlite_scan.planned_readers(n) for n in
            (0, 249_999, 499_999, 500_000, 2_000_000, 20_000_000)] \
        == [1, 1, 1, 2, 8, 8]
    monkeypatch.setattr(sqlite_scan, "usable_cpus", lambda: 3)
    assert sqlite_scan.planned_readers(2_000_000) == 3
    monkeypatch.setattr(sqlite_scan, "usable_cpus", lambda: 1)
    assert sqlite_scan.planned_readers(2_000_000) == 1


def test_uncommitted_rows_of_this_connection_keep_the_read_serial(store):
    """No reader could see them; the serial scan does."""
    conn = store.client.conn()
    conn.execute(f"DELETE FROM {TABLE} WHERE rowid = 3")
    assert conn.in_transaction
    seen = Readers()
    assert store.find_columnar(APP, ordered=False).num_rows == 110
    assert seen.used() == 1
    conn.rollback()


@pytest.mark.parametrize("shard", [
    (0, 2), (1, 2), (2, 3, (1, 121)), (0, 1, (10, 90)), (1, 2, (1, 70))],
    ids=str)
def test_a_shard_read_splits_its_own_window(store, shard):
    seen = Readers()
    got = store.find_columnar(APP, shard=shard, **TRAIN)
    lo, hi = (shard[2] if len(shard) > 2 else (1, 121))
    span = -(-(hi - lo) // shard[1])
    assert seen.used() == max(1, min(3, span // 20))
    assert same(got, serial(store, shard=shard, **TRAIN))


def test_shards_of_a_fanned_out_read_partition_the_table(store):
    parts = [store.find_columnar(APP, ordered=False, shard=(i, 2, (1, 121)))
             for i in range(2)]
    assert same(pa.concat_tables(parts),
                serial(store, ordered=False))


def test_empty_table_has_the_schema(tmp_path, small_rule):
    s = SqliteEvents(SqliteClient(str(tmp_path / "empty.db")))
    s.init_channel(APP)
    seen = Readers()
    t = s.find_columnar(APP, ordered=False, columns=("event", "event_time_ms"))
    assert t.num_rows == 0
    assert t.schema == pa.schema([("event", pa.string()),
                                  ("event_time_ms", pa.int64())])
    assert seen.used() == 1       # no rowid, no window, no reader
    s.close()


def test_a_window_whose_rows_are_gone_is_an_empty_table(store):
    """Rowids 1 and 120 stay, everything between goes: three readers,
    two rows."""
    _raw_exec(store.client.path,
              f"DELETE FROM {TABLE} WHERE rowid > 1 AND rowid < 120")
    seen = Readers()
    got = store.find_columnar(APP, ordered=False)
    assert seen.used() == 3 and got.num_rows == 2
    assert same(got, serial(store, ordered=False))


def test_missing_table_raises_the_serial_paths_error(tmp_path, small_rule):
    s = SqliteEvents(SqliteClient(str(tmp_path / "none.db")))
    with pytest.raises(StorageError) as fanned:
        s.find_columnar(7, ordered=False)
    with pytest.raises(StorageError) as ordered:
        s.find_columnar(7, ordered=True)
    assert str(fanned.value) == str(ordered.value)
    assert "cannot read app 7 channel None: no such table" in str(
        fanned.value)
    s.close()


# ---------------------------------------------------------------------------
# one snapshot
# ---------------------------------------------------------------------------

def _commit_after_window(monkeypatch, commits):
    """Make another connection commit right after the parent has taken
    its window: `commits[k]` runs after the k-th window of the pinning
    rounds (the sizing read before the readers start is not one)."""
    real = sqlite_scan.rowid_window
    calls = []

    def window(conn, table):
        out = real(conn, table)
        calls.append(out)
        k = len(calls) - 2          # call 0 sizes the read
        if 0 <= k < len(commits):
            commits[k]()
        return out

    monkeypatch.setattr(sqlite_scan, "rowid_window", window)
    return calls


def test_an_append_between_window_and_pin_is_retried(store, monkeypatch):
    path = store.client.path
    calls = _commit_after_window(
        monkeypatch, [lambda: _raw_insert(path, 900, '{"rating": 1.0}')])
    seen = Readers()
    got = store.find_columnar(APP, ordered=False)
    assert seen.gained("retries") == 1 and seen.gained("snapshot") == 0
    assert seen.used() == 3
    assert [c[1] for c in calls] == [121, 121, 122]
    assert got.num_rows == 112
    assert same(got, serial(store, ordered=False))
    assert "raw900" in got.column("event_id").to_pylist()


def test_delete_newest_then_insert_reuses_the_rowid_and_is_retried(
        store, monkeypatch):
    """The window does not move (MAX(rowid) is 120 before and after); a
    count or a window comparison would miss it, data_version does not."""
    path = store.client.path

    def swap():
        _raw_exec(path, f"DELETE FROM {TABLE} WHERE rowid = 120")
        _raw_insert(path, 901, '{"rating": 2.0}')

    calls = _commit_after_window(monkeypatch, [swap])
    seen = Readers()
    got = store.find_columnar(APP, ordered=False)
    assert [c[1] for c in calls] == [121, 121, 121]
    assert seen.gained("retries") == 1 and seen.used() == 3
    ids = got.column("event_id").to_pylist()
    assert "raw901" in ids and "raw3" not in ids and got.num_rows == 111
    assert same(got, serial(store, ordered=False))


def test_a_store_that_never_holds_still_is_read_serially(store, monkeypatch):
    path = store.client.path
    commits = [lambda k=k: _raw_insert(path, 910 + k)
               for k in range(sqlite_scan.PIN_ATTEMPTS)]
    _commit_after_window(monkeypatch, commits)
    seen = Readers()
    got = store.find_columnar(APP, ordered=False)
    assert seen.gained("snapshot") == 1
    assert seen.gained("retries") == sqlite_scan.PIN_ATTEMPTS - 1
    assert seen.gained("reader_failed") == 0
    assert seen.used() == 1
    assert got.num_rows == 111 + sqlite_scan.PIN_ATTEMPTS
    assert _reader_processes() == []


def _before_first_scan(monkeypatch, action):
    """Run `action(readers)` once every reader is pinned and the check
    has passed: just before the first reader is told to scan."""
    real = sqlite_scan._Readers.tell
    done = []

    def tell(self, i, msg):
        if msg["op"] == "scan" and not done:
            done.append(True)
            action(self)
        return real(self, i, msg)

    monkeypatch.setattr(sqlite_scan._Readers, "tell", tell)


def test_commits_after_the_pin_do_not_show(store, monkeypatch):
    """A delete in the first window, an update in the last and an append,
    committed while every reader waits to scan: the table is the state
    at the pin; the next read sees all three."""
    path = store.client.path
    before = serial(store, ordered=False)

    def commits(readers):
        _raw_exec(path, f"DELETE FROM {TABLE} WHERE rowid = 5")
        _raw_exec(path, f"UPDATE {TABLE} SET entityId = 'changed' "
                        "WHERE rowid = 110")
        _raw_insert(path, 920)

    _before_first_scan(monkeypatch, commits)
    seen = Readers()
    got = store.find_columnar(APP, ordered=False)
    assert seen.used() == 3 and seen.gained("retries") == 0
    assert same(got, before)
    after = store.find_columnar(APP, ordered=False)
    assert after.num_rows == 111
    assert "changed" in after.column("entity_id").to_pylist()
    assert "changed" not in got.column("entity_id").to_pylist()
    assert same(after, serial(store, ordered=False))


# ---------------------------------------------------------------------------
# readers that fail
# ---------------------------------------------------------------------------

def test_a_reader_killed_mid_scan_gives_the_serial_answer(
        store, monkeypatch):
    def kill_one(readers):
        readers.procs[1].kill()

    _before_first_scan(monkeypatch, kill_one)
    seen = Readers()
    got = store.find_columnar(APP, **TRAIN)
    assert seen.gained("reader_failed") == 1 and seen.gained("snapshot") == 0
    assert seen.used() == 1
    assert same(got, serial(store, **TRAIN))
    assert _reader_processes() == []


def test_a_reader_that_cannot_start_gives_the_serial_answer(
        store, monkeypatch, tmp_path):
    broken = tmp_path / "broken_reader.py"
    broken.write_text("import sys\nsys.exit(3)\n")
    monkeypatch.setattr(sqlite_scan, "READER", str(broken))
    seen = Readers()
    got = store.find_columnar(APP, **TRAIN)
    assert seen.gained("reader_failed") == 1 and seen.used() == 1
    assert same(got, serial(store, **TRAIN))


def test_a_reader_that_hangs_times_out_and_is_killed(
        store, monkeypatch, tmp_path):
    hung = tmp_path / "hung_reader.py"
    hung.write_text("import time\ntime.sleep(600)\n")
    monkeypatch.setattr(sqlite_scan, "READER", str(hung))
    monkeypatch.setattr(sqlite_scan, "HANDSHAKE_TIMEOUT_S", 0.3)
    seen = Readers()
    t0 = time.monotonic()
    got = store.find_columnar(APP, **TRAIN)
    assert time.monotonic() - t0 < 30
    assert seen.gained("reader_failed") == 1 and seen.used() == 1
    assert same(got, serial(store, **TRAIN))
    assert _reader_processes() == []


@pytest.mark.parametrize("answer", [
    "table 999999\\n", "table 12\\nshort", "rows 5\\n", ""],
    ids=["ends_early", "not_arrow", "other_word", "silent"])
def test_a_short_or_foreign_answer_gives_the_serial_answer(
        store, monkeypatch, tmp_path, answer):
    """A reader that shakes hands and then answers short, with bytes
    that are no Arrow stream, with another word, or with nothing."""
    liar = tmp_path / "liar_reader.py"
    liar.write_text(
        "import sys\n"
        "o = sys.stdout\n"
        "o.write('ready\\n'); o.flush()\n"
        "for line in sys.stdin:\n"
        "    if 'pin' in line:\n"
        "        o.write('pinned\\n'); o.flush()\n"
        "    else:\n"
        f"        o.write('{answer}'); o.flush(); break\n")
    monkeypatch.setattr(sqlite_scan, "READER", str(liar))
    seen = Readers()
    got = store.find_columnar(APP, **TRAIN)
    assert seen.gained("reader_failed") == 1 and seen.used() == 1
    assert same(got, serial(store, **TRAIN))


def test_fallback_series_exist_at_zero_after_a_fan_out(store):
    store.find_columnar(APP, ordered=False)
    counter = default_registry().get("pio_ingest_scan_fallback_total")
    assert counter.contains(reason="snapshot")
    assert counter.contains(reason="reader_failed")


# ---------------------------------------------------------------------------
# the reader module
# ---------------------------------------------------------------------------

def test_the_reader_imports_nothing_heavy():
    """sys.modules of a process that ran the reader's imports: no jax,
    no numpy, nothing of this package."""
    code = (
        "import importlib.util, sys\n"
        f"spec = importlib.util.spec_from_file_location('r', "
        f"{sqlite_scan.READER!r})\n"
        "m = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(m)\n"
        "heavy = sorted(k for k, v in sys.modules.items() if v is not None "
        "and k.split('.')[0] in ('jax', 'jaxlib', 'numpy', "
        "'predictionio_tpu', 'click'))\n"
        "print(heavy)\n")
    out = subprocess.run([sys.executable, "-c", code], check=True,
                         capture_output=True, text=True)
    assert out.stdout.strip() == "[]"


def test_the_reader_ends_when_its_parent_goes_away(store):
    """End of input, at any point of the protocol, ends the process."""
    p = subprocess.Popen(
        [sys.executable, sqlite_scan.READER, store.client.path],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE)
    assert p.stdout.readline() == b"ready\n"
    p.stdin.write(b'{"op": "pin"}\n')
    p.stdin.flush()
    assert p.stdout.readline() == b"pinned\n"
    p.stdin.close()
    assert p.wait(timeout=30) == 0
    p.stdout.close()


def test_engine_reads_through_training_scan_fan_out(tmp_path, small_rule):
    """`data/ingest.training_scan` and `aggregate_properties`, called as
    the DataSources call them, over a configured file-backed store."""
    from predictionio_tpu.data import eventstore
    from predictionio_tpu.data.ingest import clear_scan_cache, training_scan
    from predictionio_tpu.storage import App, Storage

    Storage.configure({
        "sources": {"DB": {"TYPE": "sqlite",
                           "PATH": str(tmp_path / "pio.db")}},
        "repositories": {r: {"NAME": "pio", "SOURCE": "DB"}
                         for r in ("METADATA", "EVENTDATA", "MODELDATA")},
    })
    eventstore.clear_cache()
    try:
        app_id = Storage.get_meta_data_apps().insert(App(id=0, name="fan"))
        events = Storage.get_events()
        events.init_channel(app_id)
        events.insert_batch([_event(k) for k in range(100)], app_id)
        clear_scan_cache()
        seen = Readers()
        scan = training_scan("fan", cache=False, **{
            k: v for k, v in TRAIN.items() if k != "ordered"})
        assert seen.used() == 3
        assert scan.table.num_rows == sum(
            1 for k in range(100) if k % 11 and k % 3)
        seen = Readers()
        props = eventstore.EventStoreClient.aggregate_properties(
            "fan", "item")
        assert seen.used() == 3
        assert sorted(props) == [f"i{k}" for k in (0, 1, 2, 3, 4, 5, 6)
                                 if any(j % 11 == 0 and j % 7 == k
                                        for j in range(100))]
    finally:
        Storage.reset()
        eventstore.clear_cache()
    assert _reader_processes() == []
