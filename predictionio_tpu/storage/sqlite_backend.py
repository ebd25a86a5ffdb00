"""Default storage backend on sqlite3.

The rebuild's analog of the reference's JDBC backend
(storage/jdbc/.../JDBC{LEvents,PEvents,Models,Utils}.scala): one sqlite file
holds the event tables (one per app/channel namespace, mirroring
JDBCUtils.eventTableName:108 `pio_event_<app>[_<ch>]`), the metadata tables,
and the model blob table. All SQL uses bound parameters (the reference's
string-concatenated filters, JDBCPEvents.scala:54-63, are deliberately not
reproduced). Connections are per-thread; WAL mode allows the event server's
thread pool to read during writes.
"""

from __future__ import annotations

import datetime as _dt
import itertools
import json
import sqlite3
import threading
from pathlib import Path
from typing import Iterator, List, Optional, Sequence, Tuple

from predictionio_tpu.data.datamap import DataMap
from predictionio_tpu.data.event import UTC, Event, millis as _to_ms
from predictionio_tpu.storage import base, sqlite_scan
from predictionio_tpu.storage.base import (
    AccessKey, App, Channel, EngineInstance, EvaluationInstance, Model,
    Release, StorageError, UNFILTERED, generate_id,
)


def _from_ms(ms: int, tz_offset_min: Optional[int] = None) -> _dt.datetime:
    tz = (UTC if not tz_offset_min
          else _dt.timezone(_dt.timedelta(minutes=tz_offset_min)))
    return _dt.datetime.fromtimestamp(ms / 1000, tz=UTC).astimezone(tz)


def _tz_offset_min(t: _dt.datetime) -> int:
    """Store the UTC offset in minutes so reads restore the original zone
    (JDBCLEvents keeps a zone-ID column for the same purpose)."""
    off = t.utcoffset()
    return 0 if off is None else int(off.total_seconds() // 60)


_CONN_SERIALS = itertools.count(1)


class _Connection(sqlite3.Connection):
    """A connection that knows which one it is: `serial`, one number per
    connection this process ever opened. It tells a re-opened store's
    connection from the closed one in a `change_token` (`id()` of a
    freed connection comes back)."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.serial = next(_CONN_SERIALS)


class SqliteClient:
    """Shared connection manager for one sqlite database file."""

    def __init__(self, path: str = ":memory:"):
        self.path = path
        self._local = threading.local()
        # reentrant: for :memory: the write lock and the shared-connection
        # guard are the SAME lock, and holders of write_lock() call conn()
        self._lock = threading.RLock()
        self._memory_conn: Optional[sqlite3.Connection] = None
        if path != ":memory:":
            Path(path).parent.mkdir(parents=True, exist_ok=True)

    def conn(self) -> sqlite3.Connection:
        # a single shared connection for :memory: (per-thread connections would
        # each see their own empty db); per-thread connections for files
        if self.path == ":memory:":
            with self._lock:
                if self._memory_conn is None:
                    self._memory_conn = sqlite3.connect(
                        ":memory:", check_same_thread=False,
                        factory=_Connection)
                return self._memory_conn
        c = getattr(self._local, "conn", None)
        if c is None:
            c = sqlite3.connect(self.path, factory=_Connection)
            c.execute("PRAGMA journal_mode=WAL")
            c.execute("PRAGMA synchronous=NORMAL")
            self._local.conn = c
        return c

    def change_token(self) -> Optional[tuple]:
        """What the calling thread's connection knows, in O(1), of
        whether the database changed: which connection it is, `PRAGMA
        data_version` (moves when any OTHER connection, of this process
        or another, commits), `total_changes` (this connection's own row
        changes) and `PRAGMA schema_version` (a table dropped and made
        again, which counts no row). Every commit moves one of them and
        none moves back, so two equal tokens mean that nothing was
        committed in between. Only tokens of one connection compare.
        None inside a transaction of this connection's own: a rollback
        takes rows back and no number with them."""
        c = self.conn()
        if c.in_transaction:
            return None
        return (c.serial, sqlite_scan.data_version(c), c.total_changes,
                c.execute("PRAGMA schema_version").fetchone()[0])

    def close(self) -> None:
        if self._memory_conn is not None:
            self._memory_conn.close()
            self._memory_conn = None
        c = getattr(self._local, "conn", None)
        if c is not None:
            c.close()
            self._local.conn = None

    # the :memory: lock also serializes writers on the shared connection
    def write_lock(self):
        return self._lock


# ---------------------------------------------------------------------------
# Events
# ---------------------------------------------------------------------------

_EVENT_COLS = ("id, event, entityType, entityId, targetEntityType, "
               "targetEntityId, properties, eventTime, eventTimeZone, tags, "
               "prId, creationTime, creationTimeZone")


def event_table_name(app_id: int, channel_id: Optional[int]) -> str:
    """JDBCUtils.eventTableName:108 parity: pio_event_<app>[_<channel>]."""
    suffix = f"_{channel_id}" if channel_id is not None else ""
    return f"pio_event_{app_id}{suffix}"


class SqliteEvents(base.EventStore):
    """EventStore over sqlite (JDBCLEvents.scala:37-289 behavioral parity)."""

    def __init__(self, client: SqliteClient):
        self.client = client

    # -- namespace lifecycle ------------------------------------------------
    def init_channel(self, app_id: int, channel_id: Optional[int] = None) -> bool:
        name = event_table_name(app_id, channel_id)
        with self.client.write_lock():
            self.client.conn().execute(f"""
                CREATE TABLE IF NOT EXISTS {name} (
                  id TEXT NOT NULL PRIMARY KEY,
                  event TEXT NOT NULL,
                  entityType TEXT NOT NULL,
                  entityId TEXT NOT NULL,
                  targetEntityType TEXT,
                  targetEntityId TEXT,
                  properties TEXT,
                  eventTime INTEGER NOT NULL,
                  eventTimeZone INTEGER NOT NULL,
                  tags TEXT,
                  prId TEXT,
                  creationTime INTEGER NOT NULL,
                  creationTimeZone INTEGER NOT NULL)""")
            self.client.conn().execute(
                f"CREATE INDEX IF NOT EXISTS {name}_time ON {name} (eventTime)")
            self.client.conn().commit()
        return True

    def remove_channel(self, app_id: int, channel_id: Optional[int] = None) -> bool:
        name = event_table_name(app_id, channel_id)
        with self.client.write_lock():
            self.client.conn().execute(f"DROP TABLE IF EXISTS {name}")
            self.client.conn().commit()
        return True

    def close(self) -> None:
        self.client.close()

    # -- CRUD ---------------------------------------------------------------
    def insert(self, event: Event, app_id: int,
               channel_id: Optional[int] = None) -> str:
        return self.insert_batch([event], app_id, channel_id)[0]

    def insert_batch(self, events: Sequence[Event], app_id: int,
                     channel_id: Optional[int] = None) -> List[str]:
        name = event_table_name(app_id, channel_id)
        rows, ids = [], []
        for e in events:
            eid = e.event_id or generate_id()
            ids.append(eid)
            rows.append((
                eid, e.event, e.entity_type, e.entity_id,
                e.target_entity_type, e.target_entity_id,
                e.properties.to_json() if not e.properties.is_empty else None,
                _to_ms(e.event_time), _tz_offset_min(e.event_time),
                ",".join(e.tags) if e.tags else None,
                e.pr_id, _to_ms(e.creation_time),
                _tz_offset_min(e.creation_time),
            ))
        try:
            with self.client.write_lock():
                self.client.conn().executemany(
                    f"INSERT INTO {name} VALUES (?,?,?,?,?,?,?,?,?,?,?,?,?)", rows)
                self.client.conn().commit()
        except sqlite3.OperationalError as ex:
            raise StorageError(
                f"cannot insert into app {app_id} channel {channel_id}: {ex}. "
                "Was the app initialized (pio app new)?") from ex
        return ids

    def insert_batch_idempotent(self, events: Sequence[Event], app_id: int,
                                channel_id: Optional[int] = None
                                ) -> List[str]:
        """Retry-path insert: INSERT OR IGNORE on the id primary key, so a
        replayed flush skips rows a previous ambiguous attempt committed."""
        name = event_table_name(app_id, channel_id)
        rows, ids = [], []
        for e in events:
            if not e.event_id:
                raise StorageError(
                    "insert_batch_idempotent requires pre-assigned event ids")
            ids.append(e.event_id)
            rows.append((
                e.event_id, e.event, e.entity_type, e.entity_id,
                e.target_entity_type, e.target_entity_id,
                e.properties.to_json() if not e.properties.is_empty else None,
                _to_ms(e.event_time), _tz_offset_min(e.event_time),
                ",".join(e.tags) if e.tags else None,
                e.pr_id, _to_ms(e.creation_time),
                _tz_offset_min(e.creation_time),
            ))
        try:
            with self.client.write_lock():
                self.client.conn().executemany(
                    f"INSERT OR IGNORE INTO {name} "
                    "VALUES (?,?,?,?,?,?,?,?,?,?,?,?,?)", rows)
                self.client.conn().commit()
        except sqlite3.OperationalError as ex:
            raise StorageError(
                f"cannot insert into app {app_id} channel {channel_id}: {ex}. "
                "Was the app initialized (pio app new)?") from ex
        return ids

    def compact(self, app_id: int, channel_id: Optional[int] = None,
                ttl_days: Optional[float] = None) -> dict:
        """Retention sweep as one bounded DELETE (rows are already
        physically folded in a row store; there is nothing to merge)."""
        removed = 0
        if ttl_days is not None:
            name = event_table_name(app_id, channel_id)
            cutoff = _to_ms(_dt.datetime.now(tz=UTC)
                            - _dt.timedelta(days=ttl_days))
            try:
                with self.client.write_lock():
                    cur = self.client.conn().execute(
                        f"DELETE FROM {name} WHERE eventTime < ?", (cutoff,))
                    self.client.conn().commit()
            except sqlite3.OperationalError as ex:
                raise StorageError(str(ex)) from ex
            removed = cur.rowcount
        return {"removed_rows": removed}

    def get(self, event_id: str, app_id: int,
            channel_id: Optional[int] = None) -> Optional[Event]:
        name = event_table_name(app_id, channel_id)
        try:
            cur = self.client.conn().execute(
                f"SELECT {_EVENT_COLS} FROM {name} WHERE id = ?", (event_id,))
        except sqlite3.OperationalError as ex:
            raise StorageError(str(ex)) from ex
        row = cur.fetchone()
        return _row_to_event(row) if row else None

    def delete(self, event_id: str, app_id: int,
               channel_id: Optional[int] = None) -> bool:
        name = event_table_name(app_id, channel_id)
        with self.client.write_lock():
            cur = self.client.conn().execute(
                f"DELETE FROM {name} WHERE id = ?", (event_id,))
            self.client.conn().commit()
        return cur.rowcount > 0

    # -- queries ------------------------------------------------------------
    def _find_sql(
        self,
        select_cols: str,
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
        ordered: bool = True,
        shard: Optional[Tuple] = None,
    ):
        """(sql, params) for a filtered event scan — shared by the row
        path (`find`) and the columnar training path (`find_columnar`).

        ``shard=(index, count[, snapshot])`` restricts the scan to one of
        `count` near-equal rowid ranges — the partitioned training read
        (JDBCPEvents.scala:89-101's numeric range partitions): each
        process of a multi-host run scans only its slice, so no process
        ever pulls the full event set. Multi-process readers must share
        one `read_snapshot()` window (third element) — independently
        computed bounds skew under concurrent ingest and the partitions
        gap/overlap."""
        name = event_table_name(app_id, channel_id)
        where, params = ["1=1"], []
        if shard is not None:
            if len(shard) > 2 and shard[2] is not None:
                # pre-agreed snapshot window: multi-process readers MUST
                # share one (read_snapshot + a collective broadcast) or
                # concurrent ingest skews each process's bounds and the
                # partitions gap/overlap
                lo_all, hi_all = shard[2]
            else:
                lo_all, hi_all = self.read_snapshot(app_id, channel_id)
            lo, hi = base.shard_window(lo_all, hi_all, shard)
            where.append("rowid >= ? AND rowid < ?")
            params.extend([lo, hi])
        if start_time is not None:
            where.append("eventTime >= ?")
            params.append(_to_ms(start_time))
        if until_time is not None:
            where.append("eventTime < ?")
            params.append(_to_ms(until_time))
        if entity_type is not None:
            where.append("entityType = ?")
            params.append(entity_type)
        if entity_id is not None:
            where.append("entityId = ?")
            params.append(entity_id)
        if event_names:
            qs = ",".join("?" * len(event_names))
            where.append(f"event IN ({qs})")
            params.extend(event_names)
        if target_entity_type is not UNFILTERED:
            if target_entity_type is None:
                where.append("targetEntityType IS NULL")
            else:
                where.append("targetEntityType = ?")
                params.append(target_entity_type)
        if target_entity_id is not UNFILTERED:
            if target_entity_id is None:
                where.append("targetEntityId IS NULL")
            else:
                where.append("targetEntityId = ?")
                params.append(target_entity_id)
        sql = f"SELECT {select_cols} FROM {name} WHERE {' AND '.join(where)}"
        if ordered:
            sql += f" ORDER BY eventTime {'DESC' if reversed_order else 'ASC'}"
        if limit is not None and limit >= 0:
            sql += " LIMIT ?"
            params.append(limit)
        return sql, params

    def read_snapshot(self, app_id: int,
                      channel_id: Optional[int] = None) -> Tuple[int, int]:
        """Stable row window [lo, hi) for partitioned reads: capture ONCE
        (on one process), broadcast, and pass as shard=(idx, count,
        snapshot) so every reader partitions the SAME set even while an
        event server keeps ingesting (rows landing after the snapshot are
        simply not part of this training read)."""
        name = event_table_name(app_id, channel_id)
        try:
            return sqlite_scan.rowid_window(self.client.conn(), name)
        except sqlite3.OperationalError as ex:
            raise StorageError(
                f"cannot read app {app_id} channel {channel_id}: {ex}"
            ) from ex

    def change_token(self, app_id: int,
                     channel_id: Optional[int] = None) -> Optional[tuple]:
        """`SqliteClient.change_token`: no statement reads the table. The
        numbers are the database's, so a write to any table of the file
        moves them: a miss too many, never a hit too many."""
        return self.client.change_token()

    def snapshot_digest(self, app_id: int,
                        channel_id: Optional[int] = None) -> str:
        """(min rowid, max rowid, count, max creationTime): appends grow
        the window, deletes shrink the count, and the creationTime
        component covers delete-then-insert pairs — a plain rowid table
        reuses MAX(rowid)+1 after the newest row is deleted, so window +
        count alone could alias two different states; the replacement
        row's later creationTime still changes the digest. One statement
        over the whole table (`creationTime` has no index): what the
        deploy orchestrator compares from tick to tick and across
        processes; a training read keys its cache with `change_token`."""
        name = event_table_name(app_id, channel_id)
        try:
            row = self.client.conn().execute(
                f"SELECT MIN(rowid), MAX(rowid), COUNT(*), "
                f"MAX(creationTime) FROM {name}"
            ).fetchone()
        except sqlite3.OperationalError as ex:
            raise StorageError(
                f"cannot read app {app_id} channel {channel_id}: {ex}"
            ) from ex
        return f"rowid:{row[0]}:{row[1]}:{row[2]}:{row[3]}"

    def find(self, app_id: int, channel_id: Optional[int] = None,
             **filters) -> Iterator[Event]:
        sql, params = self._find_sql(_EVENT_COLS, app_id, channel_id,
                                     **filters)
        try:
            cur = self.client.conn().execute(sql, params)
        except sqlite3.OperationalError as ex:
            raise StorageError(
                f"cannot read app {app_id} channel {channel_id}: {ex}") from ex
        for row in cur:
            yield _row_to_event(row)

    def find_columnar(self, app_id: int, channel_id: Optional[int] = None,
                      ordered: bool = True, columns=None, **filters):
        """Direct columnar scan -> pyarrow.Table, skipping per-row Event/
        DataMap materialization (the JDBCPEvents.scala:35 training-read
        analog: SQL straight into the columnar buffers that feed device
        arrays). ``ordered=False`` (training reads) additionally drops
        the global time sort; ``columns`` projects the SELECT to the
        EVENT_SCHEMA subset a training read actually consumes (fetching
        9 columns to use 4 dominates the scan otherwise).
        ``reversed_order``/``limit`` semantics require the sort, so they
        force it back on. An unordered read of a whole large table is
        cut into rowid windows read by as many short-lived processes
        (`_fanned_out`, storage/sqlite_scan.py): same rows, same order."""
        from predictionio_tpu.data.columnar import (
            SQL_COLUMN_OF, projected_schema, rows_to_event_table,
        )

        if filters.get("reversed_order") or filters.get("limit") is not None:
            ordered = True
        schema = projected_schema(columns)
        cols = ", ".join(SQL_COLUMN_OF[n] for n in schema.names)
        try:
            if not ordered:
                table = self._fanned_out(cols, schema, app_id, channel_id,
                                         filters)
                if table is not None:
                    return table
            sql, params = self._find_sql(cols, app_id, channel_id,
                                         ordered=ordered, **filters)
            rows = self.client.conn().execute(sql, params).fetchall()
        except sqlite3.OperationalError as ex:
            raise StorageError(
                f"cannot read app {app_id} channel {channel_id}: {ex}") from ex
        sqlite_scan.count_readers(1)
        return rows_to_event_table(rows, schema.names)

    def _fanned_out(self, cols: str, schema, app_id: int,
                    channel_id: Optional[int], filters: dict):
        """The unordered, unlimited read through `sqlite_scan.fan_out`, or
        None where the serial scan answers: a store in memory, a filter
        by value that makes the read a handful of rows (the serving-time
        reads by entity, fold-in's by time, which may also take the
        eventTime index that a rowid range would fight), this
        connection inside a transaction of its own (its uncommitted rows
        are not in any reader's snapshot), a window too small to be
        worth a process. A ``shard=`` read splits its shard's window."""
        if (self.client.path == ":memory:"
                or filters.get("entity_id") is not None
                or filters.get("target_entity_id", UNFILTERED)
                is not UNFILTERED
                or filters.get("start_time") is not None
                or filters.get("until_time") is not None):
            return None
        conn = self.client.conn()
        if conn.in_transaction:
            return None
        rest = {k: v for k, v in filters.items() if k != "shard"}
        shard = filters.get("shard")
        agreed = shard[2] if shard is not None and len(shard) > 2 else None
        name = event_table_name(app_id, channel_id)

        def window():
            whole = agreed or sqlite_scan.rowid_window(conn, name)
            if shard is None:
                return whole
            return base.shard_window(*whole, shard)

        return sqlite_scan.fan_out(
            conn, self.client.path, window,
            lambda sub: self._find_sql(cols, app_id, channel_id,
                                       ordered=False, shard=sub, **rest),
            schema)


def _row_to_event(row) -> Event:
    (eid, event, etype, eidv, ttype, tid, props, etime, etz, tags, prid,
     ctime, ctz) = row
    return Event(
        event_id=eid,
        event=event,
        entity_type=etype,
        entity_id=eidv,
        target_entity_type=ttype,
        target_entity_id=tid,
        properties=DataMap(json.loads(props)) if props else DataMap(),
        event_time=_from_ms(etime, etz),
        tags=tuple(tags.split(",")) if tags else (),
        pr_id=prid,
        creation_time=_from_ms(ctime, ctz),
    )


# ---------------------------------------------------------------------------
# Metadata stores
# ---------------------------------------------------------------------------

class _MetaBase:
    def __init__(self, client: SqliteClient):
        self.client = client
        with client.write_lock():
            self._ddl(client.conn())
            client.conn().commit()

    def _ddl(self, conn):
        raise NotImplementedError

    def _exec(self, sql, params=()):
        with self.client.write_lock():
            cur = self.client.conn().execute(sql, params)
            self.client.conn().commit()
            return cur

    def _query(self, sql, params=()):
        return self.client.conn().execute(sql, params)


class SqliteApps(_MetaBase, base.Apps):
    def _ddl(self, conn):
        conn.execute("""CREATE TABLE IF NOT EXISTS pio_apps (
            id INTEGER PRIMARY KEY AUTOINCREMENT,
            name TEXT NOT NULL UNIQUE,
            description TEXT)""")

    def insert(self, app: App) -> Optional[int]:
        try:
            if app.id == 0:
                cur = self._exec(
                    "INSERT INTO pio_apps (name, description) VALUES (?,?)",
                    (app.name, app.description))
            else:
                cur = self._exec(
                    "INSERT INTO pio_apps (id, name, description) VALUES (?,?,?)",
                    (app.id, app.name, app.description))
        except sqlite3.IntegrityError:
            return None
        return cur.lastrowid if app.id == 0 else app.id

    def get(self, app_id: int) -> Optional[App]:
        row = self._query("SELECT id, name, description FROM pio_apps WHERE id=?",
                          (app_id,)).fetchone()
        return App(*row) if row else None

    def get_by_name(self, name: str) -> Optional[App]:
        row = self._query("SELECT id, name, description FROM pio_apps WHERE name=?",
                          (name,)).fetchone()
        return App(*row) if row else None

    def get_all(self) -> List[App]:
        return [App(*r) for r in
                self._query("SELECT id, name, description FROM pio_apps ORDER BY id")]

    def update(self, app: App) -> None:
        self._exec("UPDATE pio_apps SET name=?, description=? WHERE id=?",
                   (app.name, app.description, app.id))

    def delete(self, app_id: int) -> None:
        self._exec("DELETE FROM pio_apps WHERE id=?", (app_id,))


class SqliteAccessKeys(_MetaBase, base.AccessKeys):
    def _ddl(self, conn):
        conn.execute("""CREATE TABLE IF NOT EXISTS pio_accesskeys (
            accesskey TEXT PRIMARY KEY,
            appid INTEGER NOT NULL,
            events TEXT)""")

    def insert(self, k: AccessKey) -> Optional[str]:
        key = k.key or self.generate_key()
        try:
            self._exec("INSERT INTO pio_accesskeys VALUES (?,?,?)",
                       (key, k.appid, ",".join(k.events)))
        except sqlite3.IntegrityError:
            return None
        return key

    def get(self, key: str) -> Optional[AccessKey]:
        row = self._query(
            "SELECT accesskey, appid, events FROM pio_accesskeys WHERE accesskey=?",
            (key,)).fetchone()
        return _row_to_accesskey(row) if row else None

    def get_all(self) -> List[AccessKey]:
        return [_row_to_accesskey(r) for r in
                self._query("SELECT accesskey, appid, events FROM pio_accesskeys")]

    def get_by_appid(self, appid: int) -> List[AccessKey]:
        return [_row_to_accesskey(r) for r in self._query(
            "SELECT accesskey, appid, events FROM pio_accesskeys WHERE appid=?",
            (appid,))]

    def update(self, k: AccessKey) -> None:
        self._exec("UPDATE pio_accesskeys SET appid=?, events=? WHERE accesskey=?",
                   (k.appid, ",".join(k.events), k.key))

    def delete(self, key: str) -> None:
        self._exec("DELETE FROM pio_accesskeys WHERE accesskey=?", (key,))


def _row_to_accesskey(row) -> AccessKey:
    key, appid, events = row
    return AccessKey(key=key, appid=appid,
                     events=tuple(e for e in (events or "").split(",") if e))


class SqliteChannels(_MetaBase, base.Channels):
    def _ddl(self, conn):
        conn.execute("""CREATE TABLE IF NOT EXISTS pio_channels (
            id INTEGER PRIMARY KEY AUTOINCREMENT,
            name TEXT NOT NULL,
            appid INTEGER NOT NULL,
            UNIQUE (name, appid))""")

    def insert(self, channel: Channel) -> Optional[int]:
        try:
            if channel.id == 0:
                cur = self._exec("INSERT INTO pio_channels (name, appid) VALUES (?,?)",
                                 (channel.name, channel.appid))
                return cur.lastrowid
            self._exec("INSERT INTO pio_channels (id, name, appid) VALUES (?,?,?)",
                       (channel.id, channel.name, channel.appid))
            return channel.id
        except sqlite3.IntegrityError:
            return None

    def get(self, channel_id: int) -> Optional[Channel]:
        row = self._query("SELECT id, name, appid FROM pio_channels WHERE id=?",
                          (channel_id,)).fetchone()
        return Channel(*row) if row else None

    def get_by_appid(self, appid: int) -> List[Channel]:
        return [Channel(*r) for r in self._query(
            "SELECT id, name, appid FROM pio_channels WHERE appid=? ORDER BY id",
            (appid,))]

    def delete(self, channel_id: int) -> None:
        self._exec("DELETE FROM pio_channels WHERE id=?", (channel_id,))


_EI_COLS = ("id, status, startTime, endTime, engineId, engineVersion, "
            "engineVariant, engineFactory, batch, env, runtimeConf, "
            "dataSourceParams, preparatorParams, algorithmsParams, servingParams")


class SqliteEngineInstances(_MetaBase, base.EngineInstances):
    def _ddl(self, conn):
        conn.execute("""CREATE TABLE IF NOT EXISTS pio_engineinstances (
            id TEXT PRIMARY KEY, status TEXT, startTime INTEGER, endTime INTEGER,
            engineId TEXT, engineVersion TEXT, engineVariant TEXT,
            engineFactory TEXT, batch TEXT, env TEXT, runtimeConf TEXT,
            dataSourceParams TEXT, preparatorParams TEXT,
            algorithmsParams TEXT, servingParams TEXT)""")

    def insert(self, i: EngineInstance) -> str:
        iid = i.id or generate_id()
        i.id = iid
        self._exec(
            f"INSERT INTO pio_engineinstances ({_EI_COLS}) "
            "VALUES (?,?,?,?,?,?,?,?,?,?,?,?,?,?,?)",
            (iid, i.status, _to_ms(i.start_time), _to_ms(i.end_time),
             i.engine_id, i.engine_version, i.engine_variant, i.engine_factory,
             i.batch, json.dumps(i.env), json.dumps(i.runtime_conf),
             i.data_source_params, i.preparator_params, i.algorithms_params,
             i.serving_params))
        return iid

    def get(self, instance_id: str) -> Optional[EngineInstance]:
        row = self._query(
            f"SELECT {_EI_COLS} FROM pio_engineinstances WHERE id=?",
            (instance_id,)).fetchone()
        return _row_to_ei(row) if row else None

    def get_all(self) -> List[EngineInstance]:
        return [_row_to_ei(r) for r in
                self._query(f"SELECT {_EI_COLS} FROM pio_engineinstances")]

    def get_completed(self, engine_id, engine_version, engine_variant):
        return [_row_to_ei(r) for r in self._query(
            f"SELECT {_EI_COLS} FROM pio_engineinstances "
            "WHERE status='COMPLETED' AND engineId=? AND engineVersion=? "
            "AND engineVariant=? ORDER BY startTime DESC",
            (engine_id, engine_version, engine_variant))]

    def update(self, i: EngineInstance) -> None:
        self._exec(
            "UPDATE pio_engineinstances SET status=?, startTime=?, endTime=?, "
            "engineId=?, engineVersion=?, engineVariant=?, engineFactory=?, "
            "batch=?, env=?, runtimeConf=?, dataSourceParams=?, "
            "preparatorParams=?, algorithmsParams=?, servingParams=? WHERE id=?",
            (i.status, _to_ms(i.start_time), _to_ms(i.end_time), i.engine_id,
             i.engine_version, i.engine_variant, i.engine_factory, i.batch,
             json.dumps(i.env), json.dumps(i.runtime_conf),
             i.data_source_params, i.preparator_params, i.algorithms_params,
             i.serving_params, i.id))

    def delete(self, instance_id: str) -> None:
        self._exec("DELETE FROM pio_engineinstances WHERE id=?", (instance_id,))


def _row_to_ei(row) -> EngineInstance:
    return EngineInstance(
        id=row[0], status=row[1], start_time=_from_ms(row[2]),
        end_time=_from_ms(row[3]), engine_id=row[4], engine_version=row[5],
        engine_variant=row[6], engine_factory=row[7], batch=row[8],
        env=json.loads(row[9] or "{}"), runtime_conf=json.loads(row[10] or "{}"),
        data_source_params=row[11], preparator_params=row[12],
        algorithms_params=row[13], serving_params=row[14])


_EVI_COLS = ("id, status, startTime, endTime, evaluationClass, "
             "engineParamsGeneratorClass, batch, env, runtimeConf, "
             "evaluatorResults, evaluatorResultsHTML, evaluatorResultsJSON")


class SqliteEvaluationInstances(_MetaBase, base.EvaluationInstances):
    def _ddl(self, conn):
        conn.execute("""CREATE TABLE IF NOT EXISTS pio_evaluationinstances (
            id TEXT PRIMARY KEY, status TEXT, startTime INTEGER, endTime INTEGER,
            evaluationClass TEXT, engineParamsGeneratorClass TEXT, batch TEXT,
            env TEXT, runtimeConf TEXT, evaluatorResults TEXT,
            evaluatorResultsHTML TEXT, evaluatorResultsJSON TEXT)""")

    def insert(self, i: EvaluationInstance) -> str:
        iid = i.id or generate_id()
        i.id = iid
        self._exec(
            f"INSERT INTO pio_evaluationinstances ({_EVI_COLS}) "
            "VALUES (?,?,?,?,?,?,?,?,?,?,?,?)",
            (iid, i.status, _to_ms(i.start_time), _to_ms(i.end_time),
             i.evaluation_class, i.engine_params_generator_class, i.batch,
             json.dumps(i.env), json.dumps(i.runtime_conf),
             i.evaluator_results, i.evaluator_results_html,
             i.evaluator_results_json))
        return iid

    def get(self, instance_id: str) -> Optional[EvaluationInstance]:
        row = self._query(
            f"SELECT {_EVI_COLS} FROM pio_evaluationinstances WHERE id=?",
            (instance_id,)).fetchone()
        return _row_to_evi(row) if row else None

    def get_all(self) -> List[EvaluationInstance]:
        return [_row_to_evi(r) for r in
                self._query(f"SELECT {_EVI_COLS} FROM pio_evaluationinstances")]

    def get_completed(self) -> List[EvaluationInstance]:
        return [_row_to_evi(r) for r in self._query(
            f"SELECT {_EVI_COLS} FROM pio_evaluationinstances "
            "WHERE status='EVALCOMPLETED' ORDER BY startTime DESC")]

    def update(self, i: EvaluationInstance) -> None:
        self._exec(
            "UPDATE pio_evaluationinstances SET status=?, startTime=?, "
            "endTime=?, evaluationClass=?, engineParamsGeneratorClass=?, "
            "batch=?, env=?, runtimeConf=?, evaluatorResults=?, "
            "evaluatorResultsHTML=?, evaluatorResultsJSON=? WHERE id=?",
            (i.status, _to_ms(i.start_time), _to_ms(i.end_time),
             i.evaluation_class, i.engine_params_generator_class, i.batch,
             json.dumps(i.env), json.dumps(i.runtime_conf),
             i.evaluator_results, i.evaluator_results_html,
             i.evaluator_results_json, i.id))

    def delete(self, instance_id: str) -> None:
        self._exec("DELETE FROM pio_evaluationinstances WHERE id=?",
                   (instance_id,))


def _row_to_evi(row) -> EvaluationInstance:
    return EvaluationInstance(
        id=row[0], status=row[1], start_time=_from_ms(row[2]),
        end_time=_from_ms(row[3]), evaluation_class=row[4],
        engine_params_generator_class=row[5], batch=row[6],
        env=json.loads(row[7] or "{}"), runtime_conf=json.loads(row[8] or "{}"),
        evaluator_results=row[9], evaluator_results_html=row[10],
        evaluator_results_json=row[11])


_REL_COLS = ("id, version, engineId, engineVersion, engineVariant, "
             "instanceId, paramsDigest, modelDigest, modelSizeBytes, "
             "status, createdTime, trainSeconds, batch, history")


class SqliteReleases(_MetaBase, base.Releases):
    """Release manifests (deploy/ subsystem) in sqlite."""

    def _ddl(self, conn):
        conn.execute("""CREATE TABLE IF NOT EXISTS pio_releases (
            id TEXT PRIMARY KEY, version INTEGER NOT NULL,
            engineId TEXT, engineVersion TEXT, engineVariant TEXT,
            instanceId TEXT, paramsDigest TEXT, modelDigest TEXT,
            modelSizeBytes INTEGER, status TEXT, createdTime INTEGER,
            trainSeconds REAL, batch TEXT, history TEXT)""")
        # two trains of the same variant must never share a version —
        # the constraint catches races the in-process write lock cannot
        # (concurrent `pio train` PROCESSES on one sqlite file)
        conn.execute(
            "CREATE UNIQUE INDEX IF NOT EXISTS pio_releases_variant_version "
            "ON pio_releases (engineId, engineVersion, engineVariant, "
            "version)")

    def insert(self, r: Release) -> str:
        rid = r.id or generate_id()
        r.id = rid
        for _attempt in range(8):
            with self.client.write_lock():
                conn = self.client.conn()
                row = conn.execute(
                    "SELECT COALESCE(MAX(version), 0) FROM pio_releases "
                    "WHERE engineId=? AND engineVersion=? AND "
                    "engineVariant=?",
                    (r.engine_id, r.engine_version,
                     r.engine_variant)).fetchone()
                r.version = int(row[0]) + 1
                try:
                    conn.execute(
                        f"INSERT INTO pio_releases ({_REL_COLS}) "
                        "VALUES (?,?,?,?,?,?,?,?,?,?,?,?,?,?)",
                        (rid, r.version, r.engine_id, r.engine_version,
                         r.engine_variant, r.instance_id, r.params_digest,
                         r.model_digest, r.model_size_bytes, r.status,
                         _to_ms(r.created_time), r.train_seconds, r.batch,
                         json.dumps(r.history)))
                    conn.commit()
                    return rid
                except sqlite3.IntegrityError:
                    # another PROCESS claimed this version between the
                    # MAX read and the insert; re-read and retry
                    conn.rollback()
        raise StorageError(
            f"could not claim a release version for {r.engine_id}/"
            f"{r.engine_variant} after 8 attempts")

    def get(self, release_id: str) -> Optional[Release]:
        row = self._query(
            f"SELECT {_REL_COLS} FROM pio_releases WHERE id=?",
            (release_id,)).fetchone()
        return _row_to_release(row) if row else None

    def get_all(self) -> List[Release]:
        return [_row_to_release(r) for r in self._query(
            f"SELECT {_REL_COLS} FROM pio_releases "
            "ORDER BY engineId, engineVariant, version DESC")]

    def get_for_variant(self, engine_id, engine_version, engine_variant):
        return [_row_to_release(r) for r in self._query(
            f"SELECT {_REL_COLS} FROM pio_releases WHERE engineId=? AND "
            "engineVersion=? AND engineVariant=? ORDER BY version DESC",
            (engine_id, engine_version, engine_variant))]

    def update(self, r: Release) -> None:
        self._exec(
            "UPDATE pio_releases SET version=?, engineId=?, engineVersion=?, "
            "engineVariant=?, instanceId=?, paramsDigest=?, modelDigest=?, "
            "modelSizeBytes=?, status=?, createdTime=?, trainSeconds=?, "
            "batch=?, history=? WHERE id=?",
            (r.version, r.engine_id, r.engine_version, r.engine_variant,
             r.instance_id, r.params_digest, r.model_digest,
             r.model_size_bytes, r.status, _to_ms(r.created_time),
             r.train_seconds, r.batch, json.dumps(r.history), r.id))

    def delete(self, release_id: str) -> None:
        self._exec("DELETE FROM pio_releases WHERE id=?", (release_id,))


def _row_to_release(row) -> Release:
    return Release(
        id=row[0], version=row[1], engine_id=row[2], engine_version=row[3],
        engine_variant=row[4], instance_id=row[5], params_digest=row[6],
        model_digest=row[7], model_size_bytes=row[8], status=row[9],
        created_time=_from_ms(row[10]), train_seconds=row[11],
        batch=row[12], history=json.loads(row[13] or "[]"))


class SqliteModels(_MetaBase, base.Models):
    """Model blobs in sqlite (JDBCModels.scala:28-55 parity)."""

    def _ddl(self, conn):
        conn.execute("""CREATE TABLE IF NOT EXISTS pio_models (
            id TEXT PRIMARY KEY, models BLOB NOT NULL)""")

    def insert(self, model: Model) -> None:
        self._exec("INSERT OR REPLACE INTO pio_models VALUES (?,?)",
                   (model.id, model.models))

    def get(self, model_id: str) -> Optional[Model]:
        row = self._query("SELECT id, models FROM pio_models WHERE id=?",
                          (model_id,)).fetchone()
        return Model(id=row[0], models=row[1]) if row else None

    def delete(self, model_id: str) -> None:
        self._exec("DELETE FROM pio_models WHERE id=?", (model_id,))
