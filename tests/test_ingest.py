"""Columnar training-ingest pipeline: vectorized fold parity, interning,
scan cache, and per-engine columnar-vs-per-event equality.

The contract under test: every result the columnar path (data/ingest +
data/columnar.aggregate_properties_table) produces must be IDENTICAL to
what the row-at-a-time reference folds (data/aggregator.py and the
engines' old per-Event loops) produce on the same store — the perf PR
must be a pure representation change.
"""

import datetime as dt
import random
import subprocess
import sys
import threading

import numpy as np
import pytest

from predictionio_tpu.data import DataMap, Event
from predictionio_tpu.data.aggregator import aggregate_properties
from predictionio_tpu.data.columnar import (
    aggregate_properties_table, events_to_table,
)
from predictionio_tpu.storage import App, Storage

UTC = dt.timezone.utc


def ms(t: int) -> dt.datetime:
    return dt.datetime.fromtimestamp(t / 1000, tz=UTC)


# ---------------------------------------------------------------------------
# Property-based parity: columnar fold == per-event fold
# ---------------------------------------------------------------------------

def _random_special_events(rng: random.Random, n_entities: int, n_events: int):
    """Randomized $set/$unset/$delete interleavings with distinct
    timestamps (tie order across backends is unspecified either way)."""
    keys = ["a", "b", "c", "d", "e"]
    times = rng.sample(range(1, n_events * 50), n_events)
    events = []
    for t in times:
        eid = f"e{rng.randrange(n_entities)}"
        op = rng.choices(("$set", "$unset", "$delete"),
                         weights=(6, 2, 1))[0]
        if op == "$set":
            props = {k: rng.choice([rng.randrange(100), "s" + str(t),
                                    [1, t], {"n": t}, None])
                     for k in rng.sample(keys, rng.randrange(0, 4))}
        elif op == "$unset":
            props = {k: None for k in rng.sample(keys, rng.randrange(1, 3))}
        else:
            props = {}
        events.append(Event(event=op, entity_type="user", entity_id=eid,
                            properties=DataMap(props), event_time=ms(t)))
    return events


@pytest.mark.parametrize("seed", range(8))
def test_columnar_fold_matches_per_event_fold(seed):
    rng = random.Random(seed)
    events = _random_special_events(rng, n_entities=7, n_events=120)
    # shuffle so neither path sees pre-sorted input
    rng.shuffle(events)
    ref = aggregate_properties(events)
    col = aggregate_properties_table(events_to_table(events))
    assert set(ref) == set(col)
    for eid in ref:
        assert ref[eid] == col[eid], eid          # fields AND times


def test_columnar_fold_required_filter():
    events = [
        Event(event="$set", entity_type="user", entity_id="u1",
              properties=DataMap({"a": 1, "b": 2}), event_time=ms(1)),
        Event(event="$set", entity_type="user", entity_id="u2",
              properties=DataMap({"a": 1}), event_time=ms(2)),
    ]
    out = aggregate_properties_table(events_to_table(events),
                                     required=["a", "b"])
    assert set(out) == {"u1"}


def test_columnar_fold_ignores_non_special_rows():
    events = [
        Event(event="$set", entity_type="user", entity_id="u1",
              properties=DataMap({"a": 1}), event_time=ms(1)),
        Event(event="view", entity_type="user", entity_id="u1",
              target_entity_type="item", target_entity_id="i1",
              event_time=ms(99)),
    ]
    out = aggregate_properties_table(events_to_table(events))
    assert out["u1"].fields == {"a": 1}
    assert out["u1"].last_updated == ms(1)        # view never advances it


def test_columnar_fold_empty_table():
    assert aggregate_properties_table(events_to_table([])) == {}


# ---------------------------------------------------------------------------
# Vectorized interning / assembly helpers
# ---------------------------------------------------------------------------

def test_batch_lookup_matches_vocab_index():
    from predictionio_tpu.data.bimap import batch_lookup, vocab_index

    vocab = np.asarray(sorted({"a", "bb", "c", "zz"}), dtype=object)
    probes = ["a", "zz", "nope", "bb", "", "c"]
    got = batch_lookup(vocab, probes)
    want = [vocab_index(vocab, p) for p in probes]
    assert [int(g) if g >= 0 else None for g in got] == \
        [w if w is not None else None for w in want]
    assert batch_lookup(np.asarray([], dtype=object), probes).tolist() == \
        [-1] * len(probes)
    assert batch_lookup(vocab, []).tolist() == []


def test_pair_counts_matches_dict_fold():
    rng = random.Random(3)
    users = [f"u{rng.randrange(6)}" for _ in range(200)]
    items = [f"i{rng.randrange(5)}" for _ in range(200)]
    w = [rng.choice([1.0, 2.0]) for _ in range(200)]
    ref = {}
    for u, i, x in zip(users, items, w):
        ref[(u, i)] = ref.get((u, i), 0.0) + x
    from predictionio_tpu.data.ingest import pair_counts

    uu, ii, ss = pair_counts(np.asarray(users, object),
                             np.asarray(items, object),
                             np.asarray(w, np.float32))
    got = {(u, i): float(s) for u, i, s in zip(uu, ii, ss)}
    assert got == pytest.approx(ref)


def test_latest_per_pair_matches_strict_greater_fold():
    rng = random.Random(4)
    n = 300
    users = [f"u{rng.randrange(5)}" for _ in range(n)]
    items = [f"i{rng.randrange(4)}" for _ in range(n)]
    times = [rng.randrange(20) for _ in range(n)]   # many ties on purpose
    vals = [float(k) for k in range(n)]
    latest = {}
    for u, i, t, v in zip(users, items, times, vals):
        key = (u, i)
        if key not in latest or t > latest[key][0]:
            latest[key] = (t, v)
    from predictionio_tpu.data.ingest import latest_per_pair

    uu, ii, vv = latest_per_pair(
        np.asarray(users, object), np.asarray(items, object),
        np.asarray(times, np.int64), np.asarray(vals, np.float32))
    got = {(u, i): float(v) for u, i, v in zip(uu, ii, vv)}
    assert got == {k: v for k, (_, v) in latest.items()}


def test_sessions_by_entity_matches_dict_fold():
    rng = random.Random(5)
    n = 150
    users = [f"u{rng.randrange(8)}" for _ in range(n)]
    items = [f"i{k}" for k in range(n)]
    times = rng.sample(range(10_000), n)
    by_user = {}
    for u, i, t in zip(users, items, times):
        by_user.setdefault(u, []).append((t, i))
    ref = []
    for u in sorted(by_user):
        pairs = sorted(by_user[u])
        ref.append([i for _, i in pairs])
    from predictionio_tpu.data.ingest import sessions_by_entity

    got = sessions_by_entity(np.asarray(users, object),
                             np.asarray(items, object),
                             np.asarray(times, np.int64))
    assert got == ref


def test_entity_map_from_columnar():
    from predictionio_tpu.data.entity_map import EntityMap

    ids = ["z", "a", "m"]
    payloads = [1, 2, 3]
    em = EntityMap.from_columnar(ids, payloads)
    ref = EntityMap(dict(zip(ids, payloads)))
    assert em.id_map == ref.id_map
    assert dict(em.items()) == dict(ref.items())
    assert em.entity_int_id("a") == 0 and em.entity_id_of(2) == "z"


# ---------------------------------------------------------------------------
# training_scan: store fixture, cache behavior
# ---------------------------------------------------------------------------

@pytest.fixture()
def backend(tmp_path):
    Storage.configure({
        "sources": {"DB": {"TYPE": "sqlite", "PATH": str(tmp_path / "t.db")}},
        "repositories": {
            "METADATA": {"NAME": "pio", "SOURCE": "DB"},
            "EVENTDATA": {"NAME": "pio", "SOURCE": "DB"},
            "MODELDATA": {"NAME": "pio", "SOURCE": "DB"},
        },
    })
    from predictionio_tpu.data.eventstore import clear_cache
    clear_cache()
    yield Storage
    Storage.reset()
    clear_cache()


def _seed_app(backend, name, n_users=6, n_items=5):
    app_id = backend.get_meta_data_apps().insert(App(id=0, name=name))
    store = backend.get_events()
    store.init_channel(app_id)
    rng = random.Random(11)
    events = []
    t = 0
    for u in range(n_users):
        events.append(Event(event="$set", entity_type="user",
                            entity_id=f"u{u}", event_time=ms(t := t + 1)))
    for i in range(n_items):
        events.append(Event(
            event="$set", entity_type="item", entity_id=f"i{i}",
            properties=DataMap({"categories": ["even" if i % 2 == 0
                                               else "odd"]}),
            event_time=ms(t := t + 1)))
    for _ in range(80):
        ev = rng.choice(["view", "buy", "like", "dislike", "rate",
                         "follow"])
        u = rng.randrange(n_users)
        if ev == "follow":
            events.append(Event(
                event="follow", entity_type="user", entity_id=f"u{u}",
                target_entity_type="user",
                target_entity_id=f"u{rng.randrange(n_users)}",
                event_time=ms(t := t + 1)))
        else:
            props = (DataMap({"rating": float(rng.randrange(1, 6))})
                     if ev == "rate" else DataMap())
            events.append(Event(
                event=ev, entity_type="user", entity_id=f"u{u}",
                target_entity_type="item",
                target_entity_id=f"i{rng.randrange(n_items)}",
                properties=props, event_time=ms(t := t + 1)))
    store.insert_batch(events, app_id)
    return app_id


def test_training_scan_cache_hits_and_invalidates(backend):
    app_id = _seed_app(backend, "ScanApp")
    from predictionio_tpu.data.ingest import training_scan

    s1 = tuple(
        training_scan("ScanApp", entity_type="user", event_names=["view"],
                      target_entity_type="item").table
        .column("event_id").to_pylist())
    s2 = training_scan("ScanApp", entity_type="user", event_names=["view"],
                       target_entity_type="item")
    assert tuple(s2.table.column("event_id").to_pylist()) == s1
    # a write changes the snapshot digest -> rescan sees the new row
    backend.get_events().insert(
        Event(event="view", entity_type="user", entity_id="u0",
              target_entity_type="item", target_entity_id="i0",
              event_time=ms(10_000)), app_id)
    s3 = training_scan("ScanApp", entity_type="user", event_names=["view"],
                       target_entity_type="item")
    assert s3.table.num_rows == len(s1) + 1


def test_training_scan_cache_disabled_by_env(backend, monkeypatch):
    _seed_app(backend, "ScanApp2")
    monkeypatch.setenv("PIO_INGEST_CACHE", "0")
    from predictionio_tpu.data import ingest

    ingest.clear_scan_cache()
    ingest.training_scan("ScanApp2", entity_type="user",
                         event_names=["view"], target_entity_type="item")
    with ingest._scan_lock:
        assert not ingest._scan_cache


def _view(k):
    return Event(event="view", entity_type="user", entity_id=f"w{k}",
                 target_entity_type="item", target_entity_id="i0",
                 event_time=ms(20_000 + k))


def _write_same_connection(backend, app_id, path):
    backend.get_events().insert(_view(1), app_id)
    return 1


def _write_other_connection(backend, app_id, path):
    """Another thread of this process: the client gives it a connection
    of its own."""
    th = threading.Thread(
        target=lambda: backend.get_events().insert(_view(2), app_id))
    th.start()
    th.join(timeout=30)
    assert not th.is_alive()
    return 1


def _write_other_process(backend, app_id, path):
    code = (
        "import sqlite3, sys\n"
        "c = sqlite3.connect(sys.argv[1])\n"
        "c.execute('INSERT INTO ' + sys.argv[2] + ' VALUES "
        "(?,?,?,?,?,?,?,?,?,?,?,?,?)', ('raw3', 'view', 'user', 'w3', "
        "'item', 'i0', None, 20003, 0, None, None, 20003, 0))\n"
        "c.commit()\n"
        "c.close()\n")
    done = subprocess.run(
        [sys.executable, "-c", code, path, f"pio_event_{app_id}"],
        timeout=60)
    assert done.returncode == 0
    return 1


def _delete_then_insert(backend, app_id, path):
    """The newest row goes and a new one takes its rowid: COUNT(*) and
    MAX(rowid) read what they read before."""
    store = backend.get_events()
    conn, table = store.client.conn(), f"pio_event_{app_id}"

    def shape():
        return conn.execute(
            f"SELECT COUNT(*), MAX(rowid) FROM {table}").fetchone()

    before = shape()
    newest = conn.execute(
        f"SELECT id FROM {table} ORDER BY rowid DESC LIMIT 1").fetchone()[0]
    assert store.delete(newest, app_id)
    store.insert(_view(4), app_id)
    assert shape() == before
    return 0


def _table_remade(backend, app_id, path):
    """DROP and CREATE count no row change: `total_changes` stands
    still, the schema cookie moves."""
    store = backend.get_events()
    n = store.client.conn().execute(
        f"SELECT COUNT(*) FROM pio_event_{app_id}").fetchone()[0]
    changes = store.client.conn().total_changes
    store.remove_channel(app_id)
    store.init_channel(app_id)
    assert store.client.conn().total_changes == changes
    return -n


def _store_reopened(backend, app_id, path):
    """The thread's connection closed, a commit from outside, a new
    connection: its three numbers read what the closed one's read (none
    of them is the file's), and only which connection it is differs."""
    client = backend.get_events().client
    numbers = client.change_token()[1:]
    client.close()
    gained = _write_other_process(backend, app_id, path)
    assert client.change_token()[1:] == numbers
    return gained


@pytest.mark.parametrize("write", [
    None, _write_same_connection, _write_other_connection,
    _write_other_process, _delete_then_insert, _table_remade,
    _store_reopened,
], ids=lambda w: "no_write" if w is None else w.__name__.lstrip("_"))
def test_scan_cache_key_sees_every_commit(backend, tmp_path, write):
    """A lookup after any commit — this connection's, another one's of
    the process, another process's, one that leaves count and MAX(rowid)
    alone, one that changes no row, one this connection was not there
    for — misses and returns the table as it is now; with no commit
    between, it hits."""
    from predictionio_tpu.data import ingest

    app_id = _seed_app(backend, "KeyApp")
    ingest.clear_scan_cache()
    backend.get_events().client.close()     # read on a connection
    first = ingest.training_scan("KeyApp").table    # that never wrote
    gained = 0 if write is None else write(
        backend, app_id, str(tmp_path / "t.db"))
    with ingest._scan_lock:
        entries = len(ingest._scan_cache)
    second = ingest.training_scan("KeyApp").table
    if write is None:
        assert second is first
        return
    assert second is not first
    assert second.num_rows == first.num_rows + gained
    ids = set(second.column("event_id").to_pylist())
    want = set(e.event_id for e in backend.get_events().find(app_id))
    assert ids == want
    with ingest._scan_lock:
        assert len(ingest._scan_cache) == entries + 1


def test_rolled_back_rows_are_not_served_from_the_cache(backend):
    """Inside a transaction of the connection's own there is no token (a
    rollback moves no counter back): the read keys with the digest, and
    the rows that were taken back are not in the next read."""
    from predictionio_tpu.data import ingest

    app_id = _seed_app(backend, "RollApp")
    ingest.clear_scan_cache()
    store = backend.get_events()
    conn = store.client.conn()
    n = ingest.training_scan("RollApp").table.num_rows
    conn.execute(
        f"INSERT INTO pio_event_{app_id} VALUES (?,?,?,?,?,?,?,?,?,?,?,?,?)",
        ("open1", "view", "user", "w9", "item", "i0", None, 20009, 0, None,
         None, 20009, 0))
    assert conn.in_transaction
    assert store.change_token(app_id) is None
    assert ingest.training_scan("RollApp").table.num_rows == n + 1
    conn.rollback()
    assert ingest.training_scan("RollApp").table.num_rows == n


def test_training_read_walks_no_table_for_its_cache_key(backend):
    """In a process whose cache is empty a training read on sqlite issues
    no statement that walks the event table before the scan, and the
    ``ingest_digest`` span is still recorded around what it does
    instead."""
    from predictionio_tpu.data import ingest
    from predictionio_tpu.obs import tracing
    from predictionio_tpu.obs.registry import default_registry

    app_id = _seed_app(backend, "TraceApp")
    ingest.clear_scan_cache()
    store = backend.get_events()
    digest = store.snapshot_digest(app_id)
    keys = default_registry().counter(
        "pio_ingest_cache_key_total", "", labelnames=("kind",))
    tokens0, digests0 = keys.value(kind="token"), keys.value(kind="digest")
    statements = []
    conn = store.client.conn()
    conn.set_trace_callback(statements.append)
    tokens, trace = tracing.start_trace("rid")
    try:
        scan = ingest.training_scan("TraceApp")
        props = ingest.aggregate_scan("TraceApp", "item")
    finally:
        tracing.reset_trace(tokens)
        conn.set_trace_callback(None)
    assert scan.table.num_rows == 91 and len(props) == 5
    assert any("PRAGMA data_version" in st for st in statements)
    for st in statements:
        assert "COUNT(*)" not in st and "MAX(creationTime)" not in st, st
    assert [s.name for s in trace.spans].count("ingest_digest") == 2
    assert keys.value(kind="token") == tokens0 + 2
    assert keys.value(kind="digest") == digests0
    # the durable fingerprint is what it was, for those who compare it
    assert store.snapshot_digest(app_id) == digest
    assert digest.startswith("rowid:1:91:91:")


def test_partitioned_store_composes_its_partitions_tokens(
        tmp_path, monkeypatch):
    from predictionio_tpu.data import ingest
    from predictionio_tpu.data.eventstore import clear_cache

    monkeypatch.setenv("PIO_INGEST_PARTITIONS", "3")
    Storage.configure({
        "sources": {"DB": {"TYPE": "sqlite", "PATH": str(tmp_path / "p.db")}},
        "repositories": {r: {"NAME": "pio", "SOURCE": "DB"}
                         for r in ("METADATA", "EVENTDATA", "MODELDATA")},
    })
    clear_cache()
    try:
        app_id = _seed_app(Storage, "PartApp")
        store = Storage.get_events()
        token = store.change_token(app_id)
        assert token[:3] == ("pmap", 3, store._gen) and len(token[3]) == 3
        assert token[3] == tuple(
            s.change_token(app_id) for s in store._stores)
        assert store.change_token(app_id) == token
        first = ingest.training_scan("PartApp").table
        assert ingest.training_scan("PartApp").table is first
        store.insert(_view(5), app_id)      # one partition's token moves
        moved = store.change_token(app_id)
        assert sum(a != b for a, b in zip(moved[3], token[3])) == 1
        second = ingest.training_scan("PartApp").table
        assert second.num_rows == first.num_rows + 1
    finally:
        Storage.reset()
        clear_cache()


def test_aggregate_scan_matches_direct(backend):
    _seed_app(backend, "AggApp")
    from predictionio_tpu.data.eventstore import EventStoreClient
    from predictionio_tpu.data.ingest import aggregate_scan

    direct = EventStoreClient.aggregate_properties("AggApp", "item")
    cached1 = aggregate_scan("AggApp", "item")
    cached2 = aggregate_scan("AggApp", "item")
    assert set(direct) == set(cached1) == set(cached2)
    for k in direct:
        assert direct[k] == cached1[k] == cached2[k]


def test_resolve_app_thread_safe(backend):
    _seed_app(backend, "RaceApp")
    from predictionio_tpu.data import eventstore

    results, errors = [], []

    def hit():
        try:
            for _ in range(50):
                results.append(eventstore.resolve_app("RaceApp"))
                eventstore.clear_cache()
        except Exception as e:          # pragma: no cover - failure path
            errors.append(e)

    threads = [threading.Thread(target=hit) for _ in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errors
    assert len(set(results)) == 1


# ---------------------------------------------------------------------------
# Per-engine parity: columnar DataSource == the per-event reference fold
# ---------------------------------------------------------------------------

def _find_events(app_name, **kw):
    from predictionio_tpu.data.eventstore import EventStoreClient

    return list(EventStoreClient.find(app_name=app_name, **kw))


def test_similarproduct_datasource_matches_row_fold(backend):
    _seed_app(backend, "SimParity")
    from predictionio_tpu.data.event import millis
    from predictionio_tpu.engines.similarproduct import (
        ALSAlgorithm, DataSourceParams, LikeAlgorithm,
        SimilarProductDataSource,
    )

    td = SimilarProductDataSource(
        DataSourceParams(app_name="SimParity")).read_training(None)
    ref = _find_events("SimParity", entity_type="user",
                       event_names=["view", "like", "dislike"],
                       target_entity_type="item")
    ref_views = {(e.entity_id, e.target_entity_id, millis(e.event_time))
                 for e in ref if e.event == "view"}
    got_views = {(v.user, v.item, v.t) for v in td.view_events}
    assert got_views == ref_views
    ref_likes = {(e.entity_id, e.target_entity_id, millis(e.event_time),
                  e.event == "like") for e in ref if e.event != "view"}
    got_likes = {(l.user, l.item, l.t, l.like) for l in td.like_events}
    assert got_likes == ref_likes

    # the algorithms' vectorized rating folds == the old dict folds
    counts = {}
    for u, i, _ in got_views:
        counts[(u, i)] = counts.get((u, i), 0.0) + 1.0
    uu, ii, vv = ALSAlgorithm()._ratings(td)
    assert {(u, i): float(v) for u, i, v in zip(uu, ii, vv)} == counts
    latest = {}
    for e in sorted(ref, key=lambda e: millis(e.event_time)):
        if e.event in ("like", "dislike"):
            key = (e.entity_id, e.target_entity_id)
            latest[key] = 1.0 if e.event == "like" else -1.0
    uu, ii, vv = LikeAlgorithm()._ratings(td)
    assert {(u, i): float(v) for u, i, v in zip(uu, ii, vv)} == latest


def test_ecommerce_datasource_matches_row_fold(backend):
    _seed_app(backend, "EcomParity")
    from predictionio_tpu.engines.ecommerce import (
        DataSourceParams, ECommerceDataSource,
    )

    td = ECommerceDataSource(
        DataSourceParams(app_name="EcomParity")).read_training(None)
    ref = _find_events("EcomParity", entity_type="user",
                       event_names=["view", "buy"],
                       target_entity_type="item")
    ref_views = sorted((e.entity_id, e.target_entity_id)
                       for e in ref if e.event == "view")
    ref_buys = sorted((e.entity_id, e.target_entity_id)
                      for e in ref if e.event == "buy")
    assert sorted(td.view_events) == ref_views
    assert sorted(td.buy_events) == ref_buys
    # users/items match the row-fold aggregate
    agg = aggregate_properties(_find_events(
        "EcomParity", entity_type="item",
        event_names=["$set", "$unset", "$delete"]))
    assert set(td.items) == set(agg)


def test_recommended_user_datasource_matches_row_fold(backend):
    _seed_app(backend, "FollowParity")
    from predictionio_tpu.data.event import millis
    from predictionio_tpu.engines.recommended_user import (
        DataSourceParams, RecommendedUserDataSource,
    )

    td = RecommendedUserDataSource(
        DataSourceParams(app_name="FollowParity")).read_training(None)
    ref = {(e.entity_id, e.target_entity_id, millis(e.event_time))
           for e in _find_events("FollowParity", entity_type="user",
                                 event_names=["follow"],
                                 target_entity_type="user")}
    assert {(f.user, f.followed_user, f.t)
            for f in td.follow_events} == ref


def test_sessionrec_datasource_matches_row_fold(backend):
    _seed_app(backend, "SessParity")
    from predictionio_tpu.engines.sessionrec import (
        DataSourceParams, SessionDataSource,
    )

    ds = SessionDataSource(DataSourceParams(app_name="SessParity"))
    got = ds._read_sessions()
    by_user = {}
    for e in _find_events("SessParity", entity_type="user",
                          event_names=["view", "buy"],
                          target_entity_type="item"):
        by_user.setdefault(e.entity_id, []).append(
            (e.event_time, e.target_entity_id))
    ref = []
    for _, pairs in sorted(by_user.items()):
        pairs.sort(key=lambda p: p[0])
        ref.append([i for _, i in pairs])
    assert got == ref


def test_classification_datasource_matches_row_fold(backend):
    app_id = backend.get_meta_data_apps().insert(
        App(id=0, name="ClassParity"))
    store = backend.get_events()
    store.init_channel(app_id)
    rng = random.Random(2)
    events = []
    for u in range(30):
        events.append(Event(
            event="$set", entity_type="user", entity_id=f"u{u}",
            properties=DataMap({
                "plan": float(u % 2), "attr0": float(rng.randrange(10)),
                "attr1": float(rng.randrange(10)),
                "attr2": float(rng.randrange(10))}),
            event_time=ms(u + 1)))
    # one user missing a required attr -> excluded on both paths
    events.append(Event(event="$set", entity_type="user", entity_id="u99",
                        properties=DataMap({"plan": 1.0}),
                        event_time=ms(500)))
    store.insert_batch(events, app_id)
    from predictionio_tpu.data.eventstore import clear_cache
    clear_cache()

    from predictionio_tpu.engines.classification import (
        ATTRS, ClassificationDataSource, DataSourceParams,
    )

    pts = ClassificationDataSource(
        DataSourceParams(app_name="ClassParity"))._points()
    agg = aggregate_properties(_find_events(
        "ClassParity", entity_type="user",
        event_names=["$set", "$unset", "$delete"]))
    ref = sorted(
        (float(pm.get("plan")), tuple(float(pm.get(a)) for a in ATTRS))
        for pm in agg.values()
        if all(r in pm for r in ("plan", *ATTRS)))
    assert sorted((p.label, p.features) for p in pts) == ref
    assert not any(p.features == () for p in pts)


def test_recommendation_datasource_matches_row_fold(backend):
    _seed_app(backend, "RecParity")
    from predictionio_tpu.engines.recommendation import (
        DataSourceParams, RecommendationDataSource,
    )

    cols = RecommendationDataSource(
        DataSourceParams(app_name="RecParity"))._read_columns()
    ref = []
    for e in _find_events("RecParity", entity_type="user",
                          event_names=["rate", "buy"],
                          target_entity_type="item"):
        v = (float(e.properties.get("rating")) if e.event == "rate"
             else 4.0)
        ref.append((e.entity_id, e.target_entity_id, v))
    got = list(zip(cols.users, cols.items, (float(v) for v in cols.values)))
    assert sorted(got) == sorted(ref)


def test_engine_training_deterministic_on_columnar_path(backend):
    """Same seeded store -> bit-identical model arrays across two train
    runs of the columnar path (the ingest produces a deterministic
    ordering, so seeded training is reproducible)."""
    _seed_app(backend, "DetApp")
    from predictionio_tpu.engines.similarproduct import (
        ALSAlgorithm, ALSAlgorithmParams, DataSourceParams,
        SimilarProductDataSource,
    )
    from predictionio_tpu.workflow.context import WorkflowContext

    ctx = WorkflowContext.create(mode="Training")
    ds = SimilarProductDataSource(DataSourceParams(app_name="DetApp"))
    algo = ALSAlgorithm(ALSAlgorithmParams(num_iterations=3))
    m1 = algo.train(ctx, ds.read_training(ctx))
    m2 = algo.train(ctx, ds.read_training(ctx))
    assert np.array_equal(m1.item_vocab, m2.item_vocab)
    np.testing.assert_array_equal(m1.V, m2.V)


# ---------------------------------------------------------------------------
# Static check: training reads must not use the row-iterator API
# ---------------------------------------------------------------------------

def test_no_engine_uses_row_find_for_training(repo_project):
    """`EventStoreClient.find` is the per-Event serving-era iterator; no
    engine module may call it anymore — training reads go through the
    columnar path (find_columnar / training_scan / aggregate_scan).
    Serving-time `find_by_entity` lookups stay allowed. Thin wrapper
    over `pio check` rule PIO102 (analysis/checkers/legacy.py)."""
    from predictionio_tpu.analysis import run_check

    report = run_check(repo_project, rules=["PIO102"])
    offenders = [f"{f.path}:{f.line}" for f in report.findings]
    assert not offenders, (
        "per-Event row scans in engine training reads (use the columnar "
        "ingest path): " + ", ".join(offenders))
