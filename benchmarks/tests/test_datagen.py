"""The generator pieces copied from the program, each deterministic from
--seed (a large one too: the driver's seeds pass 2**31)."""
import numpy as np
import pytest

from benchmarks.lib import datagen

BIG = 2**31 + 12345


@pytest.mark.parametrize("seed", [0, 7, BIG])
def test_rating_events_are_a_function_of_the_seed(seed):
    a = datagen.rating_events(300, 200, 6000, seed)
    b = datagen.rating_events(300, 200, 6000, seed)
    c = datagen.rating_events(300, 200, 6000, seed + 1)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[2], c[2])
    users, items, ratings = a
    # every id appears (widths are not cut), half-star scale, skewed items
    assert set(users.tolist()) == set(range(300))
    assert set(items.tolist()) == set(range(200))
    assert set(np.unique(ratings * 2) % 1) == {0.0}
    assert ratings.min() >= 0.5 and ratings.max() <= 5.0
    counts = np.sort(np.bincount(items, minlength=200))
    assert counts[-20:].sum() > 3 * counts[:20].sum()
    # every seed holds the same work in another order: the same rating
    # counts per user and per item, relabelled
    for col in (0, 1):
        assert np.array_equal(np.sort(np.bincount(a[col])),
                              np.sort(np.bincount(c[col])))


def test_rating_events_must_cover_the_widths():
    with pytest.raises(ValueError):
        datagen.rating_events(300, 200, 100, 0)


@pytest.mark.parametrize("seed", [3, BIG])
def test_factors_and_queries_are_functions_of_the_seed(seed):
    a = datagen.factors(500, 300, 16, seed)
    b = datagen.factors(500, 300, 16, seed)
    assert np.array_equal(a["U"], b["U"]) and np.array_equal(a["V"], b["V"])
    assert a["U"].dtype == np.float32 and a["V"].shape == (300, 16)
    assert not np.array_equal(a["V"], datagen.factors(500, 300, 16, seed + 1)["V"])
    # item norms are skewed, so top-k is not the same flat tie for everyone
    norms = np.linalg.norm(a["V"], axis=1)
    assert norms.max() > 2 * np.median(norms)
    q = datagen.query_users(500, 1.0, 2000, seed)
    assert np.array_equal(q, datagen.query_users(500, 1.0, 2000, seed))
    assert q.min() >= 0 and q.max() < 500
    top = np.bincount(q, minlength=500).max()
    assert top > 2000 / 500 * 10          # Zipf: a popular head


def test_zipf_matches_its_law():
    rng = np.random.default_rng(0)
    ranks = datagen.zipf_ranks(50, 1.0, 200_000, rng)
    pmf = np.arange(1, 51) ** -1.0
    pmf /= pmf.sum()
    got = np.bincount(ranks, minlength=50) / 200_000
    assert np.abs(got - pmf).max() < 0.005


def test_entity_ids_sort_numerically():
    ids = datagen.entity_ids(1200, "u")
    assert ids[0] == "u0000" and ids[-1] == "u1199"
    assert np.array_equal(np.sort(ids), ids)


def _ratings_columns():
    from benchmarks.events import ratings

    return ratings.generate({"n_users": 30, "n_items": 20, "n_events": 200}, 5)[0]


def _mixed_columns():
    """Per-row event times, rows with and without properties, text ids,
    rows with no target: what the ratings generator does not produce."""
    n = 50
    rng = np.random.default_rng(1)
    props = np.array([None if i % 3 else '{"price": %d.5}' % i
                      for i in range(n)], dtype=object)
    return {"event": "view", "entity_type": "user",
            "entity_id": [f"u{i % 7}" for i in range(n)],
            "target_entity_type": "item",
            "target_entity_id": [None if i % 5 == 0 else f"i{i}" for i in range(n)],
            "properties": props,
            "event_time_ms": 1_600_000_000_000 + rng.permutation(n) * 1000}


@pytest.mark.parametrize("make", [_ratings_columns, _mixed_columns])
def test_bulk_fill_writes_the_rows_insert_batch_writes(make, tmp_path,
                                                       monkeypatch):
    """The set-up's bulk writer and the store's insert_batch leave the
    same rows (ids aside) in the event table, for any generator's
    columns."""
    import datetime as dt
    import json

    for k, v in __import__("benchmarks.run", fromlist=["x"]).child_env(
            "cpu", str(tmp_path)).items():
        if k.startswith("PIO_"):
            monkeypatch.setenv(k, v)
    (tmp_path / "storage").mkdir()
    from benchmarks import child, fill
    from predictionio_tpu.data.datamap import DataMap
    from predictionio_tpu.data.event import UTC, Event
    from predictionio_tpu.storage import Storage

    Storage.reset() if hasattr(Storage, "reset") else None
    child.pio("app", "new", child.APP)
    columns = make()
    n = fill.fill_event_store(columns, 5, child.APP, block=64)
    store = Storage.get_events()
    app = Storage.get_meta_data_apps().get_by_name(child.APP)
    table = f"pio_event_{app.id}"
    bulk = store.client.conn().execute(
        f"SELECT * FROM {table} ORDER BY rowid").fetchall()
    store.client.conn().execute(f"DELETE FROM {table}")
    store.client.conn().commit()

    def column(name):
        col = columns[name]
        if isinstance(col, (str, int, type(None))):
            return [col] * n
        return col.tolist() if hasattr(col, "tolist") else list(col)

    def text(v):
        return None if v is None else str(v)

    events = []
    for ev, et, eid, tt, tid, props, ms in zip(*(
            column(c) for c in fill.EVENT_COLUMNS)):
        when = dt.datetime.fromtimestamp(ms / 1000, tz=UTC)
        events.append(Event(
            event=ev, entity_type=et, entity_id=text(eid),
            target_entity_type=tt if tid is not None else None,
            target_entity_id=text(tid),
            properties=DataMap(json.loads(props)) if props else DataMap({}),
            event_time=when, creation_time=when))
    store.insert_batch(events, app.id)
    own = store.client.conn().execute(
        f"SELECT * FROM {table} ORDER BY rowid").fetchall()
    assert len(bulk) == len(own) == n
    assert [r[1:] for r in bulk] == [r[1:] for r in own]
    assert len({r[0] for r in bulk}) == n


def test_bulk_fill_refuses_columns_that_are_not_the_stores():
    from benchmarks import fill

    cols = _mixed_columns()
    with pytest.raises(SystemExit, match="event columns"):
        fill.fill_event_store({k: v for k, v in cols.items()
                               if k != "properties"}, 1, "bench")
    cols["entity_id"] = cols["entity_id"][:-1]
    with pytest.raises(SystemExit, match="lengths"):
        fill.fill_event_store(cols, 1, "bench")


#: sha256 over the three columns' bytes of lib/datagen.rating_events(300,
#: 200, 6000, seed, 20260927) at PR 25 (commit 2e73be8), before the
#: generator moved behind events/ratings.py
BITS_AT_PR25 = {
    0: "6f66a2e32ce50da566a30b6255b94bf2e5dfbd23ad631afec969bbfacd62bce5",
    7: "f4ec6ce30670b918d8856e2a1a6f70fed255bc4e630020022a60fe57879c3939",
    BIG: "e61e6e08c23d2ad7ab34c43e3e6fdbf672d052a3fd64c1e43729bf909c71fcbb"}


@pytest.mark.parametrize("seed", sorted(BITS_AT_PR25))
def test_the_ratings_events_are_the_bits_of_rating_events(seed):
    """events/ratings.py hands the store and the check what
    lib/datagen.rating_events made at PR 25, bit for bit."""
    import hashlib
    import json

    from benchmarks.events import ratings

    cfg = {"n_users": 300, "n_items": 200, "n_events": 6000,
           "structure_seed": 20260927}
    columns, truth = ratings.generate(cfg, seed)
    want = datagen.rating_events(300, 200, 6000, seed, 20260927)
    got = (truth["users"], truth["items"], truth["ratings"])
    assert all(np.array_equal(a, b) and a.dtype == b.dtype
               for a, b in zip(got, want))
    assert hashlib.sha256(b"".join(x.tobytes() for x in got)).hexdigest() \
        == BITS_AT_PR25[seed]
    # the columns say the same as the truth: 1-based ids, the rating as
    # the JSON text the store's DataMap writes
    assert np.array_equal(columns["entity_id"], want[0] + 1)
    assert np.array_equal(columns["target_entity_id"], want[1] + 1)
    assert [json.loads(t)["rating"] for t in columns["properties"][:50]] \
        == want[2][:50].tolist()
    assert set(columns["properties"].tolist()) <= {
        json.dumps({"rating": r / 2}, sort_keys=True) for r in range(1, 11)}
    assert (columns["event"], columns["entity_type"],
            columns["target_entity_type"]) == ("rate", "user", "item")
    assert columns["event_time_ms"] == 1427760000000


#: sha256 of the engine.json child.write_variant wrote for rec-ml20m-r64
#: at PR 25 (the chip's sizes, and the --tiny sizes)
VARIANT_AT_PR25 = {
    False: "3078417c06a7f14b29b0271b6ef64e492e128c7095b69d166377e81dbc7b7690",
    True: "d615bc9217f737e3801e28382eb86eb6cd070935b966f204cfe2a016e04b1296"}


@pytest.mark.parametrize("tiny", [False, True])
def test_the_variant_written_for_ml20m_is_the_one_of_pr25(tiny, tmp_path):
    """The configuration's algorithm_params laid over the template give
    the variant file of PR 25 byte for byte: the same program, the same
    fn_cache keys, the same compile cache entry."""
    import argparse
    import hashlib

    from benchmarks import child, run
    from benchmarks.lib import manifest

    bench = manifest.load_benchmark()
    args = argparse.Namespace(tiny=tiny, seed=1, seconds=1.0, trace=0)
    spec = run.build_spec(bench, manifest.find_cell(bench, "ml20m-r64.train"),
                          args, str(tmp_path))
    path = child.write_variant(str(tmp_path), spec["config"])
    with open(path, "rb") as f:
        text = f.read()
    assert hashlib.sha256(text).hexdigest() == VARIANT_AT_PR25[tiny], text
    # and the overlay of train_again changes that parameter alone
    again = child.write_variant(str(tmp_path), spec["config"],
                                {"num_iterations": 1}, "engine_again")
    with open(again) as f:
        lines = f.read().splitlines()
    changed = [(a, b) for a, b in zip(text.decode().splitlines(), lines)
               if a != b]
    assert len(changed) == 1 and '"num_iterations": 1' in changed[0][1]
