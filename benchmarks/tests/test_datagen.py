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


def test_bulk_fill_writes_the_rows_insert_batch_writes(tmp_path, monkeypatch):
    """The set-up's bulk path and the store's insert_batch leave the same
    rows (ids aside) in the event table."""
    import datetime as dt

    for k, v in __import__("benchmarks.run", fromlist=["x"]).child_env(
            "cpu", str(tmp_path)).items():
        if k.startswith("PIO_"):
            monkeypatch.setenv(k, v)
    (tmp_path / "storage").mkdir()
    from benchmarks import child
    from predictionio_tpu.data.datamap import DataMap
    from predictionio_tpu.data.event import UTC, Event
    from predictionio_tpu.storage import Storage

    Storage.reset() if hasattr(Storage, "reset") else None
    child.pio("app", "new", child.APP)
    cfg = {"n_users": 30, "n_items": 20, "n_events": 200}
    users, items, ratings = child.fill_event_store(cfg, 5)
    store = Storage.get_events()
    app = Storage.get_meta_data_apps().get_by_name(child.APP)
    table = f"pio_event_{app.id}"
    bulk = store.client.conn().execute(
        f"SELECT * FROM {table} ORDER BY rowid").fetchall()
    store.client.conn().execute(f"DELETE FROM {table}")
    store.client.conn().commit()
    when = dt.datetime(2015, 3, 31, tzinfo=UTC)
    store.insert_batch([
        Event(event="rate", entity_type="user", entity_id=str(u + 1),
              target_entity_type="item", target_entity_id=str(i + 1),
              properties=DataMap({"rating": r}), event_time=when,
              creation_time=when)
        for u, i, r in zip(users.tolist(), items.tolist(), ratings.tolist())],
        app.id)
    own = store.client.conn().execute(
        f"SELECT * FROM {table} ORDER BY rowid").fetchall()
    assert len(bulk) == len(own) == 200
    assert [r[1:] for r in bulk] == [r[1:] for r in own]
    assert len({r[0] for r in bulk}) == 200
