"""Admin API, dashboard, self-cleaning data source, parallel helpers."""

import datetime as dt

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from predictionio_tpu.core.self_cleaning import EventWindow, clean_events
from predictionio_tpu.data import DataMap, Event
from predictionio_tpu.storage import App, EvaluationInstance, Storage

pytestmark = pytest.mark.anyio

UTC = dt.timezone.utc


@pytest.fixture()
def backend(tmp_path):
    Storage.configure({
        "sources": {"DB": {"TYPE": "sqlite", "PATH": str(tmp_path / "o.db")}},
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


# -- admin API ---------------------------------------------------------------

async def test_admin_app_lifecycle(backend):
    from predictionio_tpu.server.admin import create_admin_server

    c = TestClient(TestServer(create_admin_server()))
    await c.start_server()
    try:
        assert (await (await c.get("/")).json()) == {"status": "alive"}
        # create
        resp = await c.post("/cmd/app", json={"name": "adminapp"})
        assert resp.status == 201
        body = await resp.json()
        assert body["accessKey"]
        # duplicate -> 409
        assert (await c.post("/cmd/app", json={"name": "adminapp"})).status == 409
        # bad body -> 400
        assert (await c.post("/cmd/app", data=b"x")).status == 400
        # list
        apps = (await (await c.get("/cmd/app")).json())["apps"]
        assert [a["name"] for a in apps] == ["adminapp"]
        # wipe data
        resp = await c.delete("/cmd/app/adminapp/data")
        assert resp.status == 200
        # delete
        assert (await c.delete("/cmd/app/adminapp")).status == 200
        assert (await c.delete("/cmd/app/adminapp")).status == 404
    finally:
        await c.close()


# -- dashboard ---------------------------------------------------------------

async def test_dashboard_lists_evaluations(backend):
    from predictionio_tpu.server.dashboard import create_dashboard

    evis = backend.get_meta_data_evaluation_instances()
    instance = EvaluationInstance(
        status="EVALCOMPLETED", evaluation_class="MyEval",
        evaluator_results="[Metric] 0.9",
        evaluator_results_html="<html><body>detail here</body></html>",
        evaluator_results_json='{"score": 0.9}')
    iid = evis.insert(instance)
    instance.id = iid
    evis.update(instance)

    c = TestClient(TestServer(create_dashboard()))
    await c.start_server()
    try:
        page = await (await c.get("/")).text()
        assert "MyEval" in page and iid in page
        detail = await (await c.get(f"/engine_instances/{iid}")).text()
        assert "detail here" in detail
        assert (await c.get("/engine_instances/nope")).status == 404
        listing = await (await c.get("/evaluations.json")).json()
        assert listing[0]["id"] == iid
        one = await (await c.get(f"/evaluations/{iid}.json")).json()
        assert one["resultJSON"] == '{"score": 0.9}'
    finally:
        await c.close()


# -- self-cleaning -----------------------------------------------------------

def t(days):
    return dt.datetime(2026, 1, 1, tzinfo=UTC) + dt.timedelta(days=days)


def sev(eid, props, when, name="$set"):
    return Event(event=name, entity_type="user", entity_id=eid,
                 properties=DataMap(props), event_time=when,
                 creation_time=when)


def test_event_window_cutoff():
    w = EventWindow(duration="3 days")
    now = t(10)
    assert w.cutoff(now) == t(7)
    assert EventWindow().cutoff(now) is None
    with pytest.raises(ValueError):
        EventWindow(duration="5 fortnights").cutoff(now)


def test_clean_events_window_and_compress():
    events = [
        sev("u1", {"a": 1, "b": 2}, t(0)),
        sev("u1", {"a": 9}, t(5)),
        sev("u1", {"b": None}, t(6), name="$unset"),
        Event(event="view", entity_type="user", entity_id="u1",
              target_entity_type="item", target_entity_id="i1",
              event_time=t(6)),
    ]
    w = EventWindow(duration="30 days", compress_properties=True)
    out = clean_events(events, w, now=t(7))
    sets = [e for e in out if e.event == "$set"]
    views = [e for e in out if e.event == "view"]
    assert len(sets) == 1 and len(views) == 1
    # folded: a=9 survives; b was set then unset within the window
    assert sets[0].properties.fields == {"a": 9}
    # window drops old events
    out = clean_events(events, EventWindow(duration="3 days"), now=t(7))
    assert all(e.event_time >= t(4) for e in out)


def test_clean_events_dedup():
    e = sev("u1", {"a": 1}, t(0))
    out = clean_events([e, e, sev("u1", {"a": 1}, t(1))],
                       EventWindow(remove_duplicates=True), now=t(2))
    assert len(out) == 2  # same payload, different time -> kept


def test_self_cleaning_rewrites_store(backend):
    from predictionio_tpu.core.self_cleaning import SelfCleaningDataSource

    app_id = backend.get_meta_data_apps().insert(App(id=0, name="CleanApp"))
    store = backend.get_events()
    store.init_channel(app_id)
    store.insert_batch([
        sev("u1", {"a": 1}, t(0)),
        sev("u1", {"a": 2}, t(5)),
        sev("u2", {"x": 1}, t(6)),
    ], app_id)

    class DS(SelfCleaningDataSource):
        app_name = "CleanApp"
        # window measured from real now; wide enough to keep the fixture
        event_window = EventWindow(duration="10000 days",
                                   compress_properties=True)

    n = DS().clean_persisted_events()
    assert n == 2  # one compressed $set per live entity
    left = list(store.find(app_id))
    assert len(left) == 2
    by_entity = {e.entity_id: e for e in left}
    assert by_entity["u1"].properties.fields == {"a": 2}


# -- parallel helpers --------------------------------------------------------

def test_make_mesh_shapes(mesh8):
    from predictionio_tpu.parallel import make_mesh

    m = make_mesh()
    assert m.devices.size == 8
    m = make_mesh(shape=(2, 4), axis_names=("data", "model"))
    assert m.axis_names == ("data", "model")
    with pytest.raises(ValueError):
        make_mesh(shape=(16,))


def test_collectives_ring(mesh8):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from predictionio_tpu.parallel.collectives import psum, ring_pass, ring_reduce
    from jax import shard_map

    def f(x):
        local = x.reshape(-1)
        total = psum(local, "data")
        ringed = ring_reduce(local, "data", 8)
        passed = ring_pass(local, "data", 8)
        return total, ringed, passed

    x = jnp.arange(8.0).reshape(8, 1)
    shard = shard_map(f, mesh=mesh8, in_specs=P("data"),
                      out_specs=(P(), P("data"), P("data")),
                      check_vma=False)
    total, ringed, passed = shard(x)
    assert float(total[0]) == 28.0
    np.testing.assert_allclose(np.asarray(ringed).ravel(), [28.0] * 8)
    # ring_pass shifts blocks by one position
    np.testing.assert_allclose(np.asarray(passed).ravel(),
                               np.roll(np.arange(8.0), 1))


def test_global_array_from_local(mesh8):
    import jax

    from predictionio_tpu.parallel.distributed import global_array_from_local

    local = np.arange(16.0, dtype=np.float32)
    arr = global_array_from_local(mesh8, local)
    assert arr.shape == (16,)
    np.testing.assert_allclose(np.asarray(jax.device_get(arr)), local)
