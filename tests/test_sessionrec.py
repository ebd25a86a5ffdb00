"""Session-based (sequence) recommendation engine: event-store -> sessions
-> transformer training -> next-item queries, plus the dp x tp sharded
training path on the virtual mesh."""

import datetime as dt

import numpy as np
import pytest

from predictionio_tpu.data import DataMap, Event
from predictionio_tpu.engines.sessionrec import (
    Query, default_engine_params, engine,
)
from predictionio_tpu.storage import App, Storage
from predictionio_tpu.workflow import run_train
from predictionio_tpu.workflow.train import load_for_deploy


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


@pytest.fixture()
def session_app(backend):
    app_id = backend.get_meta_data_apps().insert(App(id=0, name="SessApp"))
    store = backend.get_events()
    store.init_channel(app_id)
    # 60 users browsing a cyclic catalog: i(k) -> i(k+1) -> i(k+2) ...
    rng = np.random.default_rng(7)
    t0 = dt.datetime(2026, 1, 1, tzinfo=dt.timezone.utc)
    events = []
    for u in range(60):
        start = int(rng.integers(0, 15))
        for j in range(int(rng.integers(4, 9))):
            events.append(Event(
                event="view", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item",
                target_entity_id=f"i{(start + j) % 15:02d}",
                event_time=t0 + dt.timedelta(minutes=u * 100 + j)))
    store.insert_batch(events, app_id)
    return backend


def _params():
    return default_engine_params(
        "SessApp", d_model=32, n_heads=2, n_layers=1, max_len=16,
        epochs=15, batch_size=32)


def test_sessionrec_train_and_predict(session_app):
    eng = engine()
    instance = run_train(eng, _params())
    assert instance.status == "COMPLETED"

    result, ctx = load_for_deploy(eng, instance)
    algo, model = result.algorithms[0], result.models[0]
    pred = algo.predict(model, Query(items=["i03", "i04", "i05"], num=3))
    items = [s.item for s in pred.item_scores]
    assert "i06" in items            # the learned cyclic successor
    assert "i05" not in items        # seen items excluded
    scores = [s.score for s in pred.item_scores]
    assert scores == sorted(scores, reverse=True)

    # unknown items -> empty, not an error
    assert algo.predict(model, Query(items=["nope"], num=3)).item_scores == []


@pytest.mark.parametrize("model_store", ["localfs", "sqlite"])
def test_a_release_takes_its_weights_from_the_device(session_app, tmp_path,
                                                     monkeypatch,
                                                     model_store):
    """`train_seqrec` hands `run_train` device arrays; the persist counts
    every byte of them as fetched under its write, with one sample of the
    wait; what the store holds is numpy; and once `run_train` has
    returned nothing keeps the trained weights on the device."""
    import weakref

    import jax

    from predictionio_tpu.engines import sessionrec
    from predictionio_tpu.obs.registry import default_registry
    from predictionio_tpu.workflow.serialization import deserialize_models

    sources = {"DB": {"TYPE": "sqlite", "PATH": str(tmp_path / "t.db")},
               "FS": {"TYPE": "localfs", "PATH": str(tmp_path / "models")}}
    Storage.configure({"sources": sources, "repositories": {
        "METADATA": {"NAME": "pio", "SOURCE": "DB"},
        "EVENTDATA": {"NAME": "pio", "SOURCE": "DB"},
        "MODELDATA": {"NAME": "pio", "SOURCE":
                      "FS" if model_store == "localfs" else "DB"}}})
    trained = {}
    train_seqrec = sessionrec.train_seqrec

    def train_and_note(*args, **kwargs):
        model = train_seqrec(*args, **kwargs)
        leaves = jax.tree.leaves(model.params)
        assert all(isinstance(leaf, jax.Array) for leaf in leaves)
        live = {id(a) for a in jax.live_arrays()}
        assert all(id(leaf) in live for leaf in leaves)
        trained["refs"] = [weakref.ref(leaf) for leaf in leaves]
        trained["bytes"] = sum(leaf.nbytes for leaf in leaves)
        return model

    monkeypatch.setattr(sessionrec, "train_seqrec", train_and_note)
    reg = default_registry()

    def read(name, how):
        metric = reg.get(name)
        return how(metric) if metric is not None else 0

    def series():
        return {
            "fetch_spans": read("pio_span_duration_seconds",
                                lambda m: m.count(span="seqrec_fetch")),
            "fetched": read("pio_train_seqrec_fetch_bytes_total",
                            lambda m: m.value()),
            "persisted": read("pio_train_persist_bytes_total",
                              lambda m: m.value()),
            "device": read("pio_train_persist_device_bytes_total",
                           lambda m: m.value()),
            "waits": read("pio_train_persist_fetch_wait_seconds",
                          lambda m: m.count())}

    before = series()
    instance = run_train(
        engine(), default_engine_params(
            "SessApp", d_model=32, n_heads=2, n_layers=1, max_len=16,
            epochs=1, batch_size=32))
    gained = {k: v - before[k] for k, v in series().items()}
    assert gained["fetch_spans"] == 1
    assert gained["fetched"] == gained["device"] == trained["bytes"] > 0
    assert gained["waits"] == 1
    assert trained["bytes"] < gained["persisted"]
    assert [ref() for ref in trained["refs"]] == [None] * len(trained["refs"])
    blob = Storage.get_model_data_models().get(instance.id).models
    params = deserialize_models(blob)[0].params
    assert all(isinstance(leaf, np.ndarray)
               for leaf in jax.tree.leaves(params))
    assert sum(leaf.nbytes for leaf in jax.tree.leaves(params)) \
        == trained["bytes"]


def test_sessionrec_sharded_2d_mesh(session_app, mesh8):
    """Full train step over a 4 (data) x 2 (model) mesh."""
    import jax
    from jax.sharding import Mesh

    from predictionio_tpu.engines.sessionrec import (
        AlgorithmParams, SessionDataSource, DataSourceParams,
        SessionPreparator, SeqRecAlgorithm,
    )
    from predictionio_tpu.models.seqrec import train_seqrec

    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(4, 2),
                axis_names=("data", "model"))
    ds = SessionDataSource(DataSourceParams(app_name="SessApp"))
    td = SessionPreparator().prepare(None, ds.read_training(None))
    params = AlgorithmParams(d_model=32, n_heads=2, n_layers=1, max_len=16,
                             epochs=20, batch_size=32)
    model = train_seqrec(mesh, td.sessions, params)
    recs = model.recommend_next(["i03", "i04", "i05"], 5)
    assert any(it == "i06" for it, _ in recs)
    # the mesh still holds the trained weights; serving, a program for
    # one device, took them whole onto one
    assert len(model.params["emb"].sharding.device_set) == 8
    assert all(len(w.sharding.device_set) == 1
               for w in jax.tree.leaves(model._device_params()[0]))


def test_sessionrec_eval_folds(session_app):
    ds_params = _params().data_source_params
    ds_params.eval_params = {"kFold": 3, "queryNum": 5}
    from predictionio_tpu.engines.sessionrec import SessionDataSource

    folds = SessionDataSource(ds_params).read_eval(None)
    assert len(folds) == 3
    td, info, qa = folds[0]
    assert qa and all(len(q.items) >= 2 for q, _ in qa)
    # held-out session tails never appear in that fold's training data
    q0, a0 = qa[0]
    assert a0.item  # leave-one-out target present


def test_sessionrec_resume_rejects_mismatched_opt_state(tmp_path, caplog):
    """Round-3 advisor regression: a snapshot whose optimizer leaves have
    the right COUNT but wrong shape/dtype must resume params with RESET
    adam moments (warning), never feed mis-shaped moments to the first
    apply_updates."""
    import logging
    import pickle

    from predictionio_tpu.engines.sessionrec import AlgorithmParams
    from predictionio_tpu.models.seqrec import train_seqrec
    from predictionio_tpu.workflow.checkpoint import Checkpointer

    sessions = [[f"i{(s + j) % 6}" for j in range(4)] for s in range(12)]
    p = AlgorithmParams(d_model=8, n_heads=2, n_layers=1, max_len=8,
                        epochs=2, batch_size=4)
    ck = Checkpointer(str(tmp_path), interval=1)
    train_seqrec(None, sessions, p, checkpointer=ck)

    # tamper every snapshot: truncate each opt leaf to shape () f16 —
    # leaf count stays right, shapes/dtypes go wrong
    snaps = [f for f in tmp_path.iterdir() if f.suffix == ".pkl"]
    assert snaps, "interval=1 must have left a mid-train snapshot"
    for f in snaps:
        snap = pickle.loads(f.read_bytes())
        snap["state"]["opt_leaves"] = [
            np.float16(0) for _ in snap["state"]["opt_leaves"]]
        f.write_bytes(pickle.dumps(snap))

    p5 = AlgorithmParams(d_model=8, n_heads=2, n_layers=1, max_len=8,
                        epochs=3, batch_size=4)
    with caplog.at_level(logging.WARNING):
        model = train_seqrec(None, sessions, p5, checkpointer=ck)
    assert model.recommend_next(["i0", "i1"], 2)
    assert any("RESET adam moments" in r.message for r in caplog.records)


def test_sessionrec_ring_attention_matches_flash(mesh8):
    """A mesh with a "seq" axis trains with ring attention (sequence
    parallelism), and that is exact: same data + seed must reproduce the
    flash-trained model."""
    import jax
    from jax.sharding import Mesh

    from predictionio_tpu.models.seqrec import SeqRecParams, train_seqrec

    sessions = [[f"i{(s + j) % 10}" for j in range(8)] for s in range(24)]
    base = dict(d_model=16, n_heads=2, n_layers=1, max_len=8, epochs=2,
                batch_size=8)
    flash = train_seqrec(None, sessions, SeqRecParams(**base))

    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                axis_names=("data", "seq"))
    ring = train_seqrec(mesh, sessions, SeqRecParams(**base))
    np.testing.assert_allclose(
        np.asarray(ring.params["emb"]), np.asarray(flash.params["emb"]),
        atol=2e-4)
    recs = ring.recommend_next(["i2", "i3"], 3)
    assert recs


