"""Multi-process runtime (parallel/distributed.py): two REAL processes,
one jax.distributed runtime, a mesh spanning both, sharded input
assembly, and a sharded ALS train whose result matches single-process.

The reference never tests its process boundary (it trusts Spark,
SURVEY.md §4 tier 2); this rebuild owns the runtime, so the boundary
gets a real test: the CI analog of a 2-host pod slice.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


CHILD = os.path.join(os.path.dirname(__file__), "distributed_child.py")


def _make_store(tmpdir: str):
    """Parquet event store pre-loaded with the toy ratings in FOUR
    fragments, so shard=(p, 2) assigns each process a strict subset."""
    from predictionio_tpu.data import Event
    from predictionio_tpu.storage.parquet_events import (
        ParquetEvents, ParquetEventsClient)
    from tests.distributed_child import make_toy_ratings

    users, items, ratings, n_users, n_items = make_toy_ratings()
    store = ParquetEvents(ParquetEventsClient(tmpdir))
    store.init_channel(1)
    events = [Event(event="rate", entity_type="user",
                    entity_id=f"u{u:03d}", target_entity_type="item",
                    target_entity_id=f"i{i:03d}",
                    properties={"rating": float(r)})
              for u, i, r in zip(users, items, ratings)]
    q = -(-len(events) // 4)
    for k in range(0, len(events), q):
        store.insert_batch(events[k:k + q], 1)
    return users, items, ratings, n_users, n_items


def _make_engine_db(db_path: str):
    """Sqlite store + app metadata so the DASE DataSource path can run
    its partitioned read through the real registry/facade."""
    from predictionio_tpu.data import Event
    from predictionio_tpu.storage import App, Storage
    from tests.distributed_child import make_toy_ratings

    Storage.configure({
        "sources": {"DB": {"TYPE": "sqlite", "PATH": db_path}},
        "repositories": {
            "METADATA": {"NAME": "pio", "SOURCE": "DB"},
            "EVENTDATA": {"NAME": "pio", "SOURCE": "DB"},
            "MODELDATA": {"NAME": "pio", "SOURCE": "DB"},
        },
    })
    from predictionio_tpu.data.eventstore import clear_cache
    clear_cache()
    apps = Storage.get_meta_data_apps()
    app_id = apps.insert(App(id=0, name="DistApp"))
    store = Storage.get_events()
    store.init_channel(app_id)
    users, items, ratings, *_ = make_toy_ratings()
    store.insert_batch(
        [Event(event="rate", entity_type="user", entity_id=f"u{u:03d}",
               target_entity_type="item", target_entity_id=f"i{i:03d}",
               properties={"rating": float(r)})
         for u, i, r in zip(users, items, ratings)], app_id)


def test_two_process_sharded_als_matches_single_process(tmp_path):
    # hang protection comes from communicate(timeout=...) below
    port = _free_port()
    store_dir = str(tmp_path / "events")
    users, items, ratings, n_users, n_items = _make_store(store_dir)
    db_path = str(tmp_path / "engine.db")
    _make_engine_db(db_path)
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    env["PIO_DIST_STORE"] = store_dir
    env["PIO_DIST_DB"] = db_path
    # the child runs as a script (sys.path[0] = tests/): put the checkout
    # on its path so the test does not depend on a pip install
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    procs = [
        subprocess.Popen(
            [sys.executable, CHILD, str(pid), "2", str(port)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env)
        for pid in (0, 1)
    ]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("distributed child hung (no Gloo rendezvous?)")
        assert p.returncode == 0, f"child failed:\n{err[-2000:]}"
        outs.append(out)

    results = {}
    for out in outs:
        for line in out.splitlines():
            if line.startswith("RESULT "):
                r = json.loads(line[len("RESULT "):])
                results[r["pid"]] = r
    assert sorted(results) == [0, 1], f"missing child results: {outs}"

    # both processes must hold identical full factor matrices after the
    # final all-gather (single-controller SPMD: same program, same state)
    np.testing.assert_allclose(results[0]["U_row0"], results[1]["U_row0"],
                               atol=1e-5)
    np.testing.assert_allclose(results[0]["V_row0"], results[1]["V_row0"],
                               atol=1e-5)

    # ...and match a single-process train of the same data (the shard
    # layout is a performance choice, not a semantic one)
    from predictionio_tpu.models.als import ALSData, ALSParams, train_als
    from tests.distributed_child import make_toy_ratings
    import jax
    from jax.sharding import Mesh

    users, items, ratings, n_users, n_items = make_toy_ratings()
    mesh = Mesh(np.asarray(jax.devices()[:2]), axis_names=("data",))
    data = ALSData.build(users, items, ratings, n_users, n_items,
                         n_shards=2)
    params = ALSParams(rank=4, num_iterations=3, chunk_size=64)
    U, V = train_als(mesh, data, params)
    np.testing.assert_allclose(np.asarray(U[0]), results[0]["U_row0"],
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(V[0]), results[0]["V_row0"],
                               atol=1e-4)

    # -- partitioned store read (P2 complete): strict-subset reads, and
    # the exchanged+locally-packed train matches a single-process train
    # of the same events with the same sorted-vocab ids
    r0, r1 = results[0], results[1]
    assert r0["store_local_n"] < r0["store_total_n"]
    assert r0["store_local_n"] + r1["store_local_n"] == r0["store_total_n"]
    assert r0["store_total_n"] == len(ratings)
    uvocab = np.unique([f"u{u:03d}" for u in users])
    ivocab = np.unique([f"i{i:03d}" for i in items])
    u_idx = np.searchsorted(uvocab, [f"u{u:03d}" for u in users])
    i_idx = np.searchsorted(ivocab, [f"i{i:03d}" for i in items])
    sdata = ALSData.build(u_idx.astype(np.int32), i_idx.astype(np.int32),
                          ratings, len(uvocab), len(ivocab), n_shards=2)
    sU, sV = train_als(mesh, sdata, params)
    # the partitioned build must digest identically to the single-process
    # build of the same data (checkpoint fingerprints survive resuming on
    # a different process count)
    assert sdata.digest == r0["store_digest"], (
        sdata.digest, r0["store_digest"])
    np.testing.assert_allclose(np.asarray(sU[0]), r0["store_U_row0"],
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(sV[0]), r0["store_V_row0"],
                               atol=1e-4)
    np.testing.assert_allclose(r0["store_U_row0"], r1["store_U_row0"],
                               atol=1e-5)

    # -- DASE layer: the engine DataSource's partitioned read + algorithm
    # build_distributed, through the real registry/facade. Each process
    # read a strict subset, both produced identical full factor models
    assert 0 < r0["engine_local_rows"] < len(ratings)
    assert r0["engine_local_rows"] + r1["engine_local_rows"] == len(ratings)
    assert r0["engine_n_users"] == n_users
    assert r0["engine_n_items"] == n_items
    np.testing.assert_allclose(r0["engine_U_row0"], r1["engine_U_row0"],
                               atol=1e-5)
    # degrade path (backend without read_snapshot): replicated read,
    # disjoint strided keep — each rating counted exactly once, so the
    # model matches the sharded-read train up to f32 reduction order
    assert (r0["engine_degrade_rows"] + r1["engine_degrade_rows"]
            == len(ratings))
    np.testing.assert_allclose(r0["engine_degrade_U_row0"],
                               r0["engine_U_row0"], atol=1e-4)

    # -- seqrec with the MODEL axis spanning both processes: both hosts
    # extract the identical full (gathered) model, and the cross-host
    # tensor-parallel train actually learns the cyclic successor
    # (vocab pads to the tp multiple, so exact single-process parity is
    # not expected — the softmax normalizes over the padded vocab)
    assert r0["seqrec_top"] == r1["seqrec_top"]
    np.testing.assert_allclose(r0["seqrec_emb_sum"], r1["seqrec_emb_sum"],
                               rtol=1e-5)
    assert "i4" in r0["seqrec_top"], r0["seqrec_top"]
    assert r0["seqrec_emb_shape"][0] % 2 == 0   # padded to tp=2 multiple

    # -- sharded cooccurrence from disjoint pair shards matches a
    # single-device run over the union of the shards
    from predictionio_tpu.models.cooccurrence import (
        cooccurrence_topn, distinct_pairs)
    rng = np.random.default_rng(21)
    cu = rng.integers(0, 40, 2000).astype(np.int32)
    ci = rng.integers(0, 30, 2000).astype(np.int32)
    du, di = distinct_pairs(cu, ci)
    mesh1 = Mesh(np.asarray(jax.devices()[:1]), axis_names=("data",))
    cv, _ = cooccurrence_topn(mesh1, du, di, 40, 30, 5)
    np.testing.assert_allclose(float(cv.sum()), r0["cooc_vals_sum"])
    np.testing.assert_allclose(np.asarray(cv[0], np.float64).tolist(),
                               r0["cooc_vals_row0"])
    assert r0["cooc_vals_sum"] == r1["cooc_vals_sum"]

    # -- classification (NB) across processes: the psum'd counts match a
    # single-process train of the same data (organic DEVICE_MIN_SIZE
    # crossing — the r4 "classification has no multi-process execution"
    # gap)
    from predictionio_tpu.models.naive_bayes import train_multinomial_nb
    rngn = np.random.default_rng(31)
    Xn = rngn.poisson(1.0, size=(140_000, 8)).astype(np.float32)
    yn = np.where(rngn.random(len(Xn)) < 0.5, "a", "b")
    mn = train_multinomial_nb(Xn, yn)
    np.testing.assert_allclose(float(np.abs(mn.log_prob).sum()),
                               r0["nb_log_prob_sum"], rtol=1e-6)
    np.testing.assert_allclose(mn.log_prior, r0["nb_log_prior"], rtol=1e-9)
    assert r0["nb_log_prob_sum"] == r1["nb_log_prob_sum"]
