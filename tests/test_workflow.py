"""Train/eval workflows with metadata + model store round trip
(mirrors reference CoreWorkflow/EvaluationWorkflow tests)."""

import dataclasses

import numpy as np
import pytest

from predictionio_tpu.core import Engine, EngineParams, MetricEvaluator, AverageMetric
from predictionio_tpu.core.evaluation import Evaluation
from predictionio_tpu.storage import Storage
from predictionio_tpu.workflow import WorkflowContext, WorkflowParams, run_evaluation, run_train
from predictionio_tpu.workflow.serialization import deserialize_models, serialize_models
from predictionio_tpu.workflow.train import engine_params_of_instance, load_for_deploy
from fake_engine import (
    Algo0, AlgoParams, DataSource0, DataSource1, DataSource1Params,
    Preparator0, Serving0,
)


@pytest.fixture()
def meta(tmp_path):
    Storage.configure({
        "sources": {"DB": {"TYPE": "sqlite", "PATH": str(tmp_path / "wf.db")}},
        "repositories": {
            "METADATA": {"NAME": "pio", "SOURCE": "DB"},
            "EVENTDATA": {"NAME": "pio", "SOURCE": "DB"},
            "MODELDATA": {"NAME": "pio", "SOURCE": "DB"},
        },
    })
    yield Storage
    Storage.reset()


def engine():
    return Engine(DataSource0, Preparator0, {"a": Algo0}, Serving0)


def ep(algo_id=3):
    return EngineParams(algorithm_params_list=[("a", AlgoParams(id=algo_id))])


def test_run_train_records_instance_and_models(meta):
    instance = run_train(engine(), ep(), engine_factory="tests.fake:engine",
                         engine_variant="v1")
    assert instance.status == "COMPLETED"
    stored = meta.get_meta_data_engine_instances().get(instance.id)
    assert stored.status == "COMPLETED"
    assert stored.engine_variant == "v1"
    assert '"id": 3' in stored.algorithms_params
    blob = meta.get_model_data_models().get(instance.id)
    assert blob is not None
    models = deserialize_models(blob.models)
    assert models[0].id == 3


def test_failed_train_leaves_init(meta):
    class BoomAlgo(Algo0):
        def train(self, ctx, pd):
            raise RuntimeError("boom")

    eng = Engine(DataSource0, Preparator0, {"a": BoomAlgo}, Serving0)
    with pytest.raises(RuntimeError):
        run_train(eng, ep())
    instances = meta.get_meta_data_engine_instances().get_all()
    assert len(instances) == 1
    assert instances[0].status == "INIT"  # never deployable
    assert meta.get_meta_data_engine_instances().get_latest_completed(
        instances[0].engine_id, "1", "default") is None


def test_load_for_deploy_round_trip(meta):
    eng = engine()
    instance = run_train(eng, ep(algo_id=9))
    latest = meta.get_meta_data_engine_instances().get_latest_completed(
        instance.engine_id, "1", "default")
    assert latest is not None
    restored_ep = engine_params_of_instance(eng, latest)
    assert restored_ep.algorithm_params_list[0][1] == AlgoParams(id=9)
    result, ctx = load_for_deploy(eng, latest)
    assert result.models[0].id == 9
    pred = result.algorithms[0].predict(result.models[0],
                                        __import__("fake_engine").Query(id=1))
    assert pred.id == 9


def test_run_evaluation_records_instance(meta):
    class IdScore(AverageMetric):
        def calculate_point(self, eval_info, q, p, a):
            return p.id

    eng = Engine(DataSource1, Preparator0, {"a": Algo0}, Serving0)
    params = [EngineParams(
        data_source_params=DataSource1Params(id=1, en=1, qn=2),
        algorithm_params_list=[("a", AlgoParams(id=i))]) for i in (2, 8)]
    ev = Evaluation(engine=eng, metric=IdScore(), output_path=None)
    result = run_evaluation(ev, params, evaluation_class="MyEval")
    assert result.best_score == 8.0
    stored = meta.get_meta_data_evaluation_instances().get_completed()
    assert len(stored) == 1
    assert stored[0].evaluation_class == "MyEval"
    assert "IdScore" in stored[0].evaluator_results
    assert "8.0" in stored[0].evaluator_results_json


def test_serialize_pytree_models():
    models = [{"u": np.arange(4, dtype=np.float32), "v": [1, 2]}, None]
    blob = serialize_models(models)
    out = deserialize_models(blob)
    assert out[1] is None
    np.testing.assert_array_equal(out[0]["u"], np.arange(4, dtype=np.float32))


def test_serialize_jax_arrays_to_host():
    import jax.numpy as jnp

    blob = serialize_models([{"w": jnp.ones((2, 2))}])
    out = deserialize_models(blob)
    assert isinstance(out[0]["w"], np.ndarray)


# -- the release's write path: one pass, digest beside the write -------------

def _als_model():
    from predictionio_tpu.models.als import ALSModel

    rng = np.random.default_rng(28)
    return [ALSModel(
        user_vocab=np.array([f"u{i}" for i in range(700)]),
        item_vocab=np.array([f"i{i}" for i in range(300)]),
        U=rng.normal(size=(700, 64)).astype(np.float32),
        V=rng.normal(size=(300, 64)).astype(np.float32))]


def _jax_pytree():
    import jax.numpy as jnp

    # leaves on both sides of the pickler's 64 KiB framing limit, one
    # Fortran-ordered, one that is not contiguous at all
    return [{"w": jnp.arange(40_000, dtype=jnp.float32).reshape(200, 200),
             "b": jnp.ones((8,)),
             "f": np.asfortranarray(np.arange(30_000.).reshape(150, 200)),
             "s": np.arange(60_000, dtype=np.float32)[::2]}]


def _list_with_none():
    return [{"u": np.arange(50_000, dtype=np.float32), "v": [1, 2]}, None,
            {"tiny": np.zeros(3)}]


@pytest.mark.parametrize("make", [_als_model, _jax_pytree, _list_with_none])
def test_streamed_release_is_serialize_models_byte_for_byte(tmp_path, make):
    """What `run_train` streams into a file store is the stream
    `serialize_models` makes, so old and new releases are the same bytes
    under the same digest."""
    import hashlib

    from predictionio_tpu.storage.fs_models import FSModels
    from predictionio_tpu.workflow.serialization import (
        DigestingWriter, dump_models,
    )

    models = make()
    want = serialize_models(models)
    store = FSModels(str(tmp_path / "models"))
    with store.open_write("m") as f:
        with DigestingWriter(f) as out:
            dump_models(models, out)
    on_disk = (tmp_path / "models" / "pio_model_m.bin").read_bytes()
    assert on_disk == want
    assert store.get("m").models == want
    assert out.hexdigest() == hashlib.sha256(want).hexdigest()
    assert out.size == len(want)
    assert out.write_seconds > 0 and out.hash_seconds > 0
    got = deserialize_models(on_disk)
    assert [m is None for m in got] == [m is None for m in models]


# -- device leaves: fetched where the pickler meets them ----------------------

def _seqrec_model(leaf):
    """A sequence model whose weights `leaf` makes from numpy arrays."""
    from predictionio_tpu.models.seqrec import SeqRecModel, SeqRecParams

    rng = np.random.default_rng(34)
    params = {"emb": rng.normal(size=(300, 64)).astype(np.float32),
              "layers": [{"wq": rng.normal(size=(64, 64)).astype(np.float32),
                          "bias": np.zeros(8, np.float32)}
                         for _ in range(2)],
              "head": rng.normal(size=(64, 300)).astype(np.float32)}
    return SeqRecModel(
        item_vocab=np.asarray([f"i{i}" for i in range(299)], dtype=object),
        params={k: ([{n: leaf(w) for n, w in layer.items()} for layer in v]
                    if isinstance(v, list) else leaf(v))
                for k, v in params.items()},
        hyper=SeqRecParams(d_model=64, n_heads=2), record={"loss": [1.5]})


def _pytree_model(leaf):
    return {"w": leaf(np.arange(40_000, dtype=np.float32).reshape(200, 200)),
            "b": leaf(np.ones(8, np.float32)), "n": 3}


def _on_device(array):
    import jax.numpy as jnp

    return jnp.asarray(array)


def _as_read(array):
    """numpy as a trainer hands over what it read from a device."""
    return np.asarray(_on_device(array))


def _by_dump_models(models, tmp_path):
    import io

    from predictionio_tpu.workflow.serialization import dump_models

    buf = io.BytesIO()
    fetched = dump_models(models, buf)
    return buf.getvalue(), fetched.device_bytes


def _by_serialize_models(models, tmp_path):
    return serialize_models(models), None


def _by_persist(models, tmp_path):
    """`_persist` into a file store: (the stored bytes, the bytes the
    device counter gained), the returned digest held to the bytes."""
    import hashlib

    from predictionio_tpu.obs.registry import default_registry
    from predictionio_tpu.workflow.train import _persist

    Storage.configure({
        "sources": {"DB": {"TYPE": "sqlite", "PATH": str(tmp_path / "p.db")},
                    "M": MODEL_STORES["localfs"](tmp_path)},
        "repositories": {
            "METADATA": {"NAME": "pio", "SOURCE": "DB"},
            "EVENTDATA": {"NAME": "pio", "SOURCE": "DB"},
            "MODELDATA": {"NAME": "pio", "SOURCE": "M"},
        },
    })
    try:
        before = _persist_series()["device"]
        digest, size = _persist("m", models)
        stored = Storage.get_model_data_models().get("m").models
    finally:
        Storage.reset()
    assert (digest, size) == (hashlib.sha256(stored).hexdigest(), len(stored))
    return stored, _persist_series()["device"] - before


@pytest.mark.parametrize("route", [_by_dump_models, _by_serialize_models,
                                   _by_persist])
@pytest.mark.parametrize("make", [_seqrec_model, _pytree_model])
def test_device_leaves_dump_to_the_host_leaves_stream(tmp_path, make, route):
    """A model with `jax.Array` leaves is the byte stream, and so the
    sha256, of the same model with numpy leaves, whichever way the
    release is written; it loads with numpy leaves and no device; and
    the device's share of it is counted."""
    import jax

    want, none_fetched = route([make(_as_read), None], tmp_path)
    got, fetched = route([make(_on_device), None], tmp_path)
    assert got == want
    weights = [x for x in jax.tree.leaves(
        getattr(make(_on_device), "params", make(_on_device)))
        if isinstance(x, jax.Array)]
    if fetched is not None:
        assert none_fetched == 0
        assert fetched == sum(x.nbytes for x in weights) > 0
    model, retrain = deserialize_models(got)
    assert retrain is None
    leaves = jax.tree.leaves(getattr(model, "params", model))
    assert sum(isinstance(x, np.ndarray) for x in leaves) == len(weights)
    assert not any(isinstance(x, jax.Array) for x in leaves)


@pytest.mark.parametrize("make", [
    _als_model, _list_with_none,
    lambda: [_seqrec_model(np.array), None],
    lambda: [_seqrec_model(_as_read)]],
    ids=["als", "list_with_none", "seqrec_writable", "seqrec_read_only"])
def test_a_model_of_host_arrays_dumps_to_plain_pickle_bytes(make):
    """No device array, no other path: the stream is what
    `pickle.dumps` at the highest protocol makes of the payload (what
    every release before device leaves was), and nothing was fetched."""
    import io
    import pickle

    from predictionio_tpu.workflow.serialization import (
        RETRAIN_ON_DEPLOY, dump_models,
    )

    models = make()
    buf = io.BytesIO()
    fetched = dump_models(models, buf)
    assert buf.getvalue() == pickle.dumps(
        [RETRAIN_ON_DEPLOY if m is None else m for m in models],
        protocol=pickle.HIGHEST_PROTOCOL)
    assert fetched == (0, 0.0)


@pytest.mark.parametrize("dtype, shape", [
    ("float32", (33, 5)), ("bfloat16", (33, 5)), ("int32", (7,)),
    ("float32", ()), ("bfloat16", ()), ("float32", (0, 4))])
def test_device_leaves_of_every_dtype_round_trip(dtype, shape):
    """float32, bfloat16 and int32 leaves, a 0-d one and an empty one:
    out of the blob each is numpy with its dtype, shape and values."""
    import jax.numpy as jnp

    values = (np.arange(int(np.prod(shape)), dtype=np.float32) - 3
              ).reshape(shape)
    leaf = jnp.asarray(values, dtype=dtype)
    blob = serialize_models([{"leaf": leaf, "twice": [leaf, leaf]}])
    host = np.asarray(leaf)
    assert blob == serialize_models([{"leaf": host, "twice": [host, host]}])
    out = deserialize_models(blob)[0]
    for got in (out["leaf"], *out["twice"]):
        assert isinstance(got, np.ndarray)
        assert (got.dtype, got.shape) == (leaf.dtype, shape)
        np.testing.assert_array_equal(got.astype(np.float32),
                                      np.asarray(leaf, dtype=np.float32))
    assert out["twice"][0] is out["twice"][1]    # one leaf, pickled once


def test_digesting_writer_keeps_order_and_stops_its_thread():
    """Many writes of every kind the pickler makes, under a short switch
    interval: the digest is of the bytes in their order, and the hash
    thread is gone after close, also when the file's write raised."""
    import hashlib
    import io
    import pickle
    import sys
    import threading

    from predictionio_tpu.workflow.serialization import DigestingWriter

    def digest_threads():
        return [t for t in threading.enumerate()
                if t.name == "pio-release-digest"]

    rng = np.random.default_rng(7)
    chunks = [rng.integers(0, 256, size=int(n), dtype=np.uint8).tobytes()
              for n in rng.integers(1, 5000, size=400)]
    sink = io.BytesIO()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with DigestingWriter(sink) as out:
            for i, c in enumerate(chunks):
                assert out.write((c, bytearray(c), memoryview(c),
                                  pickle.PickleBuffer(c))[i % 4]) == len(c)
            with pytest.raises(ValueError):
                out.hexdigest()     # not final while the thread runs
    finally:
        sys.setswitchinterval(interval)
    whole = b"".join(chunks)
    assert sink.getvalue() == whole and out.size == len(whole)
    assert out.hexdigest() == hashlib.sha256(whole).hexdigest()
    with pytest.raises(ValueError):
        out.write(b"late")
    assert digest_threads() == []

    class Full:
        def write(self, data):
            raise OSError(28, "No space left on device")

    with pytest.raises(OSError):
        with DigestingWriter(Full()) as out:
            out.write(b"x" * 100_000)
    assert digest_threads() == []


MODEL_STORES = {
    "localfs": lambda tmp: {"TYPE": "localfs", "PATH": str(tmp / "models")},
    "sqlite": lambda tmp: {"TYPE": "sqlite", "PATH": str(tmp / "wf.db")},
}


@pytest.fixture(params=sorted(MODEL_STORES))
def model_store(tmp_path, request):
    """Metadata in sqlite, models in a file store or in a sqlite row."""
    Storage.configure({
        "sources": {"DB": {"TYPE": "sqlite", "PATH": str(tmp_path / "wf.db")},
                    "M": MODEL_STORES[request.param](tmp_path)},
        "repositories": {
            "METADATA": {"NAME": "pio", "SOURCE": "DB"},
            "EVENTDATA": {"NAME": "pio", "SOURCE": "DB"},
            "MODELDATA": {"NAME": "pio", "SOURCE": "M"},
        },
    })
    yield request.param
    Storage.reset()


def _persist_series():
    from predictionio_tpu.obs.registry import default_registry

    def read(name, how):
        metric = default_registry().get(name)
        return how(metric) if metric is not None else 0.0
    return {
        "bytes": read("pio_train_persist_bytes_total", lambda m: m.value()),
        "streamed": read("pio_train_persist_streamed_bytes_total",
                         lambda m: m.value()),
        "writes": read("pio_train_persist_write_seconds",
                       lambda m: m.count()),
        "write_s": read("pio_train_persist_write_seconds",
                        lambda m: m.sum_()),
        "hashes": read("pio_train_persist_hash_seconds",
                       lambda m: m.count()),
        "hash_s": read("pio_train_persist_hash_seconds", lambda m: m.sum_()),
        "device": read("pio_train_persist_device_bytes_total",
                       lambda m: m.value()),
        "waits": read("pio_train_persist_fetch_wait_seconds",
                      lambda m: m.count()),
    }


def test_release_digest_and_size_are_of_the_stored_bytes(model_store):
    """After `run_train` the manifest describes exactly what the store
    holds, on a store that streams and on one that keeps a row; the
    streamed share of the persisted bytes says which it was."""
    import hashlib

    before = _persist_series()
    instance = run_train(engine(), ep(), engine_factory="tests.fake:engine")
    assert instance.status == "COMPLETED"
    stored = Storage.get_model_data_models().get(instance.id).models
    assert stored == serialize_models(deserialize_models(stored))
    releases = [r for r in Storage.get_meta_data_releases().get_all()
                if r.instance_id == instance.id]
    assert len(releases) == 1
    assert releases[0].model_digest == hashlib.sha256(stored).hexdigest()
    assert releases[0].model_size_bytes == len(stored) > 0
    after = _persist_series()
    assert after["bytes"] - before["bytes"] == len(stored)
    streamed = after["streamed"] - before["streamed"]
    assert streamed == (len(stored) if model_store == "localfs" else 0)
    assert after["writes"] - before["writes"] == 1
    assert after["hashes"] - before["hashes"] == 1
    assert after["write_s"] > before["write_s"]
    assert after["hash_s"] > before["hash_s"]
    # a model of host values: nothing came from a device, one sample all
    # the same
    assert after["device"] == before["device"]
    assert after["waits"] - before["waits"] == 1


class _SecondLeafBreaks:
    def __reduce__(self):
        raise RuntimeError("boom at the second leaf")


def _deleted_device_leaf():
    """A device array whose host copy raises when the pickler asks."""
    import jax.numpy as jnp

    leaf = jnp.ones(1000)
    leaf.delete()
    return leaf


@pytest.mark.parametrize("second, message", [
    (_SecondLeafBreaks, "second leaf"),
    (_deleted_device_leaf, "deleted")])
def test_failed_pickle_leaves_no_blob_no_release_and_init(
        model_store, tmp_path, second, message):
    """A train that fails while pickling, after the first leaf is
    already in the store's temporary file, leaves nothing under the
    model's name, the instance INIT and no release: a leaf whose
    `__reduce__` raises, and a device leaf whose host copy does."""
    class TwoLeafAlgo(Algo0):
        def train(self, ctx, pd):
            return {"first": np.ones(100_000, dtype=np.float32),
                    "second": second()}

    eng = Engine(DataSource0, Preparator0, {"a": TwoLeafAlgo}, Serving0)
    with pytest.raises(RuntimeError, match=message):
        run_train(eng, ep())
    instances = Storage.get_meta_data_engine_instances().get_all()
    assert [i.status for i in instances] == ["INIT"]
    assert Storage.get_model_data_models().get(instances[0].id) is None
    assert Storage.get_meta_data_releases().get_all() == []
    if model_store == "localfs":
        import os
        assert os.listdir(tmp_path / "models") == []


def test_record_release_takes_a_blob_or_its_digest(meta):
    """`record_release` keeps taking the blob (the orchestrator, the
    load tests) and takes a digest and size from a caller that has them."""
    import hashlib

    from predictionio_tpu.deploy.releases import record_release

    instance = run_train(engine(), ep())
    by_blob = record_release(instance, train_seconds=0.1, blob=b"abc")
    assert by_blob.model_digest == hashlib.sha256(b"abc").hexdigest()
    assert by_blob.model_size_bytes == 3
    by_digest = record_release(instance, train_seconds=0.1,
                               model_digest="d" * 64, model_size_bytes=7)
    assert (by_digest.model_digest, by_digest.model_size_bytes) \
        == ("d" * 64, 7)
    neither = record_release(instance, train_seconds=0.1)
    assert (neither.model_digest, neither.model_size_bytes) == ("", 0)


def test_workflow_context_mesh(mesh8):
    ctx = WorkflowContext.create(
        mode="Training",
        workflow_params=WorkflowParams(
            runtime_conf={"mesh_shape": "4,2", "mesh_axes": "data,model"}))
    assert ctx.mesh.axis_names == ("data", "model")
    assert ctx.mesh.devices.shape == (4, 2)
    assert ctx.num_devices == 8
    ctx1 = WorkflowContext.create(mode="Serving")
    assert ctx1.local_mesh().devices.size == 1
