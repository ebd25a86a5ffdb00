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


class _SecondLeafBreaks:
    def __reduce__(self):
        raise RuntimeError("boom at the second leaf")


def test_failed_pickle_leaves_no_blob_no_release_and_init(model_store,
                                                          tmp_path):
    """A train that fails while pickling, after the first leaf is
    already in the store's temporary file, leaves nothing under the
    model's name, the instance INIT and no release."""
    class TwoLeafAlgo(Algo0):
        def train(self, ctx, pd):
            return {"first": np.ones(100_000, dtype=np.float32),
                    "second": _SecondLeafBreaks()}

    eng = Engine(DataSource0, Preparator0, {"a": TwoLeafAlgo}, Serving0)
    with pytest.raises(RuntimeError, match="second leaf"):
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
