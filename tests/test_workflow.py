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


def _models_in_files(tmp_path):
    """Metadata in sqlite, models in a local file store (the caller
    resets `Storage`)."""
    Storage.configure({
        "sources": {"DB": {"TYPE": "sqlite", "PATH": str(tmp_path / "p.db")},
                    "M": MODEL_STORES["localfs"](tmp_path)},
        "repositories": {
            "METADATA": {"NAME": "pio", "SOURCE": "DB"},
            "EVENTDATA": {"NAME": "pio", "SOURCE": "DB"},
            "MODELDATA": {"NAME": "pio", "SOURCE": "M"},
        },
    })


def _by_persist(models, tmp_path):
    """`_persist` into a file store: (the stored bytes, the bytes the
    device counter gained), the returned digest held to the bytes."""
    import hashlib

    from predictionio_tpu.obs.registry import default_registry
    from predictionio_tpu.workflow.train import _persist

    _models_in_files(tmp_path)
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


# -- the write accounted from inside, by the thread that spends it ----------

class _SlowSha:
    """A sha256 whose every update takes `seconds` longer."""

    def __init__(self, seconds):
        import hashlib

        self._sha, self._seconds = hashlib.sha256(), seconds

    def update(self, view):
        import time

        time.sleep(self._seconds)
        self._sha.update(view)

    def hexdigest(self):
        return self._sha.hexdigest()


class _SlowFile:
    """A store whose write takes `seconds`, and `stall` more at the call
    of index `at`."""

    def __init__(self, seconds, at=None, stall=0.0):
        self.calls = 0
        self._seconds, self._at, self._stall = seconds, at, stall

    def write(self, view):
        import time

        time.sleep(self._seconds + (self._stall if self.calls == self._at
                                    else 0.0))
        self.calls += 1


@pytest.mark.parametrize("slow", ["store", "hash"])
def test_digesting_writer_says_which_thread_paces_the_other(slow):
    """Each thread's clocks: a slow store is `write_seconds` and, at the
    one call that stalled, `slowest_write_seconds` with that call's bytes
    and offset, and the digest thread starves while the writer never
    waits for room in the queue; a slow hash is the reverse, the writer
    blocked in `put` once it is `_QUEUE_DEPTH` buffers ahead. The digest
    thread's two sums are its whole life."""
    import hashlib
    import io

    from predictionio_tpu.workflow.serialization import DigestingWriter

    chunks = [bytes([i]) * (1000 + i) for i in range(12)]
    sink = _SlowFile(0.004, at=7, stall=0.03) if slow == "store" \
        else io.BytesIO()
    with DigestingWriter(sink) as out:
        if slow == "hash":
            out._sha = _SlowSha(0.004)
        for c in chunks:
            out.write(c)
    assert out.hexdigest() == hashlib.sha256(b"".join(chunks)).hexdigest()
    if slow == "store":
        assert 12 * 0.004 + 0.03 <= out.write_seconds < 0.2
        assert 0.034 <= out.slowest_write_seconds < 0.1
        assert out.slowest_write_bytes == len(chunks[7])
        assert out.slowest_write_offset == sum(map(len, chunks[:7]))
        assert out.put_wait_seconds < 0.03
        assert out.starved_seconds > 12 * 0.004
        assert out.hash_seconds < 0.03
    else:
        assert out.hash_seconds >= 12 * 0.004
        # eight of the twelve puts found the queue full
        assert out.put_wait_seconds > 4 * 0.004
        assert out.starved_seconds < 0.03
        assert out.write_seconds < 0.03
        assert out.slowest_write_seconds <= out.write_seconds
    assert out.hash_seconds + out.starved_seconds <= out.life_seconds
    assert out.life_seconds - out.hash_seconds - out.starved_seconds < 0.02


def test_a_collection_on_the_writing_thread_counts_once():
    """`CollectorPauses` hears the collector on the thread that entered
    it and on no other, takes `gc.callbacks` back as it was, and a clock
    with a collection inside it (a leaf's wait, the store's write) leaves
    the pause out."""
    import gc
    import threading
    import time

    from predictionio_tpu.workflow.serialization import (
        CollectorPauses, DigestingWriter, dump_models,
    )

    hooks = list(gc.callbacks)
    pauses = CollectorPauses()

    class Collecting:
        def write(self, view):
            time.sleep(0.002)
            gc.collect()

    class Leaf:
        def __reduce__(self):
            gc.collect()
            return (dict, ())

    with DigestingWriter(Collecting(), pauses) as out:
        with pauses:
            assert len(gc.callbacks) == len(hooks) + 1
            other = threading.Thread(target=gc.collect)
            other.start()
            other.join()
            assert pauses.seconds == 0.0
            t0 = time.perf_counter()
            dump_models([{"leaves": [Leaf() for _ in range(3)],
                          "w": np.arange(100_000.)}], out, pauses)
            wall = time.perf_counter() - t0
    assert gc.callbacks == hooks
    assert pauses.seconds > 0
    # every write collected: without the subtraction the write's clock
    # would hold nearly all of the collector's seconds
    assert out.write_seconds + out.put_wait_seconds + pauses.seconds \
        <= wall + 1e-4
    gc.collect()
    assert pauses.seconds + out.write_seconds < wall     # nothing hooked now


def _persist_under_a_job(tmp_path, monkeypatch, models):
    """`_persist` into a file store under a job's trace: (the record
    `observe_persist` was handed, seconds and count by span of the job's
    own registry, the job's span rows)."""
    from predictionio_tpu.obs import tracing
    from predictionio_tpu.obs.registry import MetricsRegistry
    from predictionio_tpu.workflow import train

    records = []
    observe = train.observe_persist
    monkeypatch.setattr(train, "observe_persist",
                        lambda r: (records.append(r), observe(r)))
    _models_in_files(tmp_path)
    own = MetricsRegistry()
    try:
        with tracing.adopt("job", registry=own) as trace:
            with tracing.span("train_persist"):
                train._persist("m", models)
    finally:
        Storage.reset()
    hist = own.get("pio_span_duration_seconds")
    spans = {labels["span"]: (hist.sum_(**labels), hist.count(**labels))
             for labels, _ in hist.samples()}
    return (records[0] if records else None), spans, trace.spans


PICKLING_SUM = ("persist_leaf_wait", "persist_put_wait",
                "persist_store_write", "persist_gc", "persist_walk")


def test_a_persists_parts_add_up_to_its_dump(tmp_path, monkeypatch):
    """The pickling thread's five parts are `persist_dump`'s seconds to a
    millisecond (`persist_walk` is what the four clocks leave), the
    slowest write is one of the store's calls, the digest thread's two
    parts are its life; each of the eight is one sample a persist of the
    job's span histogram, a part of 0 too, and one row of the job's
    record: the pickling thread's six under `persist_dump`, the digest
    thread's two under `train_persist`."""
    record, spans, rows = _persist_under_a_job(
        tmp_path, monkeypatch, [_seqrec_model(_on_device)])
    parts = PICKLING_SUM + ("persist_slowest_write", "persist_hash",
                            "persist_hash_starved")
    assert set(record.parts) == set(parts)
    for name in parts + ("persist_dump", "persist_close"):
        assert spans[name][1] == 1, name
        assert spans[name][0] >= 0
    assert spans["persist_gc"][0] == record.parts["persist_gc"] >= 0
    dump = spans["persist_dump"][0]
    assert sum(record.parts[n] for n in PICKLING_SUM) \
        == pytest.approx(record.dump_seconds, abs=1e-9)
    assert abs(sum(spans[n][0] for n in PICKLING_SUM) - dump) < 1e-3
    assert 0 < spans["persist_slowest_write"][0] \
        <= spans["persist_store_write"][0]
    assert record.slowest_write_bytes > 0
    assert 0 <= record.slowest_write_offset < record.size
    assert spans["persist_leaf_wait"][0] > 0 and record.device_bytes > 0
    life = record.digest_life_seconds
    assert abs(spans["persist_hash"][0] + spans["persist_hash_starved"][0]
               - life) < max(0.005, 0.01 * life)
    parents = {r.name: r.parent.name for r in rows if r.parent is not None}
    for name in PICKLING_SUM + ("persist_slowest_write",):
        assert parents[name] == "persist_dump"
    for name in ("persist_hash", "persist_hash_starved"):
        assert parents[name] == "train_persist"
    line = record.line()
    for word in ("leaf_wait", "put_wait", "store_write", "gc", "walk",
                 "slowest write", "hash", "starved",
                 f"{record.slowest_write_bytes} bytes at offset "
                 f"{record.slowest_write_offset}"):
        assert word in line


@pytest.mark.parametrize("missing", ["meminfo", "getrusage"])
def test_a_persist_without_the_machines_state_publishes_no_series(
        tmp_path, monkeypatch, missing):
    """On a platform without `/proc/meminfo` the dirty series gains
    nothing, without `getrusage` the two fault counters gain nothing,
    and the persist raises nothing and publishes the rest."""
    import builtins

    from predictionio_tpu.obs.registry import default_registry
    from predictionio_tpu.workflow import instrument

    def reading():
        reg = default_registry()
        dirty = reg.get("pio_train_persist_dirty_bytes")
        faulted = reg.get("pio_train_persist_faulted_bytes_total")
        major = reg.get("pio_train_persist_major_faults_total")
        return {"dirty": dirty.count() if dirty else 0,
                "faulted": faulted.value() if faulted else 0,
                "major": major.value() if major else 0,
                "bytes": instrument.persist_bytes().value()}

    # both there (Linux): each series gains its sample
    before = reading()
    record, _, _ = _persist_under_a_job(tmp_path, monkeypatch, _als_model())
    seen = reading()
    if instrument.unwritten_bytes() is not None:
        assert seen["dirty"] == before["dirty"] + 1
        assert record.dirty_bytes >= 0
    if instrument.process_faults() is not None:
        assert seen["faulted"] >= before["faulted"]
        assert record.faulted_bytes >= 0 and record.major_faults >= 0

    if missing == "meminfo":
        real_open = builtins.open

        def no_proc(path, *a, **kw):
            if path == instrument.MEMINFO:
                raise FileNotFoundError(path)
            return real_open(path, *a, **kw)
        monkeypatch.setattr(builtins, "open", no_proc)
        assert instrument.unwritten_bytes() is None
    else:
        import resource

        def no_rusage(who):
            raise OSError("no getrusage here")
        monkeypatch.setattr(resource, "getrusage", no_rusage)
        assert instrument.process_faults() is None
    record, spans, _ = _persist_under_a_job(tmp_path, monkeypatch,
                                            _als_model())
    after = reading()
    assert after["bytes"] == seen["bytes"] + record.size
    assert spans["persist_walk"][1] == 1
    if missing == "meminfo":
        assert record.dirty_bytes is None
        assert after["dirty"] == seen["dirty"]
    else:
        assert record.faulted_bytes is None and record.major_faults is None
        assert (after["faulted"], after["major"]) \
            == (seen["faulted"], seen["major"])
        assert "faulted None bytes" in record.line()


@pytest.mark.parametrize("breaks", [False, True])
def test_the_collectors_hook_is_gone_after_a_persist(tmp_path, monkeypatch,
                                                     breaks):
    """`gc.callbacks` is what it was before a persist, one whose pickle
    raised included; a failed persist publishes no part."""
    import gc

    hooks = list(gc.callbacks)
    models = [{"first": np.ones(50_000, np.float32),
               "second": _SecondLeafBreaks() if breaks else 2}]
    if breaks:
        with pytest.raises(RuntimeError, match="second leaf"):
            _persist_under_a_job(tmp_path, monkeypatch, models)
    else:
        record, spans, _ = _persist_under_a_job(tmp_path, monkeypatch,
                                                models)
        assert spans["persist_gc"][1] == 1 and record.size > 200_000
    assert gc.callbacks == hooks


def test_a_releases_bytes_are_pinned():
    """One release's size and sha256, as the tree before ISSUE 49 wrote
    it (pickle protocol 5 of numpy arrays, in and out of band: the
    literal is this numpy's and this Python's): the accounting changed
    no byte and no order of the writes."""
    import hashlib
    import io

    from predictionio_tpu.workflow.serialization import (
        DigestingWriter, dump_models,
    )

    rng = np.random.default_rng(49)
    models = [{"w": rng.normal(size=(300, 64)).astype(np.float32),
               "table": [np.arange(70_000, dtype=np.int32),
                         _on_device(np.linspace(0, 1, 40_000,
                                                dtype=np.float32))],
               "names": np.asarray(["a", "b"], dtype=object), "n": 49},
              None]
    sink = io.BytesIO()
    with DigestingWriter(sink) as out:
        dump_models(models, out)
    assert out.hexdigest() == hashlib.sha256(sink.getvalue()).hexdigest()
    assert (out.size, out.hexdigest()) == PINNED_RELEASE


PINNED_RELEASE = (
    517274,
    "486973847dbfde6795f754f1987db48c6fe7f94550f43565eada32a808855a6a")


def test_the_persist_probe_runs_unedited_on_this_writer(tmp_path):
    """`benchmarks/tools/persist_probe.py` (a builder's tool, not this
    PR's to edit) reads the writer's `write_seconds`, `hash_seconds` and
    `size` and subclasses `_ReleasePickler` for its `wait_seconds` and
    `device_bytes`: its `--tiny` rehearsal runs to its last line and its
    device and host routes agree on the digest."""
    import json
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "TMPDIR": str(tmp_path)}
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "tools",
                                      "persist_probe.py"),
         "--tiny", "--rounds", "1", "--ahead", "1"],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=280)
    assert done.returncode == 0, done.stderr[-2000:]
    readings = json.loads(done.stdout.strip().splitlines()[-1])["readings"]
    persists = [r for r in readings
                if str(r.get("reading", "")).startswith("persist_")]
    assert len(persists) == 4
    assert len({r["digest"] for r in persists}) == 1
    for r in persists:
        assert r["bytes"] > 0 and r["write_s"] > 0 and r["hash_s"] > 0
    assert all(r["device_bytes"] > 0 for r in persists
               if r["reading"] != "persist_host")


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
