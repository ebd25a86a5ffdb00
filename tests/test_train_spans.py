"""Timestamped, parented spans (obs/tracing.py), the spans at the layer
boundaries of `pio train`, their mirror in the profiler's trace, and the
compiler's own compile events (obs/jax_stats.py)."""

import time

import numpy as np
import pytest

from predictionio_tpu.obs import jax_stats, trace_context, tracing
from predictionio_tpu.obs.registry import MetricsRegistry, default_registry

#: table B of ISSUE 24: every span of a recommendation train, with the
#: span that encloses it (None = the job itself)
TRAIN_SPANS = {
    "train_begin": None,
    "train_read": None,
    "ingest_digest": "train_read",
    "ingest_scan": "train_read",
    "ingest_decode": "train_read",
    "train_prepare": None,
    "train_algorithm": None,
    "train_id_assign": "train_algorithm",
    "als_pack": "train_algorithm",
    "als_put": "train_algorithm",
    "als_solve": "train_algorithm",
    "als_fetch": "train_algorithm",
    "train_persist": None,
    "persist_models": "train_persist",
    "persist_dump": "train_persist",
    # ISSUE 49: `persist_dump` from inside, a sum a persist each, timed by
    # the thread that spends it (`tracing.timed_stage`, no annotation);
    # the digest thread's two are final after the join
    "persist_leaf_wait": "persist_dump",
    "persist_put_wait": "persist_dump",
    "persist_store_write": "persist_dump",
    "persist_slowest_write": "persist_dump",
    "persist_gc": "persist_dump",
    "persist_walk": "persist_dump",
    "persist_hash": "train_persist",
    "persist_hash_starved": "train_persist",
    "persist_close": "train_persist",
    "persist_commit": "train_persist",
    "persist_instance_update": "train_persist",
    "train_release": None,
}


def span_count(name, registry=None):
    hist = (registry or default_registry()).get("pio_span_duration_seconds")
    return hist.count(span=name) if hist is not None else 0


# -- the span record ----------------------------------------------------------

def test_span_records_times_and_the_enclosing_span():
    tokens, trace = tracing.start_trace("rid")
    try:
        with tracing.span("outer"):
            with tracing.span("first"):
                time.sleep(0.002)
            with tracing.span("second"):
                pass
    finally:
        tracing.reset_trace(tokens)
    outer, first, second = trace.spans
    assert [s.name for s in trace.spans] == ["outer", "first", "second"]
    for s in trace.spans:
        assert s.start_ns <= s.end_ns
    assert outer.parent is None
    assert first.parent is outer and second.parent is outer
    assert outer.start_ns <= first.start_ns <= first.end_ns \
        <= second.start_ns <= second.end_ns <= outer.end_ns
    assert first.seconds >= 0.002


def test_self_seconds_of_a_parent_with_two_children():
    trace = tracing.Trace("rid")
    ms = 1_000_000
    parent = tracing.Span("parent", 0, 100 * ms, None)
    trace.spans += [parent,
                    tracing.Span("child", 10 * ms, 30 * ms, parent),
                    tracing.Span("child", 50 * ms, 90 * ms, parent)]
    own = trace.self_seconds()
    assert own["parent"] == pytest.approx(0.040)
    assert own["child"] == pytest.approx(0.060)
    assert trace.spans_by_name() == {"parent": pytest.approx(0.100),
                                     "child": pytest.approx(0.060)}


def test_same_name_nesting_is_counted_once():
    tokens, trace = tracing.start_trace("rid")
    try:
        with tracing.span("work"):
            with tracing.span("work"):
                time.sleep(0.002)
    finally:
        tracing.reset_trace(tokens)
    outer, inner = trace.spans
    assert inner.parent is outer
    assert trace.spans_by_name() == {"work": pytest.approx(outer.seconds)}
    assert trace.self_seconds()["work"] == pytest.approx(outer.seconds)


def test_a_hop_hands_its_timeline_to_the_flight_recorder():
    trace_context.recorder().clear()
    with tracing.carried(None, "hop"):
        with tracing.span("a"):
            with tracing.span("b"):
                pass
    record = trace_context.recorder().traces()[-1]
    assert record["name"] == "hop" and set(record["spans"]) == {"a", "b"}
    a, b = record["timeline"]
    assert (a["name"], a["parent"]) == ("a", None)
    assert (b["name"], b["parent"]) == ("b", 0)
    assert 0 <= a["start"] <= b["start"] <= b["end"] <= a["end"] \
        <= record["durationSec"] + 1e-3


def test_adopt_without_a_registry_reaches_the_default_registry():
    before = span_count("adopted_probe")
    with tracing.adopt("job"):
        with tracing.span("adopted_probe"):
            pass
    assert span_count("adopted_probe") == before + 1
    # a registry that is given still wins
    own = MetricsRegistry()
    with tracing.adopt("job", registry=own):
        with tracing.span("adopted_probe"):
            pass
    assert span_count("adopted_probe", own) == 1
    assert span_count("adopted_probe") == before + 1


# -- the train path -----------------------------------------------------------

@pytest.fixture()
def rated_app(tmp_path):
    from predictionio_tpu.data import DataMap, Event
    from predictionio_tpu.data.eventstore import clear_cache
    from predictionio_tpu.data.ingest import clear_scan_cache
    from predictionio_tpu.storage import App, Storage

    Storage.configure({
        "sources": {"DB": {"TYPE": "sqlite",
                           "PATH": str(tmp_path / "spans.db")}},
        "repositories": {
            "METADATA": {"NAME": "pio", "SOURCE": "DB"},
            "EVENTDATA": {"NAME": "pio", "SOURCE": "DB"},
            "MODELDATA": {"NAME": "pio", "SOURCE": "DB"},
        },
    })
    clear_cache()
    clear_scan_cache()
    app_id = Storage.get_meta_data_apps().insert(App(id=0, name="SpanApp"))
    store = Storage.get_events()
    store.init_channel(app_id)
    rng = np.random.default_rng(11)
    store.insert_batch([
        Event(event="rate", entity_type="user", entity_id=f"u{u}",
              target_entity_type="item", target_entity_id=f"i{i}",
              properties=DataMap({"rating": float(rng.integers(1, 6))}))
        for u in range(24) for i in range(16) if rng.random() < 0.5],
        app_id)
    yield "SpanApp"
    Storage.reset()
    clear_cache()
    clear_scan_cache()


def test_a_train_leaves_every_boundary_span_once(rated_app):
    from predictionio_tpu.engines.recommendation import (
        default_engine_params, engine,
    )
    from predictionio_tpu.workflow import run_train

    trace_context.recorder().clear()
    counts0 = {name: span_count(name) for name in TRAIN_SPANS}
    reg = default_registry()
    instance = run_train(
        engine(), default_engine_params(rated_app, rank=4, num_iterations=2),
        engine_factory="predictionio_tpu.engines.recommendation:engine")
    assert instance.status == "COMPLETED"

    # every span reached the scrape without a registry= at its call site
    for name in TRAIN_SPANS:
        assert span_count(name) == counts0[name] + 1, name
    record = [t for t in trace_context.recorder().traces()
              if t["name"] == "train"][-1]
    rows = record["timeline"]
    assert sorted(r["name"] for r in rows) == sorted(TRAIN_SPANS)
    by_name = {r["name"]: r for r in rows}
    for name, parent in TRAIN_SPANS.items():
        got = by_name[name]["parent"]
        assert (rows[got]["name"] if got is not None else None) == parent
    for parent in ("train_read", "train_algorithm"):
        children = [r for r in rows if r["parent"] is not None
                    and rows[r["parent"]]["name"] == parent]
        assert sum(c["end"] - c["start"] for c in children) \
            <= by_name[parent]["end"] - by_name[parent]["start"] + 1e-5
    # the counts taken at the same boundaries
    assert reg.get("pio_train_als_entities").value(side="user") == 24
    assert reg.get("pio_train_als_entities").value(side="item") == 16
    fill = reg.get("pio_train_als_row_fill_ratio")
    assert 0 < fill.sum_(side="user") / fill.count(side="user") <= 1
    assert reg.get("pio_train_als_put_bytes_total").value() > 0
    assert reg.get("pio_train_als_fetch_bytes_total").value() \
        >= (24 + 16) * 4 * 4
    assert reg.get("pio_train_persist_bytes_total").value() > 0
    assert reg.get("pio_ingest_decoded_rows_total").value(
        app=rated_app) > 0


def run_counts(prefix):
    runs = default_registry().get(f"{prefix}_runs_total")
    return {status: runs.value(status=status) if runs is not None else 0
            for status in ("completed", "failed")}


def test_failed_train_publishes_closed_spans(rated_app):
    """A train that fails in its algorithm has already published the
    spans that closed before it failed, and its record says `error`."""
    from fake_engine import Algo0, AlgoParams, DataSource0, Preparator0, \
        Serving0
    from predictionio_tpu.core import Engine, EngineParams
    from predictionio_tpu.workflow import run_train

    class BoomAlgo(Algo0):
        def train(self, ctx, pd):
            with tracing.span("boom_step"):
                pass
            raise RuntimeError("boom")

    closed = ("train_begin", "train_read", "train_prepare", "boom_step",
              "train_algorithm")
    never = ("train_persist", "train_release")
    counts0 = {name: span_count(name) for name in closed + never}
    runs0 = run_counts("pio_train")
    trace_context.recorder().clear()
    with pytest.raises(RuntimeError, match="boom"):
        run_train(Engine(DataSource0, Preparator0, {"a": BoomAlgo}, Serving0),
                  EngineParams(
                      algorithm_params_list=[("a", AlgoParams(id=1))]))
    for name in closed:
        assert span_count(name) == counts0[name] + 1, name
    for name in never:
        assert span_count(name) == counts0[name], name
    assert run_counts("pio_train") == {
        "completed": runs0["completed"], "failed": runs0["failed"] + 1}
    record = [t for t in trace_context.recorder().traces()
              if t["name"] == "train"][-1]
    assert record["status"] == "error"
    rows = {r["name"]: r for r in record["timeline"]}
    assert set(rows) == set(closed)
    assert record["timeline"][rows["boom_step"]["parent"]]["name"] \
        == "train_algorithm"


def test_evaluation_run_records_spans(rated_app):
    """`run_evaluation` is one job too: the sweep's spans land on its
    trace and in the scrape, and the run counts once by outcome."""
    from predictionio_tpu.core import Evaluation
    from predictionio_tpu.core.params import EngineParams
    from predictionio_tpu.engines.recommendation import (
        AlgorithmParams, DataSourceParams, PrecisionAtK, engine,
    )
    from predictionio_tpu.workflow import run_evaluation

    params = [EngineParams(
        data_source_params=DataSourceParams(
            app_name=rated_app, eval_params={"kFold": 2, "queryNum": 3}),
        algorithm_params_list=[("als", AlgorithmParams(
            rank=r, num_iterations=2))]) for r in (3, 4)]
    sweep_spans = ("eval_split", "eval_build", "eval_data_put",
                   "eval_train_group", "eval_metrics")
    counts0 = {name: span_count(name) for name in sweep_spans}
    runs0 = run_counts("pio_eval")
    trace_context.recorder().clear()
    result = run_evaluation(
        Evaluation(engine=engine(), metric=PrecisionAtK(k=3),
                   output_path=None), params)
    assert result.sweep["mode"] == "batched"
    for name in sweep_spans:
        assert span_count(name) > counts0[name], name
    assert run_counts("pio_eval") == {
        "completed": runs0["completed"] + 1, "failed": runs0["failed"]}
    record = [t for t in trace_context.recorder().traces()
              if t["name"] == "evaluate"][-1]
    assert record["status"] == "ok"
    assert set(sweep_spans) <= set(record["spans"])


# -- the steps inside a model's build ----------------------------------------

def build_naive_bayes(monkeypatch, mesh8):
    from predictionio_tpu.models import naive_bayes

    rng = np.random.default_rng(29)
    X = rng.poisson(1.0, size=(64, 9)).astype(np.float32)
    labels = np.where(rng.random(64) < 0.5, "a", "b")
    host = naive_bayes.train_multinomial_nb(X, labels)
    # the size gate routes a test-sized X to the host counter
    monkeypatch.setattr(naive_bayes, "DEVICE_MIN_SIZE", 0)
    model = naive_bayes.train_multinomial_nb(X, labels)
    # the device's one-hot matmul counts what the host counted
    np.testing.assert_allclose(model.log_prob, host.log_prob, atol=1e-6)
    np.testing.assert_array_equal(model.predict(X), host.predict(X))


def build_cooccurrence(monkeypatch, mesh8):
    from predictionio_tpu.models.cooccurrence import (
        cooccurrence_topn, distinct_pairs,
    )

    rng = np.random.default_rng(29)
    users, items = distinct_pairs(rng.integers(0, 30, 200),
                                  rng.integers(0, 20, 200))
    counts, _ = cooccurrence_topn(mesh8, users, items, 30, 20, 4)
    assert counts.shape == (20, 4) and counts.max() > 0


@pytest.mark.parametrize("name, build", [
    ("nb_compact", build_naive_bayes),
    ("nb_transfer", build_naive_bayes),
    ("incidence_build", build_cooccurrence),
    ("incidence_transfer", build_cooccurrence),
])
def test_model_build_steps_are_spans(name, build, monkeypatch, mesh8):
    """The host steps of a model's device path (compaction and upload of
    naive Bayes' X, the cooccurrence incidence matrix) are spans of the
    job that runs them: under the caller's open span, in the scrape."""
    own = MetricsRegistry()
    with tracing.adopt("job", registry=own) as trace:
        with tracing.span("caller"):
            build(monkeypatch, mesh8)
    step, = [s for s in trace.spans if s.name == name]
    assert step.parent.name == "caller"
    assert 0 <= step.start_ns <= step.end_ns
    assert span_count(name, own) == 1
    assert trace.spans_by_name()[name] <= trace.spans_by_name()["caller"]


# -- the profiler's trace, and the compiler's own events ---------------------

def single_mesh():
    import jax
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:1]), axis_names=("data",))


def rating_set(nnz, n_users=48, n_items=24, seed=5):
    """`nnz` distinct (user, item) pairs with ratings, packed two to a
    row: the padded row count follows nnz, the other dims do not."""
    from predictionio_tpu.models.als import ALSData

    rng = np.random.default_rng(seed)
    cells = rng.choice(n_users * n_items, size=nnz, replace=False)
    users = np.concatenate([np.arange(n_users), cells // n_items])
    items = np.concatenate([np.arange(n_users) % n_items, cells % n_items])
    ratings = rng.integers(1, 6, len(users)).astype(np.float32)
    return ALSData.build(users.astype(np.int32), items.astype(np.int32),
                         ratings, n_users, n_items, 1, row_len=2)


def test_spans_are_annotations_in_a_profiler_capture(tmp_path):
    import glob
    import threading

    from jax.profiler import ProfileData

    from predictionio_tpu.models.als import ALSParams, train_als
    from predictionio_tpu.obs import profiler

    mesh, data = single_mesh(), rating_set(100)
    params = ALSParams(rank=4, num_iterations=2)
    train_als(mesh, data, params)                  # compile outside
    out: dict = {}
    capture = threading.Thread(target=lambda: out.update(
        profiler.capture(0.5, str(tmp_path / "prof"))))
    capture.start()
    deadline = time.monotonic() + 30
    while capture.is_alive() and time.monotonic() < deadline:
        train_als(mesh, data, params)
    capture.join(timeout=30)
    assert not capture.is_alive() and out["traceDir"]
    files = glob.glob(f"{out['traceDir']}/plugins/profile/*/*.xplane.pb")
    assert files
    host_events = {
        e.name for plane in ProfileData.from_file(files[-1]).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events}
    assert "pio:als_solve" in host_events
    assert "pio:als_fetch" in host_events
    # the Python tracer is off: no event per Python call of the host loop
    assert not any(name.startswith("$") for name in host_events)


def test_a_capture_names_the_preamble_and_keeps_persist_dump_whole(
        rated_app, tmp_path):
    """A train inside a capture: `pio:train_begin` names `run_train`'s
    stretch before the read, `pio:persist_dump` is there, and none of
    its eight parts is an annotation: they are sums a persist, timed by
    the thread that spent them, and an annotation a buffer (or any on
    the digest thread) would cut the one gap of a capture into pieces."""
    import glob
    import threading

    from jax.profiler import ProfileData

    from predictionio_tpu.engines.recommendation import (
        default_engine_params, engine,
    )
    from predictionio_tpu.obs import profiler
    from predictionio_tpu.workflow import run_train

    def train():
        return run_train(
            engine(),
            default_engine_params(rated_app, rank=4, num_iterations=2),
            engine_factory="predictionio_tpu.engines.recommendation:engine")

    train()                                        # compile outside
    before = span_count("train_begin")
    out: dict = {}
    capture = threading.Thread(target=lambda: out.update(
        profiler.capture(0.5, str(tmp_path / "prof"))))
    capture.start()
    deadline = time.monotonic() + 30
    while capture.is_alive() and time.monotonic() < deadline:
        train()
    capture.join(timeout=30)
    assert not capture.is_alive() and out["traceDir"]
    assert span_count("train_begin") > before
    files = glob.glob(f"{out['traceDir']}/plugins/profile/*/*.xplane.pb")
    host_events = {
        e.name for plane in ProfileData.from_file(files[-1]).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events}
    assert {"pio:train_begin", "pio:persist_dump",
            "pio:train_persist"} <= host_events
    parts = {name for name, parent in TRAIN_SPANS.items()
             if name.startswith("persist_")
             and (parent == "persist_dump" or "hash" in name)}
    assert len(parts) == 8
    assert not {f"pio:{name}" for name in parts} & host_events


def test_a_silent_retrace_is_counted_and_kept_out_of_the_half_sweeps():
    from predictionio_tpu.models.als import (
        TRAIN_FAMILY, ALSParams, train_als,
    )

    reg = default_registry()
    mesh = single_mesh()
    # a worker that compiled MAX_COMPILE_FUNS other functions before this
    # test folds every new name into "other": this one's label is kept
    jax_stats.listen_to_compiler()
    jax_stats._compiler_events._funs.add("jit(train)")
    params = ALSParams(rank=4, num_iterations=3, seed=24)
    small, large = rating_set(100), rating_set(900)
    assert small.by_user.tgt.shape[1] != large.by_user.tgt.shape[1]

    def readings():
        sweeps = reg.get("pio_train_als_half_sweep_seconds")
        compiles = reg.get(jax_stats.BACKEND_COMPILE_COUNTER)
        return (jax_stats.compile_counter().value(family=TRAIN_FAMILY),
                sum(v for labels, v in compiles.samples()
                    if labels["fun"] == "jit(train)") if compiles else 0,
                sweeps.count(solver="full") if sweeps else 0)

    train_als(mesh, small, params)                 # first sighting: compiles
    ledger0, compiled0, sweeps0 = readings()
    train_als(mesh, small, params)                 # warm: one sample
    assert readings() == (ledger0, compiled0, sweeps0 + 1)
    # the same ledger key (the padded row count is not in it), another
    # input shape: jit retraces, only the compiler's own count sees it
    train_als(mesh, large, params)
    assert readings() == (ledger0, compiled0 + 1, sweeps0 + 1)
    train_als(mesh, large, params)
    assert readings() == (ledger0, compiled0 + 1, sweeps0 + 2)


def test_compile_fun_labels_are_bounded():
    events = jax_stats._CompilerEvents(MetricsRegistry())
    for i in range(jax_stats.MAX_COMPILE_FUNS + 5):
        events.on_duration("/jax/core/compile/backend_compile_duration",
                           0.5, fun_name=f"jit(f{i})")
    events.on_duration("/jax/core/compile/jaxpr_trace_duration", 0.25,
                       fun_name="f")
    events.on_event("/jax/compilation_cache/cache_hits")
    funs = {labels["fun"]: v for labels, v in events.compiles.samples()}
    assert len(funs) == jax_stats.MAX_COMPILE_FUNS + 1
    assert funs["other"] == 5 and events.count == len(funs) + 4
    assert events.compile_seconds.value(fun="other") == 2.5
    assert events.durations[
        "/jax/core/compile/jaxpr_trace_duration"].value() == 0.25
    assert events.events["/jax/compilation_cache/cache_hits"].value() == 1


def test_a_trace_inside_a_trace_is_not_counted_again():
    """jax reports a function traced inside another's trace on its own
    and inside the outer one's duration; only the outer one counts."""
    import jax
    import jax.numpy as jnp

    trace = "/jax/core/compile/jaxpr_trace_duration"
    events = jax_stats._CompilerEvents(MetricsRegistry())
    during = []

    @jax.jit
    def outer(x):
        # this body runs while `outer` is traced, which is where jax
        # reports the jitted functions `outer` calls
        events.on_duration(trace, 5.0, fun_name="inner")
        during.append(events.durations[trace].value())
        return x + 1

    outer(jnp.ones(3))
    assert during == [0.0]
    events.on_duration(trace, 2.0, fun_name="outer")
    assert events.durations[trace].value() == 2.0


def test_a_sequence_model_train_counts_its_attention_tokens_by_route():
    """`pio_train_seqrec_attention_tokens_total{impl}`: every position of
    the trained batches under the route `blockwise_attention` took when
    the train's step was traced (on the CPU: `xla`), which the step
    itself reports, so a step from the cache is counted like a new one."""
    import jax

    from predictionio_tpu.models import seqrec
    from predictionio_tpu.ops.attention import attention_route

    p = seqrec.SeqRecParams(d_model=16, n_heads=2, n_layers=1, max_len=8,
                            batch_size=2, epochs=2)
    assert attention_route(jax.devices()[0].device_kind, 8, 8, 8, 8,
                           seqrec.ATTENTION_BLOCK) == "xla"
    reg = default_registry()

    def counted(label):
        c = reg.get("pio_train_seqrec_attention_tokens_total")
        return c.value(impl=label) if c is not None else 0

    def positions():
        return sum(reg.get(name).value() for name in (
            "pio_train_seqrec_tokens_total",
            "pio_train_seqrec_pad_tokens_total"))

    seqrec.train_seqrec(None, [["a", "b"]], p)       # the series exist
    before = {label: counted(label) for label in ("xla", "pallas")}
    positions0 = positions()
    sessions = [[f"i{(s + j) % 11}" for j in range(6 + s)] for s in range(4)]
    for trains in (1, 2):               # the second: the step of the first
        seqrec.train_seqrec(None, sessions, p)
        # 2 epochs x 2 steps x 2 sessions x 8 positions, padding included
        assert counted("xla") - before["xla"] == trains * 2 * 2 * 2 * 8
        assert counted("pallas") == before["pallas"]
        assert positions() - positions0 == trains * 2 * 2 * 2 * 8


@pytest.mark.parametrize("shape", [None, (4, 2)], ids=["one_device", "mesh"])
def test_a_sequence_model_train_starts_its_weights_copies_once(shape):
    """`seqrec_fetch` is one span a train, around starting the weights'
    copies and reading the steps' numbers; its counter gains the bytes of
    the weights, which the model holds as the device's arrays (a mesh's
    sharded ones among them) and numpy reads whole."""
    import jax
    from jax.sharding import Mesh

    from predictionio_tpu.models import seqrec

    mesh = None if shape is None else Mesh(
        np.asarray(jax.devices()[:8]).reshape(shape), ("data", "model"))
    p = seqrec.SeqRecParams(d_model=16, n_heads=2, n_layers=1, max_len=8,
                            batch_size=4, epochs=1)
    sessions = [[f"i{(s + j) % 11}" for j in range(6 + s % 3)]
                for s in range(8)]
    reg = default_registry()

    def fetched():
        c = reg.get("pio_train_seqrec_fetch_bytes_total")
        return c.value() if c is not None else 0

    bytes0 = fetched()
    own = MetricsRegistry()
    with tracing.adopt("job", registry=own) as trace:
        model = seqrec.train_seqrec(mesh, sessions, p)
    leaves = jax.tree.leaves(model.params)
    assert span_count("seqrec_fetch", own) == 1
    assert [s.name for s in trace.spans][-1] == "seqrec_fetch"
    assert all(isinstance(leaf, jax.Array) for leaf in leaves)
    assert fetched() - bytes0 == sum(leaf.nbytes for leaf in leaves) \
        == reg.get("pio_train_seqrec_param_bytes").value()
    assert len(model.record["loss"]) == 2
    assert np.asarray(model.params["emb"]).shape == model.params["emb"].shape


def test_a_step_says_which_route_its_attention_was_traced_on(monkeypatch):
    """The step's `attention_pallas` is what `blockwise_attention` chose
    at trace time, not what the spec would suggest: with the kernels'
    route forced (and interpreted) it reads True, under a mesh of two
    devices the same spec reads False."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from predictionio_tpu.models import seqrec
    from predictionio_tpu.ops import attention, attention_pallas

    monkeypatch.setattr(attention, "_device_kind",
                        lambda: attention_pallas.KINDS[0])
    kernels = attention_pallas.flash_attention_pallas
    monkeypatch.setattr(
        attention_pallas, "flash_attention_pallas",
        lambda q, k, v, mask, causal: kernels(q, k, v, mask, causal, True))
    p = seqrec.SeqRecParams(d_model=128, n_heads=1, n_layers=1, max_len=128,
                            batch_size=2)
    optimizer = seqrec.make_optimizer(p)
    seqs = jnp.asarray(np.random.default_rng(0).integers(1, 9, (2, 128)),
                       jnp.int32)
    for mesh, pallas in (
            (None, True),
            (Mesh(np.asarray(jax.devices()[:2]), ("data",)), False)):
        params = seqrec.init_params(np.random.default_rng(0), 8, p)
        _, _, stats = seqrec.make_train_step(mesh, p, optimizer)(
            params, optimizer.init(params), seqs, seqs)
        assert bool(stats["attention_pallas"]) is pallas
        assert np.isfinite(float(stats["loss"]))


def _gdn_spec(**over):
    from predictionio_tpu.models import seqrec

    return seqrec.SeqRecParams(**{**dict(
        d_model=32, n_heads=2, n_layers=3, max_len=64, batch_size=2,
        mixer=("gdn", "gdn", "gqa"), norm="rms", positions="rope",
        n_kv_heads=1, head_dim=16, rotary_dim=8, linear_key_heads=1,
        linear_value_heads=2, linear_key_head_dim=128,
        linear_value_head_dim=128, linear_conv_kernel=4, remat=True),
        **over})


def _on_rule_kernels(monkeypatch):
    """`gated_delta_rule` and the chain around it as a v5e would route
    them, the kernels interpreted."""
    from predictionio_tpu.ops import (
        attention_pallas, linear_attention, linear_attention_pallas,
    )

    monkeypatch.setattr(linear_attention, "_device_kind",
                        lambda: attention_pallas.KINDS[0])
    for name in ("gated_delta_rule_pallas", "gated_delta_chain_pallas"):
        kernels = getattr(linear_attention_pallas, name)
        monkeypatch.setattr(
            linear_attention_pallas, name,
            lambda *a, kernels=kernels: kernels(*a, True))


def test_a_sequence_model_train_counts_its_linear_attention_tokens_by_route(
        monkeypatch):
    """`pio_train_seqrec_linear_attention_tokens_total{impl}`: every
    position of the trained batches times the linear-attention layers,
    under the route `gated_delta_rule` took when the train's step was
    traced, which the step itself reports: on the CPU `xla`; with the
    kernels' route forced (and interpreted) `pallas`. A model without
    such a layer counts nothing."""
    from predictionio_tpu.models import seqrec

    reg = default_registry()

    def counted(label):
        c = reg.get("pio_train_seqrec_linear_attention_tokens_total")
        return c.value(impl=label) if c is not None else 0

    sessions = [[f"i{(s + j) % 11}" for j in range(40 + s)] for s in range(4)]
    before = {label: counted(label) for label in ("xla", "pallas")}
    seqrec.train_seqrec(None, sessions, seqrec.SeqRecParams(
        d_model=16, n_heads=2, n_layers=1, max_len=8, batch_size=2))
    assert {label: counted(label) for label in before} == before
    # 1 epoch x 2 steps x 2 sessions x 64 positions x 2 gdn layers
    seqrec.train_seqrec(None, sessions, _gdn_spec(epochs=1))
    assert counted("xla") - before["xla"] == 2 * 2 * 64 * 2
    assert counted("pallas") == before["pallas"]

    _on_rule_kernels(monkeypatch)
    # another seed: another step than the cached one
    seqrec.train_seqrec(None, sessions, _gdn_spec(epochs=1, seed=8))
    assert counted("pallas") - before["pallas"] == 2 * 2 * 64 * 2
    assert counted("xla") - before["xla"] == 2 * 2 * 64 * 2


def test_a_step_says_which_route_its_delta_rule_was_traced_on(monkeypatch):
    """The step's `linear_attention_pallas` is what `gated_delta_rule`
    chose at trace time: with the kernels' route forced (and
    interpreted) it reads True and the heads are taken all at once,
    under a mesh of two devices the same spec reads False; with the
    kernels' operands left float32, as the CPU leaves the scan's, the
    loss and the gradient norms are the scan's."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from predictionio_tpu.models import seqrec
    from predictionio_tpu.ops import linear_attention_pallas

    p = _gdn_spec()
    optimizer = seqrec.make_optimizer(p)
    seqs = jnp.asarray(np.random.default_rng(0).integers(1, 9, (2, 64)),
                       jnp.int32)

    def step(mesh):
        params = seqrec.init_params(np.random.default_rng(0), 8, p)
        return seqrec.make_train_step(mesh, p, optimizer)(
            params, optimizer.init(params), seqs, seqs)[2]

    scan = step(None)
    assert not bool(scan["linear_attention_pallas"])
    _on_rule_kernels(monkeypatch)
    monkeypatch.setattr(linear_attention_pallas, "_BF16", jnp.float32)
    for mesh, pallas in (
            (None, True),
            (Mesh(np.asarray(jax.devices()[:2]), ("data",)), False)):
        stats = step(mesh)
        assert bool(stats["linear_attention_pallas"]) is pallas
        assert not bool(stats["attention_pallas"])
        assert abs(float(stats["loss"]) - float(scan["loss"])) \
            < 1e-5 * float(scan["loss"])
        for group, norm in scan["grad_norm"].items():
            assert abs(float(stats["grad_norm"][group]) - float(norm)) \
                < 2e-3 * float(norm), group


def test_a_sequence_model_train_counts_its_short_convolution_tokens_by_route(
        monkeypatch):
    """`pio_train_seqrec_short_conv_chain_tokens_total{impl}`: every
    position of the trained batches times the short-convolution layers,
    under the route `gated_short_conv` took when the train's step was
    traced, which the step itself reports: on the CPU `xla`; with the
    passes' route forced (and interpreted) `pallas`. The counter has no
    other label, and a model without such a layer counts nothing."""
    from predictionio_tpu.models import seqrec
    from predictionio_tpu.ops import (
        attention_pallas, linear_attention, short_conv_pallas,
    )

    reg = default_registry()

    def counted(label):
        c = reg.get("pio_train_seqrec_short_conv_chain_tokens_total")
        return c.value(impl=label) if c is not None else 0

    def spec(**over):
        return seqrec.SeqRecParams(**{**dict(
            d_model=128, n_heads=2, n_layers=3, max_len=128, batch_size=2,
            epochs=1, mixer=("conv", "mha", "conv"), conv_kernel=3,
            norm="rms", positions="rope", remat=True), **over})

    sessions = [[f"i{(s + j) % 11}" for j in range(140 + s)]
                for s in range(4)]
    before = {label: counted(label) for label in ("xla", "pallas")}
    seqrec.train_seqrec(None, sessions, seqrec.SeqRecParams(
        d_model=16, n_heads=2, n_layers=1, max_len=8, batch_size=2))
    assert {label: counted(label) for label in before} == before
    # 1 epoch x 2 steps x 2 sessions x 128 positions x 2 conv layers
    seqrec.train_seqrec(None, sessions, spec())
    assert counted("xla") - before["xla"] == 2 * 2 * 128 * 2
    assert counted("pallas") == before["pallas"]

    monkeypatch.setattr(linear_attention, "_device_kind",
                        lambda: attention_pallas.KINDS[0])
    passes = short_conv_pallas.gated_short_conv_pallas
    monkeypatch.setattr(short_conv_pallas, "gated_short_conv_pallas",
                        lambda bcu, taps, **kw: passes(bcu, taps, True, **kw))
    # another seed: another step than the cached one
    seqrec.train_seqrec(None, sessions, spec(seed=8))
    assert counted("pallas") - before["pallas"] == 2 * 2 * 128 * 2
    assert counted("xla") - before["xla"] == 2 * 2 * 128 * 2
    counter = reg.get("pio_train_seqrec_short_conv_chain_tokens_total")
    assert tuple(counter.labelnames) == ("impl",)
