"""The per-layer metrics that read the program's own spans and the
compiler's own counters (PR 24): their files load through manifest.py,
and the two readers that are not on the menu reduce a hand-made evidence
document, and return None (never raise) on a program that has not got
the spans or counters."""
import pytest

from benchmarks.lib import layer_readers, manifest

SPAN_METRICS = {
    "ingest_digest_s": "ingest_digest", "ingest_decode_s": "ingest_decode", "id_assign_s": "train_id_assign",
    "als_pack_s": "als_pack", "als_put_s": "als_put",
    "als_solve_s": "als_solve"}
OTHER_METRICS = [
    "host_prep_unattributed_s", "als_row_fill_pct.user",
    "als_row_fill_pct.item", "xla_compiles_in_window.train",
    "setup_jax_trace_s", "setup_jax_lower_s", "setup_jax_backend_compile_s"]

SPANS = {"ingest_digest": 0.8, "ingest_scan": 4.8, "ingest_decode": 1.1, "train_id_assign": 0.6,
         "als_pack": 0.5, "als_put": 0.2, "als_solve": 3.0}


def evidence(spans=SPANS, with_counters=True):
    """What child.py hands run.py, cut to what these readers read: three
    trains, the second traced, its first jit_train program 7.4 s in."""
    fill = [[{"side": "user"}, {"sum": 0.8, "count": 2}],
            [{"side": "item"}, {"sum": 1.2, "count": 2}]]
    fill_after = [[{"side": "user"}, {"sum": 2.0, "count": 5}],
                  [{"side": "item"}, {"sum": 3.0, "count": 5}]]
    before = {"pio_jax_compile_total": [[{"family": "als_train"}, 2.0]]}
    after = {"pio_jax_compile_total": [[{"family": "als_train"}, 2.0]]}
    if with_counters:
        before.update({
            "pio_train_als_row_fill_ratio": fill,
            "pio_jax_trace_seconds_total": [[{}, 9.5]],
            "pio_jax_lower_seconds_total": [[{}, 4.25]],
            "pio_jax_backend_compile_seconds_total": [
                [{"fun": "jit(train)"}, 12.0], [{"fun": "jit(add)"}, 0.5]],
            "pio_jax_backend_compile_total": [
                [{"fun": "jit(train)"}, 2.0], [{"fun": "jit(add)"}, 1.0]]})
        after.update({
            "pio_train_als_row_fill_ratio": fill_after,
            "pio_jax_trace_seconds_total": [[{}, 9.5]],
            "pio_jax_backend_compile_total": [
                [{"fun": "jit(train)"}, 3.0], [{"fun": "jit(add)"}, 1.0]]})
    return {
        "jobs": [{"wall_s": 10.5, "traced": i == 1,
                  "spans": {k: v + 0.01 * i for k, v in spans.items()}}
                 for i in range(3)],
        "registry_before": before, "registry_after": after,
        "trace": {"jobs": [[100.0, 10.5]],
                  "modules": [["jit_convert(1)", 100.5, 0.001],
                              ["jit_train(77)", 107.4, 3.0]]},
    }


def read(name, ev):
    return layer_readers.read(ev, manifest.load_layer_reader(name))


def test_the_new_metric_files_are_listed_and_load():
    bench = manifest.load_benchmark()
    assert manifest.check(bench) == []
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in list(SPAN_METRICS) + OTHER_METRICS:
        doc = manifest.load_layer_reader(name)
        entry = listed[name]
        for key in ("layer", "moves", "unit", "better", "source"):
            assert doc[key] == entry[key], (name, key)
        assert entry["workloads"] == ["ml20m-r64.train"]
    for name in OTHER_METRICS[-3:]:
        assert listed[name]["moves"] == "setup_s"


@pytest.mark.parametrize("name,span", sorted(SPAN_METRICS.items()))
def test_span_metrics_read_the_median_over_jobs(name, span):
    assert read(name, evidence()) == pytest.approx(SPANS[span] + 0.01)
    assert read(name, evidence(spans={"ingest_scan": 4.8})) is None


def test_host_prep_residual():
    # 7.4 s to the first jit_train program, less the traced (second)
    # job's six host spans
    want = 7.4 - (0.81 + 4.81 + 1.11 + 0.61 + 0.51 + 0.21)
    assert read("host_prep_unattributed_s", evidence()) \
        == pytest.approx(want)
    # a program without the spans, a run without a trace, a trace
    # without the program: nothing to read, and no exception
    assert read("host_prep_unattributed_s",
                evidence(spans={"ingest_scan": 4.8})) is None
    no_trace = evidence()
    no_trace["trace"] = None
    assert read("host_prep_unattributed_s", no_trace) is None
    no_program = evidence()
    no_program["trace"]["modules"] = []
    assert read("host_prep_unattributed_s", no_program) is None


def test_counter_before_and_the_counter_metrics():
    ev = evidence()
    assert read("setup_jax_trace_s", ev) == 9.5
    assert read("setup_jax_lower_s", ev) == 4.25
    assert read("setup_jax_backend_compile_s", ev) == 12.5   # every fun
    assert read("xla_compiles_in_window.train", ev) == 1.0
    assert read("als_row_fill_pct.user", ev) == pytest.approx(40.0)
    assert read("als_row_fill_pct.item", ev) == pytest.approx(60.0)
    old = evidence(with_counters=False)     # the parent commit's program
    for name in OTHER_METRICS[1:]:
        assert read(name, old) is None
