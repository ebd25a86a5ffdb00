"""The reduction from a profiler trace to metrics, on a small trace
recorded on the chip (TPU v5 lite; benchmarks/tools/probe_chip.py): three
`bench_job` spans, each a 20 ms host sleep (`bench_host:prepare`) and two
runs of a 256 x 384,546 x 128 score-and-top-k program."""
import os

import pytest

from benchmarks.lib import trace_reduce as T

TRACE = os.path.join(os.path.dirname(__file__), "data", "small_trace.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return T.reduce(T.load(TRACE), host_label="probe_host")


def test_window_is_the_jobs_and_busy_is_the_union(reduced):
    assert len(reduced["jobs"]) == 3
    assert reduced["window_s"] == pytest.approx(0.1295, abs=1e-3)
    # six programs of about 6.5 ms each
    assert reduced["busy_s"] == pytest.approx(0.0391, abs=5e-4)
    assert reduced["idle_pct"] == pytest.approx(69.8, abs=0.5)
    assert reduced["n_devices"] == 1


def test_kernel_time_by_name_pattern(reduced):
    n, s = T.kernel_time(reduced, "TopK")
    assert n == 6 and s == pytest.approx(0.0336, abs=3e-4)
    n, s = T.kernel_time(reduced, r"^fusion")
    assert n == 6 and s == pytest.approx(0.00543, abs=1e-4)
    n, s = T.kernel_time(reduced, "jit_topk", "modules")
    # a program's time is its operations' time
    assert n == 6 and s == pytest.approx(reduced["busy_s"], rel=1e-3)
    assert T.kernel_time(reduced, "no_such_kernel") == (0, 0.0)


def test_idle_gaps_are_named_by_the_host_span(reduced):
    gaps = reduced["idle_gaps_top"]
    # the three longest gaps are the host sleeps inside the jobs
    assert [g[0] for g in gaps[:3]] == ["probe_host:prepare"] * 3
    assert all(g[1] > 0.019 for g in gaps[:3])
    assert all(g[1] >= T.MIN_GAP_S for g in gaps)
    assert gaps == sorted(gaps, key=lambda g: -g[1])
    assert reduced["device_ops_top"][0][0] == "custom-call_TopK"


def test_first_module_start_lies_after_the_host_span(reduced):
    first = T.first_module_start(reduced, "jit_topk")
    assert first - reduced["jobs"][0][0] == pytest.approx(0.0202, abs=2e-3)
    assert T.first_module_start(reduced, "jit_train") is None


def test_short_op_name():
    assert T.short_op_name("%fusion.75 = f32[8,128]{1,0} fusion(%a)") == "fusion.75"
    assert T.short_op_name(
        '%custom-call.3 = (f32[2]) custom-call(%x), custom_call_target="TopK"'
    ) == "custom-call.3_TopK"


def test_a_trace_without_device_operations_is_refused():
    with pytest.raises(ValueError):
        T.reduce({"devices": {}, "annotations": []})


def test_union_merges_overlaps():
    busy, merged = T.union_s([("a", 0.0, 1.0), ("b", 0.5, 1.0), ("c", 3.0, 1.0)])
    assert busy == pytest.approx(2.5) and merged == [(0.0, 1.5), (3.0, 4.0)]


def test_a_gap_is_cut_where_the_programs_span_changes():
    """The program's `pio:<span>` annotations are kept beside the
    benchmark's own, and the one long gap before the first device
    operation is cut where the innermost span changes: the breakdown
    names what the host was doing, not a position."""
    trace = {
        "devices": {"/device:TPU:0": {
            "ops": [("%fusion.1 = f32[8] fusion(%a)", 14.0, 3.0),
                    ("%fusion.2 = f32[8] fusion(%b)", 17.0005, 0.5)],
            "modules": [("jit_train(1)", 14.0, 3.5005)]}},
        "annotations": sorted([
            ("bench_job", 10.0, 8.0),
            ("pio:train_read", 10.1, 2.9),        # parent of the next three
            ("pio:ingest_digest", 10.1, 0.8),
            ("pio:ingest_scan", 10.9, 1.3),
            ("pio:ingest_decode", 12.2, 0.7),
            ("pio:als_pack", 13.1, 0.6),
            ("pio:train_persist", 17.6, 0.1)], key=lambda a: a[1])}
    r = T.reduce(trace, host_label="train_host", top=20)
    assert r["window_s"] == pytest.approx(8.0)
    assert r["busy_s"] == pytest.approx(3.5)
    gaps = dict(r["idle_gaps_top"])
    assert gaps["train_host:ingest_scan"] == pytest.approx(1.3)
    assert gaps["train_host:ingest_digest"] == pytest.approx(0.8)
    assert gaps["train_host:ingest_decode"] == pytest.approx(0.7)
    assert gaps["train_host:als_pack"] == pytest.approx(0.6)
    # inside train_read but under none of its children
    assert gaps["train_host:train_read"] == pytest.approx(0.1)
    assert gaps["train_host:train_persist"] == pytest.approx(0.1)
    assert gaps["train_host:between_device_ops"] == pytest.approx(5e-4)
    # what no span covers keeps the gap's position, once per stretch
    before = [t for n, t in r["idle_gaps_top"]
              if n == "train_host:before_the_first_device_op"]
    assert sorted(before) == pytest.approx([0.1, 0.1, 0.3])
    # the pieces add up to the idle time
    assert sum(t for _, t in r["idle_gaps_top"]) == pytest.approx(
        r["window_s"] - r["busy_s"])


def test_load_keeps_both_kinds_of_annotation():
    assert "pio:als_solve".startswith(T.ANNOTATION_PREFIXES)
    assert "bench_job".startswith(T.ANNOTATION_PREFIXES)
    assert not "$core.py:331 dispatch".startswith(T.ANNOTATION_PREFIXES)
