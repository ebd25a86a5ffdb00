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
