"""Operation and byte counts against hand-worked numbers, and the
property that keeps a later kernel from reading over 100%."""
import itertools

import pytest

from benchmarks.lib import roofline as R

KIND = "TPU v5 lite"


def test_scorer_counts_hand_worked():
    # ISSUE 23: B = 4096 rows on MSD (N = 384,546, K = 128): 403 GFLOP,
    # 2.05 ms at 197 TFLOP/s, compute-bound; 2.1% of PR 22's 99.2 ms
    ops, nbytes = R.scorer_counts(4096, 384_546, 128, 10)
    assert ops == 2 * 4096 * 384_546 * 128 == pytest.approx(403.2e9, rel=1e-3)
    # V once, the user rows once, ten (score, index) pairs a row: NOT B x N
    assert nbytes == (384_546 * 128 + 4096 * 128) * 4 + 4096 * 10 * 8
    assert nbytes < 4096 * 384_546 * 4 / 30
    least, bound = R.least_time_s(ops, nbytes, KIND)
    assert bound == "compute" and least == pytest.approx(2.05e-3, rel=5e-3)
    pct, _ = R.roofline_pct(ops, nbytes, 99.2e-3, KIND)
    assert pct == pytest.approx(2.06, abs=0.02)


def test_solve_counts_hand_worked():
    # ISSUE 23: S = 138,493 systems of 64 x 64: 2.34 GB, 2.86 ms at
    # 819 GB/s, memory-bound; 24% of 11.7 ms
    ops, nbytes = R.solve_counts(138_493, 64)
    assert nbytes == 138_493 * (64 * 64 + 2 * 64) * 4 == pytest.approx(2.34e9, rel=2e-3)
    assert ops == pytest.approx(138_493 * (64 ** 3 / 3 + 2 * 64 ** 2))
    least, bound = R.least_time_s(ops, nbytes, KIND)
    assert bound == "memory" and least == pytest.approx(2.86e-3, rel=5e-3)
    pct, _ = R.roofline_pct(ops, nbytes, 11.7e-3, KIND)
    assert pct == pytest.approx(24.4, abs=0.2)


@pytest.mark.parametrize("b,n,k,num", itertools.product(
    (1, 64, 4096), (26_744, 384_546), (64, 128), (10, 100)))
def test_no_scorer_shape_reads_over_100(b, n, k, num):
    ops, nbytes = R.scorer_counts(b, n, k, num)
    least, _ = R.least_time_s(ops, nbytes, KIND)
    pct, _ = R.roofline_pct(ops, nbytes, least, KIND)
    assert pct == pytest.approx(100.0)
    assert R.roofline_pct(ops, nbytes, least * 1.5, KIND)[0] < 100.0


@pytest.mark.parametrize("s,k", itertools.product((1, 26_744, 138_493), (8, 64, 128)))
def test_no_solve_shape_reads_over_100(s, k):
    ops, nbytes = R.solve_counts(s, k)
    least, _ = R.least_time_s(ops, nbytes, KIND)
    assert R.roofline_pct(ops, nbytes, least, KIND)[0] == pytest.approx(100.0)


def test_a_device_outside_the_table_is_an_error():
    with pytest.raises(R.UnknownDevice):
        R.peaks("cpu")
    with pytest.raises(R.UnknownDevice):
        R.peaks("_source")
    assert R.peaks(KIND)["hbm_bytes_per_s"] == 819e9
