"""`counts/attention_kernel.py` at the cell's shapes against the figures
of ISSUE 30, the property that a device time equal to the count's least
time reads 100%, and the reader of `attention_kernel_token_pct`."""
import json
import os

import pytest

from benchmarks.counts import attention_kernel
from benchmarks.lib import layer_readers, manifest, roofline as R

KIND = "TPU v5 lite"


def cell_shapes():
    with open(os.path.join(manifest.ROOT, "benchmarks", "configs",
                           "seqrec-kimi-vl-a3b-ep8.json")) as f:
        params = json.load(f)["algorithm_params"]
    return {**params, "n_vocab": 20480, "steps": 8, "tokens_per_step": 16384}


def test_the_count_is_the_issues_figures():
    ops, nbytes = attention_kernel.counts({"shapes": cell_shapes()}, {}, 192)
    pairs = 2 * 16 * 8192 * 8193 / 2          # 2 sessions x 16 heads a step
    forward, backward = 2 * (192 + 128), 2 * (3 * 192 + 2 * 128)
    assert (forward, backward) == (640, 1664)
    # 8 steps x 6 layers, two forward calls (remat) and one backward each
    assert ops == 8 * 6 * pairs * (2 * forward + backward)
    assert ops / 8 == pytest.approx(18.97e12, rel=1e-3)     # a step
    rows = 2 * 16 * 8192
    assert nbytes == 8 * 6 * 4 * rows * (
        2 * (192 + 192 + 128 + 128) + (192 + 192 + 128 + 128)
        + (192 + 192 + 128))
    least, bound = R.least_time_s(ops, nbytes, KIND)
    assert bound == "compute"
    assert least / 8 == pytest.approx(0.0963, rel=1e-3)     # seconds a step


def test_without_remat_a_layer_makes_one_forward_call():
    shapes = cell_shapes()
    with_remat, _ = attention_kernel.counts({"shapes": shapes}, {}, 1)
    without, _ = attention_kernel.counts(
        {"shapes": {**shapes, "remat": False}}, {}, 1)
    assert with_remat / without == pytest.approx((2 * 640 + 1664)
                                                 / (640 + 1664))


def test_a_train_that_made_no_step_or_has_no_such_widths_counts_nothing():
    assert attention_kernel.counts(
        {"shapes": {**cell_shapes(), "steps": 0}}, {}, 1) is None
    assert attention_kernel.counts({"shapes": {"n_users": 1}}, {}, 1) is None


def evidence(seconds, after):
    return {"shapes": cell_shapes(), "device": {"kind": KIND},
            "trace": {"ops": [["flash_attention_pallas_fwd.3_tpu_custom_call",
                               96, seconds / 2],
                              ["flash_attention_pallas_bwd.1_tpu_custom_call",
                               48, seconds / 2],
                              ["fusion.1", 5, 1.0]], "modules": []},
            "registry_before": {}, "registry_after": after}


def metric(name):
    with open(os.path.join(manifest.ROOT, "benchmarks", "layer_metrics",
                           f"{name}.json")) as f:
        return json.load(f)


def test_least_time_reads_100_and_the_kernels_own_time_per_call():
    ops, nbytes = attention_kernel.counts({"shapes": cell_shapes()}, {}, 144)
    least, _ = R.least_time_s(ops, nbytes, KIND)
    ev = evidence(least, {})
    assert layer_readers.read(ev, metric("attention_kernel_roofline")) \
        == pytest.approx(100.0)
    assert layer_readers.read(evidence(2 * least, {}), metric(
        "attention_kernel_roofline")) == pytest.approx(50.0)
    # each kernel's own time a call: the evidence gives each half of it
    assert layer_readers.read(ev, metric("attention_kernel_fwd_ms")) \
        == pytest.approx(1000.0 * least / 2 / 96)
    assert layer_readers.read(ev, metric("attention_kernel_bwd_ms")) \
        == pytest.approx(1000.0 * least / 2 / 48)


@pytest.mark.parametrize("series,want", [
    ([[{"impl": "pallas"}, 131072.0]], 100.0),
    ([[{"impl": "pallas"}, 98304.0], [{"impl": "xla"}, 32768.0]], 75.0),
    ([[{"impl": "xla"}, 131072.0]], 0.0),
    (None, None),                       # the parent: no such counter
])
def test_token_pct_is_the_pallas_share_of_all_attention_tokens(series, want):
    after = {} if series is None else {
        "pio_train_seqrec_attention_tokens_total": series}
    got = layer_readers.read(evidence(1.0, after),
                             metric("attention_kernel_token_pct"))
    assert got == want


def test_a_program_without_the_kernels_reports_nothing():
    ev = evidence(1.0, {})
    ev["trace"]["ops"] = [["dynamic_update_slice.317", 8, 0.137]]
    names = ("attention_kernel_fwd_ms", "attention_kernel_bwd_ms",
             "attention_kernel_roofline")
    for name in names:
        assert layer_readers.read(ev, metric(name)) is None
    ev["trace"] = None
    for name in names:
        assert layer_readers.read(ev, metric(name)) is None
