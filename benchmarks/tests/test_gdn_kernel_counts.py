"""`counts/gdn_kernel.py` at the cell's shapes against the figures of
ISSUE 32, the property that a device time equal to the count's least
time reads 100%, the two kernel times per call, the reader of
`linear_attention_kernel_token_pct`, and all four on a program without
the kernels or the counter."""
import json
import os

import pytest

from benchmarks.counts import gdn_kernel
from benchmarks.lib import layer_readers, manifest, roofline as R

KIND = "TPU v5 lite"
CELL = "qwen3next-a3b-ep16.train"
NAMES = ("linear_attention_kernel_token_pct", "gdn_kernel_fwd_ms",
         "gdn_kernel_bwd_ms", "gdn_kernel_roofline")


def cell_shapes():
    with open(os.path.join(manifest.ROOT, "benchmarks", "configs",
                           "seqrec-qwen3-next-80b-a3b-ep16.json")) as f:
        params = json.load(f)["algorithm_params"]
    return {**params, "n_vocab": 18992, "steps": 8, "tokens_per_step": 16384}


def test_the_count_is_the_issues_figures():
    ops, nbytes = gdn_kernel.counts({"shapes": cell_shapes()}, {}, 1)
    # a layer's forward call: 16,384 positions x 32 value heads, three
    # products of 2 x 128 x 128 over the state: 51.5 GFLOP; q, k, v, o
    # 1.07 GB with g and beta beside them
    forward_ops = 16384 * 32 * 3 * 2 * 128 * 128
    assert forward_ops == pytest.approx(51.5e9, rel=1e-2)
    # 8 steps x 3 gdn layers, two forward calls (remat) and a backward of
    # twice a forward's operations
    assert ops == 8 * 3 * (2 + 2) * forward_ops
    rows = 16384 * 32
    forward_bytes = rows * 4 * (128 + 128 + 128 + 1 + 1 + 128)
    assert forward_bytes == pytest.approx(1.07e9, rel=1e-2)
    backward_bytes = rows * 4 * ((3 * 128 + 2) + 128 + (3 * 128 + 2))
    assert nbytes == 8 * 3 * (2 * forward_bytes + backward_bytes)
    least, bound = R.least_time_s(ops, nbytes, KIND)
    assert bound == "memory"
    # a forward call 1.3 ms at 819 GB/s; a step's three layers 14.8 ms
    assert forward_bytes / 819e9 == pytest.approx(1.3e-3, rel=2e-2)
    assert least / 8 == pytest.approx(14.8e-3, rel=1e-2)


def test_without_remat_a_layer_makes_one_forward_call():
    shapes = cell_shapes()
    with_remat, _ = gdn_kernel.counts({"shapes": shapes}, {}, 1)
    without, _ = gdn_kernel.counts(
        {"shapes": {**shapes, "remat": False}}, {}, 1)
    assert with_remat / without == pytest.approx(4 / 3)


def test_a_train_without_steps_or_without_a_gdn_layer_counts_nothing():
    assert gdn_kernel.counts(
        {"shapes": {**cell_shapes(), "steps": 0}}, {}, 1) is None
    assert gdn_kernel.counts(
        {"shapes": {**cell_shapes(), "mixer": "gqa"}}, {}, 1) is None
    assert gdn_kernel.counts({"shapes": {"n_users": 1}}, {}, 1) is None
    with open(os.path.join(manifest.ROOT, "benchmarks", "configs",
                           "seqrec-kimi-vl-a3b-ep8.json")) as f:
        kimi = json.load(f)["algorithm_params"]
    assert gdn_kernel.counts({"shapes": {
        **kimi, "steps": 8, "tokens_per_step": 16384}}, {}, 1) is None


def evidence(seconds, after):
    return {"shapes": cell_shapes(), "device": {"kind": KIND},
            "trace": {"ops": [
                ["gated_delta_rule_pallas_fwd.5_tpu_custom_call", 48,
                 seconds / 4],
                ["gated_delta_rule_pallas_bwd.2_tpu_custom_call", 192,
                 3 * seconds / 4],
                ["flash_attention_pallas_fwd.3_tpu_custom_call", 16, 1.0],
                ["fusion.1", 5, 1.0]], "modules": []},
            "registry_before": {}, "registry_after": after}


def metric(name):
    with open(os.path.join(manifest.ROOT, "benchmarks", "layer_metrics",
                           f"{name}.json")) as f:
        return json.load(f)


def test_least_time_reads_100_and_each_kernels_time_per_call():
    ops, nbytes = gdn_kernel.counts({"shapes": cell_shapes()}, {}, 240)
    least, _ = R.least_time_s(ops, nbytes, KIND)
    ev = evidence(least, {})
    assert layer_readers.read(ev, metric("gdn_kernel_roofline")) \
        == pytest.approx(100.0)
    assert layer_readers.read(evidence(4 * least, {}), metric(
        "gdn_kernel_roofline")) == pytest.approx(25.0)
    assert layer_readers.read(ev, metric("gdn_kernel_fwd_ms")) \
        == pytest.approx(1000.0 * least / 4 / 48)
    assert layer_readers.read(ev, metric("gdn_kernel_bwd_ms")) \
        == pytest.approx(1000.0 * 3 * least / 4 / 192)


@pytest.mark.parametrize("series,want", [
    ([[{"impl": "pallas"}, 393216.0]], 100.0),
    ([[{"impl": "pallas"}, 98304.0], [{"impl": "xla"}, 294912.0]], 25.0),
    ([[{"impl": "xla"}, 393216.0]], 0.0),
    (None, None),                       # the parent: no such counter
])
def test_token_pct_is_the_pallas_share_of_the_linear_layers_tokens(series,
                                                                   want):
    after = {} if series is None else {
        "pio_train_seqrec_linear_attention_tokens_total": series,
        "pio_train_seqrec_attention_tokens_total": [
            [{"impl": "xla"}, 131072.0]]}
    got = layer_readers.read(evidence(1.0, after),
                             metric("linear_attention_kernel_token_pct"))
    assert got == want


def test_a_program_without_the_kernels_or_the_counter_reports_nothing():
    """The parent under this PR's files: the scan's operations in its
    trace, no such counter in its registry."""
    ev = evidence(1.0, {"pio_train_seqrec_mixer_tokens_total": [
        [{"mixer": "gdn"}, 393216.0]]})
    ev["trace"]["ops"] = [["multiply_reduce_fusion.12", 8, 0.072],
                          ["flash_attention_pallas_bwd.1_tpu_custom_call",
                           8, 0.25]]
    for name in NAMES:
        assert layer_readers.read(ev, metric(name)) is None
    ev["trace"] = None
    for name in NAMES:
        assert layer_readers.read(ev, metric(name)) is None


def test_the_four_metrics_are_the_cells_alone():
    bench = manifest.load_benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"][-4:]] == list(NAMES)
    for name in NAMES:
        file = metric(name)
        for key in ("unit", "better", "source", "layer", "moves"):
            assert entries[name][key] == file[key], (name, key)
        assert entries[name]["layer"] == "linear attention layer"
        assert entries[name]["moves"] == "train_wall_s"
        assert entries[name]["workloads"] == [CELL]
