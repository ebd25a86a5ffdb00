"""`counts/gdn_chain.py` at the cell's shapes against the byte budget of
ISSUE 39, the property that a device time equal to the count's least
time reads 100%, the kernels' time per call, the reader of
`linear_attention_chain_kernel_token_pct`, all three on a program without
the kernels or the counter, and that the rule's kernels' metrics do not
read the chain's kernels."""
import json
import os

import pytest

from benchmarks.counts import gdn_chain
from benchmarks.lib import layer_readers, manifest, roofline as R

KIND = "TPU v5 lite"
CELL = "qwen3next-a3b-ep16.train"
NAMES = ("linear_attention_chain_kernel_token_pct", "gdn_chain_kernel_ms",
         "gdn_chain_roofline")


def cell_shapes():
    with open(os.path.join(manifest.ROOT, "benchmarks", "configs",
                           "seqrec-qwen3-next-80b-a3b-ep16.json")) as f:
        params = json.load(f)["algorithm_params"]
    return {**params, "n_vocab": 18992, "steps": 8, "tokens_per_step": 16384}


def test_the_count_is_the_issues_byte_budget():
    ops, nbytes = gdn_chain.counts({"shapes": cell_shapes()}, {}, 1)
    # ISSUE 39: q, k, v read where the projection wrote them (134 + 134 +
    # 268 MB) and written once at 16 key heads (537 MB); o and z read and
    # the gated output written once (805 MB): 1.9 GB, 2.3 ms at 819 GB/s
    q = k = 16384 * 16 * 128 * 4
    v = gate = 16384 * 32 * 128 * 4
    assert (q, v) == (pytest.approx(134e6, rel=2e-3),
                      pytest.approx(268e6, rel=2e-3))
    forward = 2 * (q + k + v) + 3 * gate
    assert forward == pytest.approx(1.9e9, rel=2e-2)
    assert forward / 819e9 == pytest.approx(2.3e-3, rel=1e-2)
    # backward: the gated output's gradient, o and z read, do and dz
    # written; dq, dk, dv and the pre-convolution columns read, the
    # projection's gradient columns written: 1.57 forward passes
    backward = 3 * (q + k + v) + 5 * gate
    assert backward / forward == pytest.approx(1.57, rel=1e-2)
    # 8 steps x 3 gdn layers, two forward passes (remat) and one backward
    assert nbytes == 8 * 3 * (2 * forward + backward)
    least, bound = R.least_time_s(ops, nbytes, KIND)
    assert bound == "memory"
    # a layer 8.2 ms, a step's three layers 24.6 ms at the memory's peak
    assert least / 8 / 3 == pytest.approx(8.2e-3, rel=1e-2)
    assert least / 8 == pytest.approx(24.6e-3, rel=1e-2)
    # the convolution's taps: 2 x 4 a q, k, v element forward, twice that
    # backward; nothing beside the bytes
    assert ops == 8 * 3 * (2 + 2) * 2 * 4 * 16384 * 8192
    assert ops / 197e12 < 1e-2 * least


def test_without_remat_a_layer_makes_one_forward_pass():
    shapes = cell_shapes()
    _, with_remat = gdn_chain.counts({"shapes": shapes}, {}, 1)
    _, without = gdn_chain.counts(
        {"shapes": {**shapes, "remat": False}}, {}, 1)
    forward = (2 * 8192 + 3 * 4096) * 16384 * 4.0
    assert with_remat - without == 8 * 3 * forward


def test_a_train_without_steps_or_without_a_gdn_layer_counts_nothing():
    assert gdn_chain.counts(
        {"shapes": {**cell_shapes(), "steps": 0}}, {}, 1) is None
    assert gdn_chain.counts(
        {"shapes": {**cell_shapes(), "mixer": "gqa"}}, {}, 1) is None
    assert gdn_chain.counts({"shapes": {"n_users": 1}}, {}, 1) is None
    for other in ("seqrec-kimi-vl-a3b-ep8", "seqrec-lfm2-24b-a2b-ep8",
                  "seqrec-ouro-2.6b-pp8"):
        with open(os.path.join(manifest.ROOT, "benchmarks", "configs",
                               f"{other}.json")) as f:
            params = json.load(f)["algorithm_params"]
        assert gdn_chain.counts({"shapes": {
            **params, "steps": 8, "tokens_per_step": 16384}}, {}, 1) is None


def evidence(seconds, after):
    """A traced train: a gdn layer and step's twelve calls of the chain's
    kernels beside the rule's and the attention's."""
    return {"shapes": cell_shapes(), "device": {"kind": KIND},
            "trace": {"ops": [
                ["gdn_chain_front_fwd.7_tpu_custom_call", 144, seconds / 4],
                ["gdn_chain_back_fwd.2_tpu_custom_call", 48, seconds / 8],
                ["gdn_chain_front_bwd.9_tpu_custom_call", 72, seconds / 2],
                ["gdn_chain_back_bwd.1_tpu_custom_call", 24, seconds / 8],
                ["gated_delta_rule_pallas_fwd.5_tpu_custom_call", 48, 0.5],
                ["gated_delta_rule_pallas_bwd.2_tpu_custom_call", 24, 0.25],
                ["flash_attention_pallas_fwd.3_tpu_custom_call", 16, 1.0],
                ["fusion.1", 5, 1.0]], "modules": []},
            "registry_before": {}, "registry_after": after}


def metric(name):
    with open(os.path.join(manifest.ROOT, "benchmarks", "layer_metrics",
                           f"{name}.json")) as f:
        return json.load(f)


def test_least_time_reads_100_and_the_kernels_time_per_call():
    ops, nbytes = gdn_chain.counts({"shapes": cell_shapes()}, {}, 288)
    least, _ = R.least_time_s(ops, nbytes, KIND)
    ev = evidence(least, {})
    assert layer_readers.read(ev, metric("gdn_chain_roofline")) \
        == pytest.approx(100.0)
    assert layer_readers.read(evidence(4 * least, {}), metric(
        "gdn_chain_roofline")) == pytest.approx(25.0)
    assert layer_readers.read(ev, metric("gdn_chain_kernel_ms")) \
        == pytest.approx(1000.0 * least / 288)


def test_the_rules_metrics_do_not_read_the_chains_kernels():
    """The readers match by substring and average per event: the chain's
    names hold none of the other kernels'."""
    ev = evidence(1.0, {})
    assert layer_readers.read(ev, metric("gdn_kernel_fwd_ms")) \
        == pytest.approx(1000.0 * 0.5 / 48)
    assert layer_readers.read(ev, metric("gdn_kernel_bwd_ms")) \
        == pytest.approx(1000.0 * 0.25 / 24)
    for name, _, _ in ev["trace"]["ops"][:4]:
        for other in ("gated_delta_rule_pallas", "flash_attention_pallas",
                      "grouped_product_pallas"):
            assert other not in name


@pytest.mark.parametrize("series,want", [
    ([[{"impl": "pallas"}, 393216.0]], 100.0),
    ([[{"impl": "pallas"}, 98304.0], [{"impl": "xla"}, 294912.0]], 25.0),
    ([[{"impl": "xla"}, 393216.0]], 0.0),
    (None, None),                       # the parent: no such counter
])
def test_token_pct_is_the_pallas_share_of_the_chains_tokens(series, want):
    after = {} if series is None else {
        "pio_train_seqrec_linear_attention_chain_tokens_total": series,
        "pio_train_seqrec_linear_attention_tokens_total": [
            [{"impl": "xla"}, 131072.0]]}
    got = layer_readers.read(evidence(1.0, after), metric(
        "linear_attention_chain_kernel_token_pct"))
    assert got == want


def test_a_program_without_the_kernels_or_the_counter_reports_nothing():
    """The parent under this PR's files: XLA's fusions and the rule's
    kernels in its trace, no such counter in its registry."""
    ev = evidence(1.0, {"pio_train_seqrec_linear_attention_tokens_total": [
        [{"impl": "pallas"}, 393216.0]]})
    ev["trace"]["ops"] = ev["trace"]["ops"][4:]
    for name in NAMES:
        assert layer_readers.read(ev, metric(name)) is None
    ev["trace"] = None
    for name in NAMES:
        assert layer_readers.read(ev, metric(name)) is None


def test_the_three_metrics_are_the_cells_alone():
    bench = manifest.load_benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"][-3:]] == list(NAMES)
    for name in NAMES:
        file = metric(name)
        for key in ("unit", "better", "source", "layer", "moves"):
            assert entries[name][key] == file[key], (name, key)
        assert entries[name]["layer"] == "linear attention layer"
        assert entries[name]["moves"] == "train_wall_s"
        assert entries[name]["workloads"] == [CELL]
