"""`counts/short_conv_chain.py` at the cell's shapes against the byte
budget of ISSUE 42, the property that a device time equal to the count's
least time reads 100%, the kernels' time per call, the reader of
`short_conv_chain_kernel_token_pct`, all three on a program without the
kernels or the counter, and that the other kernels' metrics do not read
these kernels."""
import json
import os

import pytest

from benchmarks.counts import short_conv_chain
from benchmarks.lib import layer_readers, manifest, roofline as R

KIND = "TPU v5 lite"
CELL = "lfm2-a2b-ep8.train"
NAMES = ("short_conv_chain_kernel_token_pct", "short_conv_chain_kernel_ms",
         "short_conv_chain_roofline")


def params_of(config):
    with open(os.path.join(manifest.ROOT, "benchmarks", "configs",
                           f"{config}.json")) as f:
        return json.load(f)["algorithm_params"]


def cell_shapes():
    return {**params_of("seqrec-lfm2-24b-a2b-ep8"), "n_vocab": 49152,
            "steps": 8, "tokens_per_step": 32768}


def test_the_count_is_the_issues_byte_budget():
    shapes = cell_shapes()
    assert (shapes["d_model"], shapes["conv_kernel"], shapes["remat"]) \
        == (2048, 3, True)
    assert [shapes["mixer"][i % len(shapes["mixer"])]
            for i in range(shapes["n_layers"])].count("conv") == 4
    ops, nbytes = short_conv_chain.counts({"shapes": shapes}, {}, 1)
    # ISSUE 42: b, c, u read and y written forward: 1.07 GB; b, c, u and
    # dy read and the projection's gradient written backward: 1.88 GB
    part = 32768 * 2048 * 4
    forward, backward = 4 * part, 7 * part
    assert forward == pytest.approx(1.07e9, rel=5e-3)
    assert backward == pytest.approx(1.88e9, rel=5e-3)
    # 8 steps x 4 conv layers, two forward passes (remat) and one backward
    assert nbytes == 8 * 4 * (2 * forward + backward)
    least, bound = R.least_time_s(ops, nbytes, KIND)
    assert bound == "memory"
    # 2 x 1.31 + 2.30 = 4.9 ms a layer, 19.7 ms a step at the memory's peak
    assert least / 8 / 4 == pytest.approx(4.92e-3, rel=1e-2)
    assert least / 8 == pytest.approx(19.7e-3, rel=5e-3)
    # the convolution's taps: 2 x 3 an element forward, twice that
    # backward; nothing beside the bytes
    assert ops == 8 * 4 * (2 + 2) * 2 * 3 * 32768 * 2048
    assert ops / 197e12 < 1e-2 * least


def test_without_remat_a_layer_makes_one_forward_pass():
    shapes = cell_shapes()
    _, with_remat = short_conv_chain.counts({"shapes": shapes}, {}, 1)
    _, without = short_conv_chain.counts(
        {"shapes": {**shapes, "remat": False}}, {}, 1)
    assert with_remat - without == 8 * 4 * 4 * 32768 * 2048 * 4.0


def test_a_train_without_steps_or_without_a_conv_layer_counts_nothing():
    assert short_conv_chain.counts(
        {"shapes": {**cell_shapes(), "steps": 0}}, {}, 1) is None
    assert short_conv_chain.counts(
        {"shapes": {**cell_shapes(), "mixer": "gqa"}}, {}, 1) is None
    assert short_conv_chain.counts({"shapes": {"n_users": 1}}, {}, 1) is None
    for other in ("seqrec-kimi-vl-a3b-ep8", "seqrec-qwen3-next-80b-a3b-ep16",
                  "seqrec-ouro-2.6b-pp8",
                  "seqrec-nemotron3-super-120b-a12b-tp8ep64"):
        assert short_conv_chain.counts({"shapes": {
            **params_of(other), "steps": 8, "tokens_per_step": 16384}},
            {}, 1) is None


def evidence(seconds, after):
    """A traced train: a conv layer and step's three calls of the chain's
    kernels beside the attention's and the experts'."""
    return {"shapes": cell_shapes(), "device": {"kind": KIND},
            "trace": {"ops": [
                ["short_conv_chain_fwd.7_tpu_custom_call", 64, seconds / 2],
                ["short_conv_chain_bwd.9_tpu_custom_call", 32, seconds / 2],
                ["flash_attention_pallas_fwd.3_tpu_custom_call", 16, 1.0],
                ["grouped_product_pallas_up.3_tpu_custom_call", 48, 0.5],
                ["fusion.1", 5, 1.0]], "modules": []},
            "registry_before": {}, "registry_after": after}


def metric(name):
    with open(os.path.join(manifest.ROOT, "benchmarks", "layer_metrics",
                           f"{name}.json")) as f:
        return json.load(f)


def test_least_time_reads_100_and_the_kernels_time_per_call():
    ops, nbytes = short_conv_chain.counts({"shapes": cell_shapes()}, {}, 96)
    least, _ = R.least_time_s(ops, nbytes, KIND)
    ev = evidence(least, {})
    assert layer_readers.read(ev, metric("short_conv_chain_roofline")) \
        == pytest.approx(100.0)
    assert layer_readers.read(evidence(4 * least, {}), metric(
        "short_conv_chain_roofline")) == pytest.approx(25.0)
    assert layer_readers.read(ev, metric("short_conv_chain_kernel_ms")) \
        == pytest.approx(1000.0 * least / 96)


def test_no_other_kernels_metric_reads_these_kernels():
    """The readers match by substring and average per event: these names
    hold none of the other kernels', and the others' none of these."""
    ev = evidence(1.0, {})
    assert layer_readers.read(ev, metric("attention_kernel_fwd_ms")) \
        == pytest.approx(1000.0 * 1.0 / 16)
    assert layer_readers.read(ev, metric("expert_kernel_ms")) \
        == pytest.approx(1000.0 * 0.5 / 48)
    for name in os.listdir(os.path.join(manifest.ROOT, "benchmarks",
                                        "layer_metrics")):
        pattern = metric(name[:-5])["reader"].get("pattern")
        if pattern and name[:-5] not in NAMES:
            assert pattern not in "short_conv_chain_fwd short_conv_chain_bwd"


@pytest.mark.parametrize("series,want", [
    ([[{"impl": "pallas"}, 1048576.0]], 100.0),
    ([[{"impl": "pallas"}, 262144.0], [{"impl": "xla"}, 786432.0]], 25.0),
    ([[{"impl": "xla"}, 1048576.0]], 0.0),
    (None, None),                       # the parent: no such counter
])
def test_token_pct_is_the_pallas_share_of_the_chains_tokens(series, want):
    after = {} if series is None else {
        "pio_train_seqrec_short_conv_chain_tokens_total": series,
        "pio_train_seqrec_mixer_tokens_total": [
            [{"mixer": "conv"}, 1048576.0]]}
    got = layer_readers.read(evidence(1.0, after), metric(
        "short_conv_chain_kernel_token_pct"))
    assert got == want


def test_a_program_without_the_kernels_or_the_counter_reports_nothing():
    """The parent under this PR's files: XLA's fusions and the other
    kernels in its trace, no such counter in its registry."""
    ev = evidence(1.0, {"pio_train_seqrec_mixer_tokens_total": [
        [{"mixer": "conv"}, 1048576.0]]})
    ev["trace"]["ops"] = ev["trace"]["ops"][2:]
    for name in NAMES:
        assert layer_readers.read(ev, metric(name)) is None
    ev["trace"] = None
    for name in NAMES:
        assert layer_readers.read(ev, metric(name)) is None


def test_the_three_metrics_are_the_cells_alone():
    bench = manifest.load_benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]
            if m["name"].startswith("short_conv_chain")] == list(NAMES)
    for name in NAMES:
        file = metric(name)
        for key in ("unit", "better", "source", "layer", "moves"):
            assert entries[name][key] == file[key], (name, key)
        assert entries[name]["layer"] == "short convolution layer"
        assert entries[name]["moves"] == "train_wall_s"
        assert entries[name]["workloads"] == [CELL]
