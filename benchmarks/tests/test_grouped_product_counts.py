"""`counts/grouped_product.py` at the three sequence cells' shapes against
the figures of ISSUE 37, the property that a device time equal to the
count's least time reads 100%, the kernels' time per call, the reader of
`expert_kernel_token_pct`, and all three on a program without the kernels
or the counters."""
import json
import os

import pytest

from benchmarks.counts import grouped_product
from benchmarks.lib import layer_readers, manifest, roofline as R

KIND = "TPU v5 lite"
NAMES = ("expert_kernel_token_pct", "expert_kernel_ms",
         "expert_kernel_roofline")
CELLS = ("kimivl-a3b-ep8.train", "qwen3next-a3b-ep16.train",
         "lfm2-a2b-ep8.train")
SLOTS = "pio_train_seqrec_expert_tokens_total"


def shapes(config, tokens):
    with open(os.path.join(manifest.ROOT, "benchmarks", "configs",
                           f"{config}.json")) as f:
        params = json.load(f)["algorithm_params"]
    return {**params, "n_vocab": 20480, "steps": 8, "tokens_per_step": tokens}


def evidence(config, tokens, slots_a_step, seconds=1.0, jobs=5):
    """A window of `jobs` trains of 8 steps whose expert layers held
    `slots_a_step` routed slots a step in all, one of them traced."""
    return {"shapes": shapes(config, tokens), "device": {"kind": KIND},
            "jobs": [{"wall_s": 9.0}] * jobs,
            "trace": {"ops": [
                ["grouped_product_pallas_rows.5_tpu_custom_call", 240,
                 seconds / 2],
                ["grouped_product_pallas_rows_t.2_tpu_custom_call", 120,
                 seconds / 4],
                ["grouped_product_pallas_groups.7_tpu_custom_call", 120,
                 seconds / 4],
                ["flash_attention_pallas_fwd.3_tpu_custom_call", 16, 1.0],
                ["fusion.1", 5, 1.0]], "modules": []},
            "registry_before": {},
            "registry_after": {SLOTS: [
                [{"layer": "0"}, jobs * 8.0 * slots_a_step]]}}


@pytest.mark.parametrize("config,tokens,slots,tflop,ms_at_peak,bound", [
    # ISSUE 37's table: 5 layers x 12 x 2 x 12,288 x 2048 x 1408 = 4.25
    # TFLOP a step, 21.6 ms at the peak; 4 x .. 16,384 x 1536 = 4.95, 25.1
    ("seqrec-kimi-vl-a3b-ep8", 16384, 5 * 12288, 4.25, 21.6, "compute"),
    ("seqrec-lfm2-24b-a2b-ep8", 32768, 4 * 16384, 4.95, 25.1, "compute"),
    # 1.03 TFLOP a step, 5.2 ms at the peak, but its 32 matrices and
    # narrow rows make the bytes the roof: 2.87 GB a layer, 14.0 ms
    ("seqrec-qwen3-next-80b-a3b-ep16", 16384, 4 * 10240, 1.03, 14.0,
     "memory"),
])
def test_the_count_is_the_issues_figures(config, tokens, slots, tflop,
                                         ms_at_peak, bound):
    ops, nbytes = grouped_product.counts(evidence(config, tokens, slots), {},
                                         480)
    assert ops / 8 == pytest.approx(tflop * 1e12, rel=1e-2)
    least, roof = R.least_time_s(ops, nbytes, KIND)
    assert roof == bound
    assert least / 8 == pytest.approx(ms_at_peak * 1e-3, rel=1e-2)
    s = shapes(config, tokens)
    d, w = s["d_model"], s["moe_width"]
    held = s["held_experts"][1] - s["held_experts"][0]
    layers = s["n_layers"] - s.get("first_dense_layers", 0)
    assert nbytes == 12 * 4.0 * 8 * (slots * (d + w) + layers * held * d * w)


def test_the_count_follows_the_routed_slots_and_never_a_passes_rows():
    few = grouped_product.counts(
        evidence("seqrec-kimi-vl-a3b-ep8", 16384, 1000), {}, 480)
    many = grouped_product.counts(
        evidence("seqrec-kimi-vl-a3b-ep8", 16384, 2000), {}, 480)
    assert many[0] == 2 * few[0]
    # the window's slots are shared among its trains: one is traced
    one = grouped_product.counts(
        evidence("seqrec-kimi-vl-a3b-ep8", 16384, 1000, jobs=1), {}, 480)
    assert one == few


def test_a_train_without_steps_slots_or_experts_counts_nothing():
    ev = evidence("seqrec-kimi-vl-a3b-ep8", 16384, 1000)
    assert grouped_product.counts(
        {**ev, "shapes": {**ev["shapes"], "steps": 0}}, {}, 1) is None
    assert grouped_product.counts(
        {**ev, "shapes": {**ev["shapes"], "ffn": "swiglu"}}, {}, 1) is None
    assert grouped_product.counts({**ev, "registry_after": {}}, {}, 1) is None
    assert grouped_product.counts({**ev, "jobs": []}, {}, 1) is None
    assert grouped_product.counts({"shapes": {"n_users": 1}}, {}, 1) is None


def metric(name):
    with open(os.path.join(manifest.ROOT, "benchmarks", "layer_metrics",
                           f"{name}.json")) as f:
        return json.load(f)


def test_least_time_reads_100_and_the_kernels_time_per_call():
    ev = evidence("seqrec-kimi-vl-a3b-ep8", 16384, 5 * 12288)
    ops, nbytes = grouped_product.counts(ev, {}, 480)
    least, _ = R.least_time_s(ops, nbytes, KIND)
    ev = evidence("seqrec-kimi-vl-a3b-ep8", 16384, 5 * 12288, least)
    assert layer_readers.read(ev, metric("expert_kernel_roofline")) \
        == pytest.approx(100.0)
    slow = evidence("seqrec-kimi-vl-a3b-ep8", 16384, 5 * 12288, 4 * least)
    assert layer_readers.read(slow, metric("expert_kernel_roofline")) \
        == pytest.approx(25.0)
    assert layer_readers.read(ev, metric("expert_kernel_ms")) \
        == pytest.approx(1000.0 * least / 480)


@pytest.mark.parametrize("series,want", [
    ([[{"impl": "pallas"}, 491520.0]], 100.0),
    ([[{"impl": "pallas"}, 122880.0], [{"impl": "xla"}, 368640.0]], 25.0),
    ([[{"impl": "xla"}, 491520.0]], 0.0),
    (None, None),                       # the parent: no such counter
])
def test_token_pct_is_the_pallas_share_of_the_routed_slots(series, want):
    ev = evidence("seqrec-kimi-vl-a3b-ep8", 16384, 5 * 12288)
    if series is not None:
        ev["registry_after"][
            "pio_train_seqrec_expert_product_tokens_total"] = series
    assert layer_readers.read(ev, metric("expert_kernel_token_pct")) == want


def test_a_program_without_the_kernels_or_the_counter_reports_nothing():
    """The parent under this PR's files: `ragged-dot` calls in its trace,
    no such counter in its registry."""
    ev = evidence("seqrec-kimi-vl-a3b-ep8", 16384, 5 * 12288)
    ev["trace"]["ops"] = [["ragged-dot-none.12", 480, 1.15],
                          ["flash_attention_pallas_bwd.1_tpu_custom_call",
                           8, 0.25]]
    for name in NAMES:
        assert layer_readers.read(ev, metric(name)) is None
    ev["trace"] = None
    for name in NAMES:
        assert layer_readers.read(ev, metric(name)) is None


def test_the_three_metrics_list_the_sequence_cells():
    bench = manifest.load_benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NAMES:
        file = metric(name)
        for key in ("unit", "better", "source", "layer", "moves"):
            assert entries[name][key] == file[key], (name, key)
        assert entries[name]["layer"] == "expert layer"
        assert entries[name]["moves"] == "train_wall_s"
        assert entries[name]["workloads"] == list(CELLS)
    assert manifest.check(bench) == []
