"""The `seqrec-lfm2-24b-a2b-ep8` configuration: its file against the
catalog row and against the parameters it hands the program; its check's
first batch against what the program trained on; its check's controls,
each failing `correct` by a named row; its counts and readers. At the
rehearsal's size on the CPU; PERF.md has the controls' readings on the
chip at the cell's size. What is asserted of `BENCHMARK.json` is what it
contains, never what it equals or how long a list is: later PRs append."""
import dataclasses
import json
import os

import numpy as np
import pytest

from benchmarks.checks import seqrec_conv_reference as ref
from benchmarks.checks import seqrec_conv_step as conv_step
from benchmarks.checks import seqrec_step
from benchmarks.counts import gqa_attention_kernel, seqrec_conv_model
from benchmarks.events import sessions_longhist
from benchmarks.lib import layer_readers, manifest

NAME = "seqrec-lfm2-24b-a2b-ep8"
CELL = "lfm2-a2b-ep8.train"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["layer_types", "num_dense_layers", "num_experts",
           "num_hidden_layers", "vocab_size"]


@pytest.fixture(scope="module")
def config():
    return manifest.load_config(manifest.load_benchmark(), NAME)


@pytest.fixture(scope="module")
def tiny(config):
    return {**config, **config["tiny"]}


def test_the_file_holds_the_catalog_row_but_for_what_is_reduced(config):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == config["source"])
    entry = next(c for c in manifest.load_benchmark()["configs"]
                 if c["name"] == NAME)
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == REDUCED
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config[key] != value
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    # no width is cut: what is reduced counts layers, experts held, rows
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in config["reduced"])
    # published layers 1-5: a dense convolution layer, then a whole period
    assert config["layer_types"] == row["config"]["layer_types"][1:6] == \
        ["conv", "full_attention", "conv", "conv", "conv"]


def test_the_program_is_handed_the_published_widths(config):
    ap = config["algorithm_params"]
    same = {"d_model": "hidden_size", "n_heads": "num_attention_heads",
            "n_kv_heads": "num_key_value_heads",
            "n_layers": "num_hidden_layers",
            "first_dense_layers": "num_dense_layers",
            "ffn_width": "intermediate_size",
            "moe_width": "moe_intermediate_size",
            "conv_kernel": "conv_L_cache",
            "experts_per_token": "num_experts_per_tok",
            "norm_topk_prob": "norm_topk_prob", "norm_eps": "norm_eps",
            "routed_scaling_factor": "routed_scaling_factor"}
    for ours, theirs in same.items():
        assert ap[ours] == config[theirs], ours
    assert (ap["d_model"], ap["ffn_width"], ap["moe_width"], ap["n_heads"],
            ap["n_kv_heads"], ap["conv_kernel"], ap["experts_per_token"],
            ap["norm_eps"]) == (2048, 11776, 1536, 32, 8, 3, 4, 1e-5)
    # the head width is hidden / heads; rotary positions on all of it
    assert ap["head_dim"] == config["hidden_size"] \
        // config["num_attention_heads"] == ap["rotary_dim"] == 64
    assert ap["rope_theta"] == config["rope_parameters"]["rope_theta"] == 1e6
    kinds = {"conv": "conv", "full_attention": "gqa"}
    assert ap["mixer"] == [kinds[k] for k in config["layer_types"]]
    assert len(ap["mixer"]) == ap["n_layers"] == 5
    assert ap["attention_gate"] is False and config["conv_bias"] is False
    # the router keeps its published width; the held range is the file's
    assert ap["n_routed_experts"] == config["published"]["num_experts"] == 64
    lo, hi = ap["held_experts"]
    assert hi - lo == config["num_experts"] >= 8
    assert ap["n_shared_experts"] == 0 and ap["tied_head"] is True
    assert config["use_expert_bias"] is True and ap["bias_update_rate"] > 0
    assert (ap["router_scoring"], ap["router_norm_eps"]) == ("sigmoid", 1e-6)
    assert (ap["ffn"], ap["norm"], ap["positions"]) == ("moe", "rms", "rope")
    assert ap["balance_loss_alpha"] == 0.0
    assert config["n_items"] + 1 == config["vocab_size"]
    assert config["vocab_size"] * 8 >= config["published"]["vocab_size"]
    assert ap["max_len"] + 1 == config["session_len"]
    assert set(config["reduced"]) == set(config["held"]) \
        == set(config["published"])
    # every key of the file's algorithm_params is one of the program's
    from predictionio_tpu.models import seqrec

    seqrec.SeqRecParams(**ap).check()
    seqrec.SeqRecParams(**config["tiny"]["algorithm_params"]).check()
    assert set(config["tiny"]["algorithm_params"]) == set(ap)


def test_the_programs_own_parameter_count(config):
    """ISSUE 33's arithmetic: 469.3 M parameters, 7.51 GB at 16 bytes."""
    import jax

    from predictionio_tpu.models import seqrec

    p = seqrec.SeqRecParams(**config["algorithm_params"])
    shapes = jax.eval_shape(
        lambda: seqrec.init_params(None, config["n_items"], p))
    leaves = dict(jax.tree_util.tree_leaves_with_path(shapes))
    count = lambda pick: sum(int(np.prod(v.shape)) for k, v in leaves.items()
                             if pick(jax.tree_util.keystr(k)))
    assert count(lambda k: True) == 469_285_248
    assert count(lambda k: "[0]" in k and "conv_" in k) \
        == 2048 * 6144 + 2048 * 2048 + 3 * 2048 == 16_783_360
    full = ("'wq'", "wk", "wv", "q_norm", "k_norm", "'wo'")
    assert count(lambda k: "[1]" in k and any(n in k for n in full)) \
        == 2 * 2048 * 2048 + 2 * 2048 * 512 + 128 == 10_485_888
    assert count(lambda k: "[0]" in k and "w_" in k) == 3 * 2048 * 11776
    assert count(lambda k: "[3]" in k and "experts" in k) \
        == 8 * 3 * 2048 * 1536
    assert count(lambda k: "emb" in k) == 8_192 * 2048    # once: tied
    assert not count(lambda k: "head" in k or "shared" in k)


@pytest.mark.parametrize("seed", [5, 2**31 + 5])
def test_sessions_cover_the_catalogue(config, seed):
    columns, truth = sessions_longhist.generate(config, seed)
    sessions = truth["sessions"]
    assert sessions.shape == (8, 32_769)
    assert len(columns["entity_id"]) == sessions.size == 262_152
    assert np.array_equal(np.unique(sessions),
                          np.arange(1, config["n_items"] + 1))


@pytest.fixture(scope="module")
def releases(tiny):
    """(theta_0's release, the trained release, the sessions) of the
    rehearsal's train, through the program's own train."""
    from predictionio_tpu.models import seqrec

    _, truth = sessions_longhist.generate(tiny, 2**31 + 41)
    ids = np.argsort([str(u + 1) for u in range(tiny["n_users"])])
    sessions = [[str(i) for i in truth["sessions"][u]] for u in ids]
    params = seqrec.SeqRecParams(**tiny["algorithm_params"])
    trained = seqrec.train_seqrec(None, sessions, params)
    start = seqrec.train_seqrec(None, sessions,
                                dataclasses.replace(params, epochs=0))
    return start, trained, truth["sessions"]


@pytest.fixture(scope="module")
def reference(tiny, releases):
    start, _, sessions = releases
    seqs, targets = seqrec_step.first_batch(tiny, sessions)
    spec = ref.Spec.of(tiny["algorithm_params"], recompute=True)
    grads = ref.loss_and_grads(start.params, seqs, targets, spec)
    return (seqs, targets, spec, grads, conv_step.reference_numbers(
        start.params, seqs, targets, spec, grads))


def rows_of(tiny, releases, reference, program=None, unmoved=None,
            bias_err=None):
    start, trained, _ = releases
    seqs, targets, spec, grads, sound = reference
    if program is not None:
        program = conv_step.reference_numbers(
            start.params, seqs, targets, dataclasses.replace(spec, **program),
            grads if set(program) == {"learning_rate"} else None)
    rows = seqrec_step.compare(
        program or seqrec_step.program_numbers(trained.record), sound,
        trained.record, conv_step.groups_unmoved(
            start.params, trained.params) if unmoved is None else unmoved,
        seqrec_step.router_bias_err(trained.params, trained.record, spec)
        if bias_err is None else bias_err, tiny["limits"])
    return {r[0]: r for r in rows}


def failed(rows):
    return sorted(name for name, row in rows.items() if not row[3])


def test_the_sound_train_is_correct(tiny, releases, reference):
    rows = rows_of(tiny, releases, reference)
    assert not failed(rows), rows
    assert set(rows) == set(tiny["limits"])
    parts = {name.split(".")[1] for name in rows if "." in name}
    assert parts == {"short_conv", "attention", "ffn", "experts", "router",
                     "embedding", "norms"}


def test_the_full_sizes_limits_name_the_same_rows(config):
    assert set(config["limits"]) == set(config["tiny"]["limits"])


def test_the_first_batch_is_made_from_the_sessions_alone(tiny, releases):
    _, trained, sessions = releases
    ap = tiny["algorithm_params"]
    rows = seqrec_step.epoch0_rows(ap, len(sessions))
    assert [[r] for r in rows[:3].tolist()] == trained.record["rows"][:3]
    seqs, targets = seqrec_step.first_batch(tiny, sessions)
    ordered = seqrec_step.program_order(sessions)[rows[:1]]
    assert [trained.item_code(str(it)) for it in ordered[0][:-1]] == \
        seqs[0].tolist()
    assert [trained.item_code(str(it)) for it in ordered[0][1:]] == \
        targets[0].tolist()


@pytest.mark.parametrize("fault,row", [
    # the next precision below the stated one, in the program's place
    ({"precision": "int8"}, "seqrec_grad_norm_rel_err.short_conv"),
    ({"held_experts": (0, 3)}, "seqrec_grad_norm_rel_err.experts"),
    ({"conv_gate": False}, "seqrec_grad_norm_rel_err.short_conv"),
    ({"qk_norm": False}, "seqrec_grad_norm_rel_err.attention"),
])
def test_a_fault_is_not_correct(tiny, releases, reference, fault, row):
    rows = rows_of(tiny, releases, reference, program=fault)
    assert not rows[row][3], rows[row]
    assert not rows["seqrec_loss_rel_err"][3]


def test_a_wrong_optimizer_or_an_unchanged_state_is_not_correct(
        tiny, releases, reference):
    """A learning rate ten times off fails by every part's update and no
    other row; a selection bias moved the wrong way, or ten times as
    fast, by the bias row alone; an unchanged state by its own row."""
    rows = rows_of(tiny, releases, reference, program={
        "learning_rate": 10 * tiny["algorithm_params"]["learning_rate"]})
    assert failed(rows) == sorted(
        name for name in rows if name.startswith("seqrec_update_norm"))
    assert len(failed(rows)) == 7
    start, trained, _ = releases
    spec = reference[2]
    assert seqrec_step.router_bias_err(trained.params, trained.record,
                                       spec) < 1e-7
    for fault in (lambda b: -b, lambda b: 10 * b):
        moved = {"layers": [
            dict(layer, router_bias=fault(layer["router_bias"]))
            if "router_bias" in layer else layer
            for layer in trained.params["layers"]]}
        err = seqrec_step.router_bias_err(moved, trained.record, spec)
        assert failed(rows_of(tiny, releases, reference, bias_err=err)) == \
            ["seqrec_router_bias_err"]
    same = conv_step.groups_unmoved(start.params, start.params)
    assert same == len(ref.group_norms(start.params))
    assert failed(rows_of(tiny, releases, reference, unmoved=same)) == \
        ["seqrec_groups_unmoved"]


def shapes_of(config, steps=8):
    return {**config["algorithm_params"], "n_vocab": 8_192, "steps": steps,
            "tokens_per_step": 32_768}


def test_the_models_operations_by_count(config):
    """ISSUE 33's arithmetic: 186.2 M matrix parameters a token pass
    (89.1 the dense layer, 15.3 the attention layer, 3 x 21.6 the
    convolution layers, 16.8 the head; half a routed slot a token and
    layer), x 6 x 32,768 = 36.6 TFLOP a step; the attention layer's
    1.718e10 pairs x 768 = 13.2 TFLOP in the model's own count, x 1,152 =
    19.8 in the kernels' contract, which counts the forward call `remat`
    repeats."""
    shapes = shapes_of(config, steps=1)
    tokens = 32_768
    slots = tokens * 4 * 8 / 64 * 4          # the mean: 2,048 a held expert
    ops = seqrec_conv_model.counts(shapes, slots)
    no_pairs = seqrec_conv_model.counts({**shapes, "max_len": 0}, slots)
    assert ops == pytest.approx(49.8e12, rel=0.002)
    assert ops - no_pairs == 32 * tokens * tokens / 2 * 768
    assert ops - no_pairs == pytest.approx(13.2e12, rel=0.002)
    assert no_pairs / tokens / 6 == pytest.approx(186.2e6, rel=0.001)
    by_hand = (2048 * 6144 + 3 * 2048 + 2048 * 2048) * 4 \
        + 2 * 2048 * 2048 + 2 * 2048 * 512 + 3 * 2048 * 11776 \
        + 4 * 2048 * 64 + 8_192 * 2048 + 4 * 0.5 * 3 * 2048 * 1536
    assert no_pairs == 6.0 * tokens * by_hand
    # a gate would add the gate's half of the query projection
    gated = seqrec_conv_model.counts({**shapes, "attention_gate": True},
                                     slots)
    assert gated - ops == 6.0 * tokens * 2048 * 2048
    step_ops, step_bytes = gqa_attention_kernel.counts(
        {"shapes": shapes}, {}, 3)
    pairs = 32 * 32_768 * 32_769 / 2
    assert step_ops == pairs * (2 * 256 + 640)        # two forward calls
    assert step_ops == pytest.approx(19.8e12, rel=0.002)
    # K and V of 8 heads read once beside q and o of 32
    assert step_bytes == 32_768 * 64 * 4 * (2 * (2 * 32 + 2 * 8)
                                            + 3 * 32 + 4 * 8)


def test_the_new_readers_return_nothing_from_a_program_without_them():
    """The parent commit has no such counter label and no such shapes:
    the metric is left out of the line and nothing raises."""
    bench = manifest.load_benchmark()
    new = [m["name"] for m in bench["per_layer"]
           if m.get("workloads") == [CELL]]
    assert {"mixer_conv_token_pct", "seqrec_conv_mfu_pct"} <= set(new)
    evidence = {"jobs": [{"wall_s": 1.0, "spans": {"als_solve": 1.0}}],
                "registry_before": {}, "registry_after": {},
                "device": {"kind": "TPU v5 lite"}, "shapes": {"rank": 64},
                "trace": None, "memory": {}}
    for name in ("mixer_conv_token_pct", "seqrec_conv_mfu_pct"):
        assert layer_readers.read(
            evidence, manifest.load_layer_reader(name)) is None, name
    # a step of other mixers moved the counter under other labels: 0
    evidence["registry_after"] = {"pio_train_seqrec_mixer_tokens_total": [
        [{"mixer": "gdn"}, 300.0], [{"mixer": "gqa"}, 100.0]]}
    assert layer_readers.read(evidence, manifest.load_layer_reader(
        "mixer_conv_token_pct")) == 0.0


def test_the_new_readers_read_what_the_program_counts(config):
    shapes = shapes_of(config, steps=8)
    positions = 8 * 32_768.0
    after = {"pio_train_seqrec_mixer_tokens_total": [
        [{"mixer": "conv"}, 2 * 4 * positions],
        [{"mixer": "gqa"}, 2 * positions]],
        "pio_train_seqrec_expert_tokens_total":
            [[{"layer": str(i)}, 2 * 131_072.0] for i in range(4)]}
    evidence = {"jobs": [{"spans": {"seqrec_steps": 7.0}},
                         {"spans": {"seqrec_steps": 9.0}}],
                "registry_before": {}, "registry_after": after,
                "device": {"kind": "TPU v5 lite"}, "shapes": shapes}
    read = lambda name: layer_readers.read(
        evidence, manifest.load_layer_reader(name))
    assert read("mixer_conv_token_pct") == 80.0
    want = 100 * seqrec_conv_model.counts(shapes, 4 * 131_072.0) \
        / 8.0 / 197e12
    assert read("seqrec_conv_mfu_pct") == pytest.approx(want)
    assert want == pytest.approx(100 * 8 * 49.8e12 / 8.0 / 197e12, rel=0.01)
    evidence["device"]["kind"] = "cpu"          # no peak, no share
    assert read("seqrec_conv_mfu_pct") is None


def test_the_cell_lists_what_it_feeds_and_not_what_it_cannot():
    bench = manifest.load_benchmark()
    assert not manifest.check(bench)
    mine = {m["name"] for m in manifest.metrics_of_cell(bench, CELL,
                                                        "per_layer")}
    assert {"mixer_conv_token_pct", "seqrec_conv_mfu_pct",
            "gqa_attention_kernel_roofline", "attention_kernel_token_pct",
            "attention_kernel_fwd_ms", "attention_kernel_bwd_ms",
            "moe_dropped_tokens", "moe_expert_load_max_over_mean",
            "seqrec_step_ms", "seqrec_steps_s", "seqrec_fetch_s",
            "hbm_peak_in_use_bytes.train", "device_idle_pct.train",
            "train_persist_s", "ingest_scan_s"} <= mine
    # latent attention's counts, the hybrid's, the delta rule's: not its own
    assert not mine & {"seqrec_model_flops_pct", "attention_kernel_roofline",
                       "seqrec_hybrid_mfu_pct", "mixer_linear_token_pct",
                       "linear_attention_kernel_token_pct",
                       "gdn_kernel_fwd_ms", "gdn_kernel_bwd_ms",
                       "gdn_kernel_roofline", "als_solve_s"}
    assert CELL in next(m for m in bench["end_to_end"]
                        if m["name"] == "train_wall_s")["workloads"]
    cell = manifest.find_cell(bench, CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "train-backtoback")
    assert manifest.load_traffic(cell)["warm_jobs"] == 2
