"""The `seqrec-qwen3-next-80b-a3b-ep16` configuration: its file against
the catalog row and against the parameters it hands the program; its
check's first batch against what the program trained on; its check's
controls, each failing `correct` by a named row; its counts and readers.
At the rehearsal's size on the CPU; PERF.md has the controls' readings on
the chip at the cell's size."""
import dataclasses
import json
import os

import numpy as np
import pytest

from benchmarks.checks import seqrec_hybrid_reference as ref
from benchmarks.checks import seqrec_hybrid_step as hybrid_step
from benchmarks.checks import seqrec_step
from benchmarks.counts import gqa_attention_kernel, seqrec_hybrid_model
from benchmarks.events import sessions_longhist
from benchmarks.lib import layer_readers, manifest

NAME = "seqrec-qwen3-next-80b-a3b-ep16"
CELL = "qwen3next-a3b-ep16.train"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


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
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == \
        ["num_experts", "num_hidden_layers", "vocab_size"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config[key] != value
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in config["reduced"])


def test_the_program_is_handed_the_published_widths(config):
    ap = config["algorithm_params"]
    same = {"d_model": "hidden_size", "n_heads": "num_attention_heads",
            "n_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
            "n_layers": "num_hidden_layers",
            "moe_width": "moe_intermediate_size",
            "linear_key_heads": "linear_num_key_heads",
            "linear_value_heads": "linear_num_value_heads",
            "linear_key_head_dim": "linear_key_head_dim",
            "linear_value_head_dim": "linear_value_head_dim",
            "linear_conv_kernel": "linear_conv_kernel_dim",
            "experts_per_token": "num_experts_per_tok",
            "norm_topk_prob": "norm_topk_prob", "norm_eps": "rms_norm_eps",
            "rope_theta": "rope_theta"}
    for ours, theirs in same.items():
        assert ap[ours] == config[theirs], ours
    assert ap["rotary_dim"] == config["partial_rotary_factor"] \
        * config["head_dim"] == 64
    # one shared expert of the published width, gated
    assert ap["n_shared_experts"] * ap["moe_width"] == \
        config["shared_expert_intermediate_size"]
    assert ap["shared_expert_gate"] is True
    # one period of the published interval, whole
    period = config["full_attention_interval"]
    assert ap["mixer"] == ["gdn"] * (period - 1) + ["gqa"]
    assert ap["n_layers"] % period == 0 and ap["n_layers"] >= 4
    assert config["decoder_sparse_step"] == 1 and not config["mlp_only_layers"]
    assert (ap["ffn"], ap.get("first_dense_layers", 0)) == ("moe", 0)
    # the router keeps its published width; the held range is the file's
    assert ap["n_routed_experts"] == config["published"]["num_experts"]
    lo, hi = ap["held_experts"]
    assert hi - lo == config["num_experts"] >= 8
    assert ap["tied_head"] is config["tie_word_embeddings"]
    assert config["n_items"] + 1 == config["vocab_size"]
    assert config["vocab_size"] * 8 >= config["published"]["vocab_size"]
    assert ap["max_len"] + 1 == config["session_len"]
    assert (ap["norm"], ap["positions"], ap["router_scoring"]) == \
        ("rms_zero_centered", "rope", "softmax")
    # no balance term, no scaling: the config names neither
    assert (ap["bias_update_rate"], ap["balance_loss_alpha"],
            ap["routed_scaling_factor"]) == (0.0, 0.0, 1.0)
    # every key of the file's algorithm_params is one of the program's
    from predictionio_tpu.models import seqrec

    seqrec.SeqRecParams(**ap).check()
    seqrec.SeqRecParams(**config["tiny"]["algorithm_params"]).check()


def test_the_programs_own_parameter_count(config):
    """ISSUE 31's arithmetic: 625.7 M parameters, 10.0 GB at 16 bytes."""
    import jax

    from predictionio_tpu.models import seqrec

    p = seqrec.SeqRecParams(**config["algorithm_params"])
    shapes = jax.eval_shape(
        lambda: seqrec.init_params(None, config["n_items"], p))
    leaves = dict(jax.tree_util.tree_leaves_with_path(shapes))
    count = lambda pick: sum(int(np.prod(v.shape)) for k, v in leaves.items()
                             if pick(jax.tree_util.keystr(k)))
    assert count(lambda k: True) == 625_669_184
    linear = ("w_qkvz", "w_ba", "conv", "A_log", "dt_bias", "o_norm", "w_out")
    assert count(lambda k: "[0]" in k and any(n in k for n in linear)) \
        == 33_718_464
    full = ("wq_gate", "wk", "wv", "q_norm", "k_norm", "'wo'")
    assert count(lambda k: "[3]" in k and any(n in k for n in full)) \
        == 27_263_488
    assert count(lambda k: "emb" in k or "head" in k) == 2 * 18_992 * 2048


@pytest.mark.parametrize("seed", [5, 2**31 + 5])
def test_sessions_cover_the_catalogue(config, seed):
    columns, truth = sessions_longhist.generate(config, seed)
    sessions = truth["sessions"]
    assert sessions.shape == (8, 16_385)
    assert len(columns["entity_id"]) == sessions.size == 131_080
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
    return (seqs, targets, spec, grads, hybrid_step.reference_numbers(
        start.params, seqs, targets, spec, grads))


def rows_of(tiny, releases, reference, program=None, unmoved=None):
    start, trained, _ = releases
    seqs, targets, spec, grads, sound = reference
    if program is not None:
        program = hybrid_step.reference_numbers(
            start.params, seqs, targets, dataclasses.replace(spec, **program),
            grads if set(program) == {"learning_rate"} else None)
    rows = hybrid_step.compare(
        program or seqrec_step.program_numbers(trained.record), sound,
        trained.record, hybrid_step.groups_unmoved(
            start.params, trained.params) if unmoved is None else unmoved,
        tiny["limits"])
    return {r[0]: r for r in rows}


def failed(rows):
    return sorted(name for name, row in rows.items() if not row[3])


def test_the_sound_train_is_correct(tiny, releases, reference):
    rows = rows_of(tiny, releases, reference)
    assert not failed(rows), rows
    assert set(rows) == set(tiny["limits"])
    parts = {name.split(".")[1] for name in rows if "." in name}
    assert parts == {"linear_attention", "attention", "experts", "router",
                     "shared_expert", "head", "embedding", "norms"}


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
    ({"precision": "int8"}, "seqrec_grad_norm_rel_err.linear_attention"),
    ({"held_experts": (0, 3)}, "seqrec_grad_norm_rel_err.experts"),
    ({"zero_decay_layer": 1}, "seqrec_grad_norm_rel_err.linear_attention"),
    ({"attention_gate": False}, "seqrec_grad_norm_rel_err.attention"),
])
def test_a_fault_is_not_correct(tiny, releases, reference, fault, row):
    rows = rows_of(tiny, releases, reference, program=fault)
    assert not rows[row][3], rows[row]
    assert not rows["seqrec_loss_rel_err"][3]


def test_a_wrong_optimizer_or_an_unchanged_state_is_not_correct(
        tiny, releases, reference):
    rows = rows_of(tiny, releases, reference, program={
        "learning_rate": 10 * tiny["algorithm_params"]["learning_rate"]})
    assert failed(rows) == sorted(
        name for name in rows if name.startswith("seqrec_update_norm"))
    assert len(failed(rows)) == 8
    start = releases[0]
    same = hybrid_step.groups_unmoved(start.params, start.params)
    assert same == len(ref.group_norms(start.params))
    assert failed(rows_of(tiny, releases, reference, unmoved=same)) == \
        ["seqrec_groups_unmoved"]


def shapes_of(config, steps=8):
    return {**config["algorithm_params"], "n_vocab": 18_992, "steps": steps,
            "tokens_per_step": 16_384}


def test_the_models_operations_by_count(config):
    """ISSUE 31's arithmetic: about 200 M active parameters a token; the
    full layer's 2.15e9 pairs x 4,608 = 9.9 TFLOP a step in the kernels'
    contract, which counts the forward call `remat` repeats, and x 3,072
    = 6.6 TFLOP in the model's own count, which does not."""
    shapes = shapes_of(config)
    tokens = 8 * 16_384
    slots = tokens * 10 * 32 / 512 * 4       # the mean: 320 a held expert
    ops = seqrec_hybrid_model.counts(shapes, slots)
    no_pairs = seqrec_hybrid_model.counts({**shapes, "max_len": 0}, slots)
    assert (ops - no_pairs) / 8 == pytest.approx(6.6e12, rel=0.01)
    # three products over a 128 x 128 state a position and value head,
    # three layers, the backward pass twice the forward
    state = 3 * 3 * tokens * 32 * 3 * 2 * 128 * 128
    assert state / 8 == pytest.approx(0.46e12, rel=0.02)
    assert 190e6 < (no_pairs - state) / tokens / 6 < 215e6
    step_ops, step_bytes = gqa_attention_kernel.counts(
        {"shapes": shapes_of(config, steps=1)}, {}, 3)
    pairs = 16 * 16_384 * 16_385 / 2
    assert step_ops == pairs * (2 * 1024 + 2560)      # two forward calls
    assert step_ops == pytest.approx(9.9e12, rel=0.01)
    # K and V of 2 heads read once: q and o dominate the bytes
    assert step_bytes == 16_384 * 256 * 4 * (2 * (2 * 16 + 2 * 2)
                                             + 3 * 16 + 4 * 2)
    assert gqa_attention_kernel.counts(
        {"shapes": {**shapes, "mixer": "mla"}}, {}, 3) is None


def test_the_new_readers_return_nothing_from_a_program_without_them():
    """The parent commit has no such counter and no such shapes: the
    metric is left out of the line and nothing raises."""
    bench = manifest.load_benchmark()
    new = [m["name"] for m in bench["per_layer"]
           if m.get("workloads") == [CELL]]
    assert new == ["mixer_linear_token_pct", "seqrec_hybrid_mfu_pct",
                   "gqa_attention_kernel_roofline"]
    evidence = {"jobs": [{"wall_s": 1.0, "spans": {"als_solve": 1.0}}],
                "registry_before": {}, "registry_after": {},
                "device": {"kind": "TPU v5 lite"}, "shapes": {"rank": 64},
                "trace": None, "memory": {}}
    for name in new:
        assert layer_readers.read(
            evidence, manifest.load_layer_reader(name)) is None, name
    # the Kimi cell's shapes under the kernels' pattern: nothing either
    kimi = manifest.load_config(bench, "seqrec-kimi-vl-a3b-ep8")
    evidence["shapes"] = {**kimi["algorithm_params"], "steps": 8,
                          "tokens_per_step": 16_384}
    evidence["trace"] = {"device_ops": [
        ["flash_attention_pallas_fwd.1_tpu_custom_call", 96, 0.6]]}
    assert gqa_attention_kernel.counts(evidence, {}, 96) is None


def test_the_new_readers_read_what_the_program_counts(config):
    shapes = shapes_of(config, steps=8)
    after = {"pio_train_seqrec_mixer_tokens_total": [
        [{"mixer": "gdn"}, 3 * 262_144.0], [{"mixer": "gqa"}, 262_144.0]],
        "pio_train_seqrec_expert_tokens_total":
            [[{"layer": str(i)}, 2 * 81_920.0] for i in range(4)]}
    evidence = {"jobs": [{"spans": {"seqrec_steps": 7.0}},
                         {"spans": {"seqrec_steps": 9.0}}],
                "registry_before": {}, "registry_after": after,
                "device": {"kind": "TPU v5 lite"}, "shapes": shapes}
    read = lambda name: layer_readers.read(
        evidence, manifest.load_layer_reader(name))
    assert read("mixer_linear_token_pct") == 75.0
    want = 100 * seqrec_hybrid_model.counts(shapes, 4 * 81_920.0) \
        / 8.0 / 197e12
    assert read("seqrec_hybrid_mfu_pct") == pytest.approx(want)
    assert 5 < want < 40
    evidence["device"]["kind"] = "cpu"          # no peak, no share
    assert read("seqrec_hybrid_mfu_pct") is None


def test_the_cell_lists_what_it_feeds_and_not_what_it_cannot():
    bench = manifest.load_benchmark()
    assert not manifest.check(bench)
    mine = {m["name"] for m in manifest.metrics_of_cell(bench, CELL,
                                                        "per_layer")}
    kimi = {m["name"] for m in manifest.metrics_of_cell(
        bench, "kimivl-a3b-ep8.train", "per_layer")}
    assert kimi - mine == {"seqrec_model_flops_pct",
                           "attention_kernel_roofline"}
    assert mine - kimi == {"mixer_linear_token_pct", "seqrec_hybrid_mfu_pct",
                           "gqa_attention_kernel_roofline"}
    assert manifest.load_traffic(manifest.find_cell(bench, CELL))[
        "warm_jobs"] == 2
