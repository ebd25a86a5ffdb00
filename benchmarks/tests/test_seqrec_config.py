"""The `seqrec-kimi-vl-a3b-ep8` configuration: its file against the
catalog row and against the parameters it hands the program; its events;
its check's controls, each failing `correct` by a named row; its counts
and readers. At the rehearsal's size on the CPU; PERF.md has the controls'
readings on the chip at the cell's size."""
import dataclasses
import json
import math
import os

import numpy as np
import pytest

from benchmarks.checks import seqrec_reference as ref
from benchmarks.checks import seqrec_step
from benchmarks.counts import seqrec_model
from benchmarks.events import sessions_longhist
from benchmarks.lib import layer_readers, manifest

NAME = "seqrec-kimi-vl-a3b-ep8"
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
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
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
            "n_layers": "num_hidden_layers", "ffn_width": "intermediate_size",
            "moe_width": "moe_intermediate_size",
            "first_dense_layers": "first_k_dense_replace",
            "qk_nope_head_dim": "qk_nope_head_dim",
            "qk_rope_head_dim": "qk_rope_head_dim",
            "v_head_dim": "v_head_dim", "kv_lora_rank": "kv_lora_rank",
            "experts_per_token": "num_experts_per_tok",
            "n_shared_experts": "n_shared_experts",
            "routed_scaling_factor": "routed_scaling_factor",
            "norm_topk_prob": "norm_topk_prob", "norm_eps": "rms_norm_eps",
            "rope_theta": "rope_theta"}
    for ours, theirs in same.items():
        assert ap[ours] == config[theirs], ours
    # the router keeps its published width; the held range is the file's
    assert ap["n_routed_experts"] == config["published"]["n_routed_experts"]
    lo, hi = ap["held_experts"]
    assert hi - lo == config["n_routed_experts"]
    assert ap["tied_head"] is config["tie_word_embeddings"]
    assert config["n_items"] + 1 == config["vocab_size"]
    assert ap["max_len"] + 1 == config["session_len"]
    # the guide's floors: a whole period and four layers after the dense
    # one, eight experts, an eighth of the vocabulary
    assert ap["n_layers"] - ap["first_dense_layers"] >= 4
    assert hi - lo >= 8
    assert config["vocab_size"] * 8 >= config["published"]["vocab_size"]
    assert (ap["mixer"], ap["ffn"], ap["norm"], ap["positions"]) == \
        ("mla", "moe", "rms", "rope")


@pytest.mark.parametrize("seed", [5, 2**31 + 5])
def test_sessions_cover_the_catalogue_and_can_be_learned(config, seed):
    columns, truth = sessions_longhist.generate(config, seed)
    sessions = truth["sessions"]
    assert sessions.shape == (config["n_users"], config["session_len"])
    assert len(columns["entity_id"]) == sessions.size == 131_088
    assert np.array_equal(np.unique(sessions),
                          np.arange(1, config["n_items"] + 1))
    again = sessions_longhist.generate(config, seed)[1]["sessions"]
    assert np.array_equal(again, sessions)
    # one successor an item, nine times in ten
    follows = {}
    for a, b in zip(sessions[:, :-1].ravel().tolist(),
                    sessions[:, 1:].ravel().tolist()):
        follows.setdefault(a, []).append(b)
    top = sum(max(np.bincount(v)) for v in follows.values())
    assert 0.88 < top / (sessions.size - len(sessions)) < 0.93
    # rows shuffled: the store does not hold a session in order
    user1 = np.asarray(columns["event_time_ms"])[
        np.asarray(columns["entity_id"]) == 1]
    assert (np.diff(user1) < 0).any()


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


def rows_of(tiny, releases, program=None, unmoved=None, bias_err=None):
    start, trained, sessions = releases
    seqs, targets = seqrec_step.first_batch(tiny, sessions)
    spec = ref.Spec.of(tiny["algorithm_params"], recompute=True)
    reference = seqrec_step.reference_numbers(start.params, seqs, targets,
                                              spec)
    if program is not None:
        program = seqrec_step.reference_numbers(
            start.params, seqs, targets,
            dataclasses.replace(spec, **program))
    rows = seqrec_step.compare(
        program or seqrec_step.program_numbers(trained.record), reference,
        trained.record, seqrec_step.groups_unmoved(
            start.params, trained.params) if unmoved is None else unmoved,
        seqrec_step.router_bias_err(trained.params, trained.record, spec)
        if bias_err is None else bias_err, tiny["limits"])
    return {r[0]: r for r in rows}


def failed(rows):
    return sorted(name for name, row in rows.items() if not row[3])


def test_the_sound_train_is_correct(tiny, releases):
    rows = rows_of(tiny, releases)
    assert not failed(rows), rows
    assert set(rows) == set(tiny["limits"])


def test_the_first_batch_is_made_from_the_sessions_alone(tiny, releases):
    """The check asks the release neither for its vocabulary nor for the
    sessions of its first step; what it makes of the generated sessions
    is what the program trained on."""
    _, trained, sessions = releases
    ap = tiny["algorithm_params"]
    rows = seqrec_step.epoch0_rows(ap, len(sessions))
    assert [rows[i:i + 2].tolist() for i in range(0, 6, 2)] == \
        trained.record["rows"][:3]
    seqs, targets = seqrec_step.first_batch(tiny, sessions)
    ordered = seqrec_step.program_order(sessions)[rows[:2]]
    assert [trained.item_code(str(it)) for it in ordered[0][:-1]] == \
        seqs[0].tolist()
    assert [trained.item_code(str(it)) for it in ordered[1][1:]] == \
        targets[1].tolist()


def test_int8_operands_are_not_correct(tiny, releases):
    """The next precision below the stated one, in the program's place."""
    rows = rows_of(tiny, releases, program={"precision": "int8"})
    assert not rows["seqrec_grad_norm_rel_err.attention"][3]
    assert not rows["seqrec_loss_rel_err"][3]


def test_a_held_expert_left_out_is_not_correct(tiny, releases):
    lo, hi = tiny["algorithm_params"]["held_experts"]
    rows = rows_of(tiny, releases, program={"held_experts": (lo, hi - 1)})
    assert not rows["seqrec_grad_norm_rel_err.experts"][3]


def test_a_train_that_returns_its_state_unchanged_is_not_correct(
        tiny, releases):
    start, trained, sessions = releases
    same = seqrec_step.groups_unmoved(start.params, start.params)
    assert same == len(ref.group_norms(start.params))
    rows = rows_of(tiny, releases, unmoved=same)
    assert failed(rows) == ["seqrec_groups_unmoved"]
    # and a number that is not finite is not ok
    record = dict(trained.record, loss=[math.nan] * 2)
    bad = seqrec_step.compare(
        {"loss": math.nan, "grad_norm": {}, "update_norm": {},
         "load": np.zeros((2, 8))},
        {"loss": 1.0, "grad_norm": {"embedding": 1.0},
         "update_norm": {"embedding": 1.0}, "load": np.ones((2, 8))},
        record, 0, math.nan, tiny["limits"])
    assert [r[3] for r in bad] == [False, False, False, False, False, True,
                                   True, False]


def test_a_wrong_optimizer_is_not_correct(tiny, releases):
    """A learning rate ten times off fails by every part's update; a
    selection bias moved the wrong way, or ten times as fast, by the bias
    row; neither by any row of the gradient."""
    rows = rows_of(tiny, releases, program={
        "learning_rate": 10 * tiny["algorithm_params"]["learning_rate"]})
    assert failed(rows) == sorted(
        name for name in rows if name.startswith("seqrec_update_norm"))
    assert len(failed(rows)) == 8
    start, trained, _ = releases
    spec = ref.Spec.of(tiny["algorithm_params"])
    assert seqrec_step.router_bias_err(trained.params, trained.record,
                                       spec) < 1e-7
    for fault in (lambda b: -b, lambda b: 10 * b):
        moved = {"layers": [
            dict(layer, router_bias=fault(layer["router_bias"]))
            if "router_bias" in layer else layer
            for layer in trained.params["layers"]]}
        err = seqrec_step.router_bias_err(moved, trained.record, spec)
        assert failed(rows_of(tiny, releases, bias_err=err)) == \
            ["seqrec_router_bias_err"]


def test_the_models_operations_by_count(config):
    """ISSUE 27's arithmetic: about 1.9 GFLOP a token in matrix products
    over 313 M active parameters, 0.8 in causal attention at 8k."""
    shapes = {**config["algorithm_params"], "n_vocab": 20480, "steps": 8,
              "tokens_per_step": 16384}
    tokens = 8 * 16384
    slots = tokens * 6 * 8 / 64 * 5        # the mean: 1,536 a held expert
    ops = seqrec_model.counts(shapes, slots)
    attention = seqrec_model.counts(shapes, slots) - seqrec_model.counts(
        {**shapes, "max_len": 0}, slots)
    assert 0.74e9 < attention / tokens < 0.77e9
    assert 1.85e9 < (ops - attention) / tokens < 1.92e9
    assert 0.33e15 < ops < 0.37e15      # 2.7 GFLOP a token, 131,072 tokens


def test_the_new_readers_return_nothing_from_a_program_without_them():
    """The parent commit has no such span or counter: the metric is left
    out of the line and nothing raises."""
    bench = manifest.load_benchmark()
    evidence = {"jobs": [{"wall_s": 1.0, "spans": {"als_solve": 1.0}}],
                "registry_before": {}, "registry_after": {},
                "device": {"kind": "TPU v5 lite"}, "shapes": {"rank": 64},
                "trace": None, "memory": {}}
    new = [m["name"] for m in bench["per_layer"]
           if m.get("workloads") == ["kimivl-a3b-ep8.train"]]
    assert len(new) == 9
    for name in new:
        assert layer_readers.read(
            evidence, manifest.load_layer_reader(name)) is None, name


def test_the_new_readers_read_what_the_program_counts(config):
    shapes = {**config["algorithm_params"], "n_vocab": 20480, "steps": 12,
              "tokens_per_step": 16384}
    after = {"pio_train_seqrec_pad_tokens_total": [[{}, 50.0]],
             "pio_train_seqrec_tokens_total": [[{}, 150.0]],
             "pio_train_seqrec_expert_tokens_total":
                 [[{"layer": str(i)}, 2 * 147456.0] for i in range(5)]}
    evidence = {"jobs": [{"spans": {"seqrec_steps": 10.0}},
                         {"spans": {"seqrec_steps": 12.0}}],
                "registry_before": {}, "registry_after": after,
                "device": {"kind": "TPU v5 lite"}, "shapes": shapes}
    read = lambda name: layer_readers.read(
        evidence, manifest.load_layer_reader(name))
    assert read("seqrec_pad_pct") == 25.0
    assert read("seqrec_steps_s") == 11.0
    want = 100 * seqrec_model.counts(shapes, 5 * 147456.0) / 11.0 / 197e12
    assert read("seqrec_model_flops_pct") == pytest.approx(want)
    assert 20 < want < 30
    evidence["device"]["kind"] = "cpu"          # no peak, no share
    assert read("seqrec_model_flops_pct") is None
