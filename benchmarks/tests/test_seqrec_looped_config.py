"""The `seqrec-ouro-2.6b-pp8` configuration: its file against the catalog
row and against the parameters it hands the program; its check's first
batch against what the program trained on; its check's controls, each
failing `correct` by a named row; its counts and readers. At the
rehearsal's size on the CPU; PERF.md has the controls' readings on the
chip at the cell's size. What is asserted of `BENCHMARK.json` is what it
contains, never what it equals or how long a list is: later PRs append."""
import dataclasses
import json
import os

import numpy as np
import pytest

from benchmarks.checks import seqrec_looped_reference as ref
from benchmarks.checks import seqrec_looped_step as looped_step
from benchmarks.checks import seqrec_step
from benchmarks.counts import mha_attention_kernel, seqrec_looped_model
from benchmarks.events import sessions_longhist
from benchmarks.lib import layer_readers, manifest

NAME = "seqrec-ouro-2.6b-pp8"
CELL = "ouro-2.6b-pp8.train"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("loop_repeat_token_pct", "seqrec_looped_mfu_pct",
       "mha_attention_kernel_roofline")
COUNTER = "pio_train_seqrec_layer_pass_tokens_total"


@pytest.fixture(scope="module")
def config():
    return manifest.load_config(manifest.load_benchmark(), NAME)


@pytest.fixture(scope="module")
def tiny(config):
    return {**config, **config["tiny"]}


def test_the_file_holds_the_catalog_row_but_for_the_depth(config):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == config["source"])
    entry = next(c for c in manifest.load_benchmark()["configs"]
                 if c["name"] == NAME)
    assert entry["reduced"] == list(config["reduced"]) \
        == ["num_hidden_layers"]
    assert entry["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config[key] != value
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    # every width as published; the list of layer kinds whole
    assert (config["hidden_size"], config["intermediate_size"],
            config["head_dim"], config["num_attention_heads"],
            config["num_key_value_heads"], config["vocab_size"],
            config["rope_theta"], config["rms_norm_eps"],
            config["total_ut_steps"], config["early_exit_threshold"],
            config["tie_word_embeddings"]) == (
        2048, 5632, 128, 16, 16, 49152, 1_000_000, 1e-6, 4, 1, False)
    assert config["layer_types"] == ["full_attention"] * 48
    assert (config["num_hidden_layers"],
            config["published"]["num_hidden_layers"]) == (6, 48)
    assert set(config["reduced"]) == set(config["held"]) \
        == set(config["published"])
    for word in ("8 pipeline stages of 6", "layers 1-6", "whole vocabulary"):
        assert word in config["deployment"], word


def test_the_program_is_handed_the_published_widths(config):
    ap = config["algorithm_params"]
    same = {"d_model": "hidden_size", "n_heads": "num_attention_heads",
            "n_layers": "num_hidden_layers", "n_loops": "total_ut_steps",
            "ffn_width": "intermediate_size", "norm_eps": "rms_norm_eps",
            "rope_theta": "rope_theta", "tied_head": "tie_word_embeddings"}
    for ours, theirs in same.items():
        assert ap[ours] == config[theirs], ours
    assert set(ap) == {
        "mixer", "n_layers", "n_loops", "d_model", "n_heads", "ffn",
        "ffn_width", "norm", "norm_eps", "post_norm", "positions",
        "rope_theta", "tied_head", "exit_gate", "exit_entropy_beta", "remat",
        "device_init", "learning_rate", "max_len", "batch_size", "epochs",
        "seed"}
    assert (ap["mixer"], ap["ffn"], ap["norm"], ap["positions"]) == \
        ("mha", "swiglu", "rms", "rope")
    assert (ap["post_norm"], ap["exit_gate"], ap["exit_entropy_beta"],
            ap["remat"], ap["device_init"]) == (True, True, 0.05, True, True)
    # one key/value head a query head of the published width: `mha`
    assert config["num_key_value_heads"] == config["num_attention_heads"]
    assert ap["d_model"] // ap["n_heads"] == config["head_dim"] == 128
    assert config["hidden_act"] == "silu" and config["sliding_window"] is None
    assert config["n_items"] + 1 == config["vocab_size"]
    assert ap["max_len"] + 1 == config["session_len"] == 8193
    assert (ap["batch_size"], ap["epochs"], config["n_users"]) == (1, 1, 8)
    from predictionio_tpu.models import seqrec

    seqrec.SeqRecParams(**ap).check()
    seqrec.SeqRecParams(**config["tiny"]["algorithm_params"]).check()
    assert set(config["tiny"]["algorithm_params"]) == set(ap)


def test_the_programs_own_parameter_count(config):
    """ISSUE 38's arithmetic: 509.66 M parameters, 8.15 GB at 16 bytes."""
    import jax

    from predictionio_tpu.models import seqrec

    p = seqrec.SeqRecParams(**config["algorithm_params"])
    shapes = jax.eval_shape(
        lambda: seqrec.init_params(None, config["n_items"], p))
    leaves = dict(jax.tree_util.tree_leaves_with_path(shapes))
    count = lambda pick: sum(int(np.prod(v.shape)) for k, v in leaves.items()
                             if pick(jax.tree_util.keystr(k)))
    assert count(lambda k: True) == 509_661_185
    assert count(lambda k: "[0]" in k and ("wqkv" in k or "'wo'" in k)) \
        == 2048 * 6144 + 2048 * 2048 == 16_777_216
    assert count(lambda k: "[0]" in k and "w_" in k) == 3 * 2048 * 5632 \
        == 34_603_008
    assert count(lambda k: "[0]" in k and "scale" in k) == 4 * 2048
    assert count(lambda k: "[5]" in k) == 51_388_416
    assert count(lambda k: "emb" in k) == count(lambda k: "head" in k) \
        == 49_152 * 2048
    assert count(lambda k: "ln_f" in k or "exit_gate" in k) == 4_097
    assert 16 * 509_661_185 == pytest.approx(8.15e9, rel=0.001)


@pytest.mark.parametrize("seed", [5, 2**31 + 5])
def test_sessions_cover_the_catalogue(config, seed):
    columns, truth = sessions_longhist.generate(config, seed)
    sessions = truth["sessions"]
    assert sessions.shape == (8, 8_193)
    assert len(columns["entity_id"]) == sessions.size == 65_544
    assert np.array_equal(np.unique(sessions),
                          np.arange(1, config["n_items"] + 1))
    # a session opens with its 6,144-item share of the sweep
    assert len(np.unique(sessions[:, :6144])) == 8 * 6144 - 1


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
    return (seqs, targets, spec, grads, looped_step.reference_numbers(
        start.params, seqs, targets, spec, grads))


def rows_of(tiny, releases, reference, program=None, unmoved=None):
    start, trained, _ = releases
    seqs, targets, spec, grads, sound = reference
    if program is not None:
        program = looped_step.reference_numbers(
            start.params, seqs, targets, dataclasses.replace(spec, **program),
            grads if set(program) == {"learning_rate"} else None)
    rows = looped_step.compare(
        program or looped_step.program_numbers(trained.record), sound,
        looped_step.groups_unmoved(
            start.params, trained.params) if unmoved is None else unmoved,
        tiny["limits"])
    return {r[0]: r for r in rows}


def failed(rows):
    return sorted(name for name, row in rows.items() if not row[3])


def test_the_sound_train_is_correct(tiny, releases, reference):
    rows = rows_of(tiny, releases, reference)
    assert not failed(rows), rows
    assert set(rows) == set(tiny["limits"])
    parts = {name.split(".")[1] for name in rows
             if name.startswith("seqrec_grad_norm")}
    assert parts == {"attention", "ffn", "norms", "embedding", "head",
                     "exit_gate"}
    passes = {name.split(".")[1] for name in rows if "loop_loss" in name}
    assert passes == {"0", "1", "2", "3"}


def test_the_full_sizes_limits_name_the_same_rows(config):
    assert set(config["limits"]) == set(config["tiny"]["limits"])
    # every limit is a reading's: none left open
    assert all(0 <= v <= 1.0 for v in config["limits"].values())


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
    ({"precision": "int8"}, "seqrec_grad_norm_rel_err.norms"),
    # one pass left out: three for four
    ({"n_loops": 3}, "seqrec_loop_loss_rel_err.3"),
    # the earlier passes' part of the shared weights' gradient dropped
    ({"last_pass_only": True}, "seqrec_grad_norm_rel_err.attention"),
    ({"post_norm": False}, "seqrec_grad_norm_rel_err.norms"),
    ({"exit_entropy_beta": 0.0}, "seqrec_loss_rel_err"),
])
def test_a_fault_is_not_correct(tiny, releases, reference, fault, row):
    rows = rows_of(tiny, releases, reference, program=fault)
    assert not rows[row][3], rows[row]
    if "last_pass_only" in fault:
        # the forward pass is the sound one: only gradients tell
        assert rows["seqrec_loss_rel_err"][3]
        assert not rows["seqrec_grad_norm_rel_err.embedding"][3]
        assert rows["seqrec_grad_norm_rel_err.head"][3]
    else:
        assert not rows["seqrec_loss_rel_err"][3]


def test_a_wrong_optimizer_or_an_unchanged_state_is_not_correct(
        tiny, releases, reference):
    """A learning rate ten times off fails by every part's update and no
    other row; an unchanged state by its own row."""
    rows = rows_of(tiny, releases, reference, program={
        "learning_rate": 10 * tiny["algorithm_params"]["learning_rate"]})
    assert failed(rows) == sorted(
        name for name in rows if name.startswith("seqrec_update_norm"))
    assert len(failed(rows)) == 6
    start, _, _ = releases
    same = looped_step.groups_unmoved(start.params, start.params)
    assert same == len(ref.group_norms(start.params))
    assert failed(rows_of(tiny, releases, reference, unmoved=same)) == \
        ["seqrec_groups_unmoved"]


def shapes_of(config, steps=8):
    return {**config["algorithm_params"], "n_vocab": 49_152, "steps": steps,
            "tokens_per_step": 8_192}


def test_the_models_operations_by_count(config):
    """ISSUE 38's arithmetic: 6 x 51.38 M x 8,192 x 24 layer passes =
    60.6 TFLOP of layer products, 5.37e8 causal pairs x 1,792 x 24 = 23.1
    of attention, 6 x 100.66 M x 8,192 x 4 = 19.8 of head: 103.5 TFLOP a
    step; the kernels' contract, which counts the forward call `remat`
    repeats, 5.37e8 x 2,304 x 24 = 29.7."""
    shapes = shapes_of(config, steps=1)
    tokens = 8_192
    ops = seqrec_looped_model.counts(shapes, 24.0 * tokens)
    assert ops == pytest.approx(103.5e12, rel=0.002)
    pairs = 16 * 8_192 * 8_193 / 2
    assert pairs == pytest.approx(5.37e8, rel=0.001)
    layer = 2048 * 6144 + 2048 * 2048 + 3 * 2048 * 5632
    assert layer == 51_380_224
    by_hand = 6.0 * 24 * tokens * layer + 24 * pairs * 1_792 \
        + 6.0 * 4 * tokens * 2048 * 49_152
    assert ops == pytest.approx(by_hand, rel=1e-12)
    assert 6.0 * 24 * tokens * layer == pytest.approx(60.6e12, rel=0.002)
    assert 24 * pairs * 1_792 == pytest.approx(23.1e12, rel=0.002)
    assert 6.0 * 4 * tokens * 2048 * 49_152 == pytest.approx(19.8e12,
                                                             rel=0.002)
    # a step that ran the stack once: a quarter of the layers' and the
    # head's work, whatever the spec says
    once = seqrec_looped_model.counts(shapes, 6.0 * tokens)
    assert once == pytest.approx(by_hand / 4, rel=1e-12)
    step_ops, step_bytes = mha_attention_kernel.counts(
        {"shapes": shapes}, {}, 3)
    assert step_ops == 24 * pairs * (2 * 512 + 1_280)   # two forward calls
    assert step_ops == pytest.approx(29.7e12, rel=0.002)
    # q, k, v, o of 16 heads once forward (twice); q, k, v, do, dq, dk, dv
    assert step_bytes == 24 * 16 * 8_192 * 128 * 4 * (2 * 4 + 7)
    # eight steps a train
    assert mha_attention_kernel.counts(
        {"shapes": shapes_of(config)}, {}, 3)[0] == 8 * step_ops
    # not the other attention layers' count: a latent or grouped spec
    for other in ({"mixer": "mla"}, {"mixer": ["gdn", "gqa"]},
                  {"positions": "learned"}, {"steps": 0}):
        assert mha_attention_kernel.counts(
            {"shapes": {**shapes, **other}}, {}, 3) is None


def test_the_new_readers_return_nothing_from_a_program_without_them():
    """The parent commit has no such counter and no such shapes: the
    metric is left out of the line and nothing raises."""
    bench = manifest.load_benchmark()
    new = [m["name"] for m in bench["per_layer"]
           if m.get("workloads") == [CELL]]
    assert set(NEW) <= set(new)
    evidence = {"jobs": [{"wall_s": 1.0, "spans": {"als_solve": 1.0}}],
                "registry_before": {}, "registry_after": {},
                "device": {"kind": "TPU v5 lite"}, "shapes": {"rank": 64},
                "trace": None, "memory": {}}
    for name in NEW:
        assert layer_readers.read(
            evidence, manifest.load_layer_reader(name)) is None, name
    # a sequence cell's step of one pass: first passes only
    evidence["registry_after"] = {COUNTER: [[{"pass": "first"}, 300.0],
                                            [{"pass": "repeat"}, 0.0]]}
    assert layer_readers.read(evidence, manifest.load_layer_reader(
        "loop_repeat_token_pct")) == 0.0
    # a trace of another cell's kernels: not this count's shapes
    evidence["trace"] = {"ops": [["flash_attention_pallas_fwd.1", 2, 0.01]]}
    evidence["shapes"] = {"mixer": "mla", "steps": 8}
    assert layer_readers.read(evidence, manifest.load_layer_reader(
        "mha_attention_kernel_roofline")) is None


def test_the_new_readers_read_what_the_program_counts(config):
    shapes = shapes_of(config, steps=8)
    positions = 8 * 8_192.0
    after = {COUNTER: [[{"pass": "first"}, 2 * 6 * positions],
                       [{"pass": "repeat"}, 2 * 18 * positions]]}
    evidence = {"jobs": [{"spans": {"seqrec_steps": 9.0}},
                         {"spans": {"seqrec_steps": 11.0}}],
                "registry_before": {}, "registry_after": after,
                "device": {"kind": "TPU v5 lite"}, "shapes": shapes}
    read = lambda name: layer_readers.read(
        evidence, manifest.load_layer_reader(name))
    assert read("loop_repeat_token_pct") == 75.0
    want = 100 * seqrec_looped_model.counts(shapes, 24 * positions) \
        / 10.0 / 197e12
    assert read("seqrec_looped_mfu_pct") == pytest.approx(want)
    assert want == pytest.approx(100 * 8 * 103.5e12 / 10.0 / 197e12,
                                 rel=0.01)
    evidence["device"]["kind"] = "cpu"          # no peak, no share
    assert read("seqrec_looped_mfu_pct") is None


def test_the_cell_lists_what_it_feeds_and_not_what_it_cannot():
    bench = manifest.load_benchmark()
    assert not manifest.check(bench)
    mine = {m["name"] for m in manifest.metrics_of_cell(bench, CELL,
                                                        "per_layer")}
    assert {*NEW, "attention_kernel_token_pct", "attention_kernel_fwd_ms",
            "attention_kernel_bwd_ms", "seqrec_step_ms", "seqrec_steps_s",
            "seqrec_init_s", "seqrec_prepare_s", "seqrec_fetch_s",
            "seqrec_pad_pct", "step_scope_ms.attention", "step_scope_ms.ffn",
            "step_scope_ms.head_loss", "step_scope_ms.optimizer",
            "scope_named_pct.train", "hbm_peak_in_use_bytes.train",
            "hbm_peak_reserved_bytes.train", "device_idle_pct.train",
            "train_persist_s", "persist_device_fetched_pct",
            "persist_fetch_wait_s", "compiles_in_window.train",
            "xla_compiles_in_window.train", "ingest_scan_s"} <= mine
    # no experts, no other mixer, and the other attention layers' counts
    assert not mine & {"seqrec_model_flops_pct", "attention_kernel_roofline",
                       "gqa_attention_kernel_roofline",
                       "seqrec_hybrid_mfu_pct", "seqrec_conv_mfu_pct",
                       "mixer_linear_token_pct", "mixer_conv_token_pct",
                       "moe_dropped_tokens", "moe_expert_load_max_over_mean",
                       "expert_kernel_ms", "expert_kernel_roofline",
                       "expert_kernel_token_pct", "step_scope_ms.router",
                       "step_scope_ms.experts", "step_scope_ms.shared_expert",
                       "step_scope_ms.short_conv", "gdn_kernel_roofline",
                       "als_solve_s"}
    assert CELL in next(m for m in bench["end_to_end"]
                        if m["name"] == "train_wall_s")["workloads"]
    cell = manifest.find_cell(bench, CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) == \
        (1, "train-backtoback", NAME)
    assert manifest.load_traffic(cell)["warm_jobs"] == 2
    assert len(cell["why"]) <= 200
