"""The `seqrec-mellum2-12b-a2.5b-ep4` configuration: its file against the
catalog row and against the parameters it hands the program; its event
generator; its check's batch against the program's own packing; the
check's controls, each failing `correct` by a named row; its counts and
readers. At the rehearsal's size on the CPU; PERF.md has the controls'
readings on the chip at the cell's size. What is asserted of
`BENCHMARK.json` is what it contains, never what it equals or how long a
list is: later PRs append."""
import dataclasses
import json
import os

import numpy as np
import pytest

from benchmarks.checks import seqrec_packed_reference as ref
from benchmarks.checks import seqrec_packed_step as packed_step
from benchmarks.counts import (
    packed_attention_kernel, packed_window_attention_kernel,
    seqrec_packed_model,
)
from benchmarks.events import sessions_packed
from benchmarks.lib import layer_readers, manifest

NAME = "seqrec-mellum2-12b-a2.5b-ep4"
CELL = "mellum2-a2.5b-ep4.train"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("seqrec_sessions_per_row", "seqrec_pack_s",
       "packed_attention_score_fill_pct",
       "packed_window_attention_score_fill_pct",
       "packed_attention_kernel_roofline",
       "packed_window_attention_kernel_roofline", "seqrec_packed_mfu_pct")
#: what counts a whole triangle or band a row: not this cell's
NOT_JOINED = ("gqa_attention_kernel_roofline",
              "window_attention_kernel_roofline",
              "window_attention_block_fill_pct", "seqrec_window_mfu_pct")
CUTS = {"num_hidden_layers": (28, 4), "num_experts": (64, 16),
        "vocab_size": (98_304, 24_576)}


@pytest.fixture(scope="module")
def config():
    return manifest.load_config(manifest.load_benchmark(), NAME)


@pytest.fixture(scope="module")
def tiny(config):
    return {**config, **config["tiny"]}


def test_the_file_holds_the_catalog_row_but_for_the_three_cuts(config):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == config["source"])
    entry = next(c for c in manifest.load_benchmark()["configs"]
                 if c["name"] == NAME)
    assert entry["reduced"] == list(config["reduced"]) == list(CUTS)
    assert entry["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CUTS:
            assert (config["published"][key], config[key]) == CUTS[key] \
                and value == CUTS[key][0]
        else:
            assert config[key] == value, key
    assert set(CUTS) <= set(config["held"])
    # no width among the cuts: every one as published
    assert (config["hidden_size"], config["head_dim"],
            config["intermediate_size"], config["moe_intermediate_size"],
            config["num_experts_per_tok"], config["sliding_window"],
            config["num_attention_heads"],
            config["num_key_value_heads"]) == (2304, 128, 7168, 896, 8, 1024,
                                               32, 4)
    assert len(config["layer_types"]) == 28
    assert config["layer_types"][:4] == ["sliding_attention"] * 3 \
        + ["full_attention"]
    assert set(config["mlp_layer_types"]) == {"sparse"}
    assert {"router", "mtp_head", "qk_norm", "session_lengths"} \
        <= set(config["assumed"])


def test_the_program_is_handed_the_published_widths_and_the_share(config):
    from predictionio_tpu.models import seqrec

    ap = config["algorithm_params"]
    p = seqrec.SeqRecParams(**ap)
    p.check()
    kinds = {"full_attention": "gqa", "sliding_attention": "swa"}
    assert list(p.mixer_kinds()) == [
        kinds[t] for t in config["layer_types"][:p.n_layers]]
    assert {ffn for _, ffn in p.layer_kinds()} == {"moe"}
    full, band = p.held_kind("gqa"), p.held_kind("swa")
    rope = config["rope_parameters"]
    for kind in (full, band):
        assert (kind.heads, kind.kv_heads, kind.head_dim, kind.rotary_dim,
                kind.gate, kind.qk_norm, kind.theta) == (
            config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], config["head_dim"], False, False,
            rope["full_attention"]["rope_theta"])
    assert rope["sliding_attention"]["rope_theta"] == band.theta
    assert band.window == config["sliding_window"] and full.window is None
    assert band.scaling is None
    yarn = rope["full_attention"]
    assert dataclasses.asdict(full.scaling) == {
        "factor": yarn["factor"],
        "original_max_len": yarn["original_max_position_embeddings"],
        "beta_fast": yarn["beta_fast"], "beta_slow": yarn["beta_slow"],
        "attention_factor": yarn["attention_factor"]}
    assert yarn["attention_factor"] == pytest.approx(
        0.1 * np.log(yarn["factor"]) + 1.0)
    assert (p.d_model, p.n_routed_experts, p.experts_per_token, p.moe_width,
            p.n_shared_experts, p.first_dense_layers, p.norm_eps,
            p.tied_head, p.router_scoring, p.norm_topk_prob,
            p.routed_scaling_factor, p.bias_update_rate,
            p.balance_loss_alpha) == (
        config["hidden_size"], config["published"]["num_experts"],
        config["num_experts_per_tok"], config["moe_intermediate_size"], 0, 0,
        config["rms_norm_eps"], config["tie_word_embeddings"], "softmax",
        config["norm_topk_prob"], 1.0, 0.0, 0.0)
    # the share: what is held of each published count
    assert (p.held_experts[1] - p.held_experts[0], p.n_layers,
            config["n_items"] + 1, p.tensor_ways) == (
        config["num_experts"], config["num_hidden_layers"],
        config["vocab_size"], 1)
    assert p.packing and (p.max_len, p.batch_size, p.learning_rate) == (
        yarn["original_max_position_embeddings"], 2, 1e-4)


def test_the_programs_own_parameter_count(config):
    import jax

    from predictionio_tpu.models import seqrec

    p = seqrec.SeqRecParams(**config["algorithm_params"])
    params = jax.eval_shape(
        lambda: seqrec.init_params(None, config["n_items"], p))
    count = lambda tree: sum(int(np.prod(x.shape))
                             for x in jax.tree.leaves(tree))
    biases = sum(count(layer["router_bias"]) for layer in params["layers"])
    assert (count(params) - biases, biases) == (595_153_152, 4 * 64)
    assert "595,153,152" in config["parameters"]
    layer = params["layers"][0]
    assert count(layer) - 64 == 120_476_160
    assert count(layer["swa"]) == 21_233_664
    assert count(layer["experts"]) == 16 * 6_193_152


def test_the_generator_makes_the_cells_sessions(config):
    """The same multiset of lengths on every seed, dealt anew; every item
    appears; a large seed is a seed."""
    lengths = sessions_packed.lengths(config)
    assert (len(lengths), int(lengths.sum()), int(lengths.min()),
            int(lengths.max())) == (672, 126_648, 2, 4096)
    seen = []
    for seed in (3, 2 ** 31 + 11):
        columns, truth = sessions_packed.generate(config, seed)
        sessions = truth["sessions"]
        assert sorted(map(len, sessions)) == lengths.tolist()
        assert len(np.unique(np.concatenate(sessions))) == config["n_items"]
        assert len(columns["entity_id"]) == 126_648
        # a row's user, item and time are its session's
        u = int(columns["entity_id"][0]) - 1
        at = (int(columns["event_time_ms"][0]) - sessions_packed.START_MS
              - 86_400_000 * u) // 1000
        assert sessions[u][at] == columns["target_entity_id"][0]
        seen.append([len(s) for s in sessions])
    assert seen[0] != seen[1]
    again = sessions_packed.generate(config, 3)[1]["sessions"]
    assert all(np.array_equal(a, b) for a, b in zip(
        again, sessions_packed.generate(config, 3)[1]["sessions"]))


def test_the_checks_rows_are_the_programs(tiny):
    """The check's own packing of the generated sessions (its few lines,
    the data source's order, codes by rank as text) gives the rows
    `pack_sessions` gives the program; its first batch is those rows'
    sessions, each alone."""
    from predictionio_tpu.models import seqrec

    ap = tiny["algorithm_params"]
    _, truth = sessions_packed.generate(tiny, 5)
    coded, rows = packed_step.packed_rows(tiny, truth["sessions"])
    packed = seqrec.pack_sessions(
        [np.concatenate([i, t[-1:]]).tolist() for i, t in coded],
        ap["max_len"])
    assert [list(r) for r in packed.sessions] == rows
    alone, n_positions = packed_step.first_batch(tiny, truth["sessions"])
    first = packed_step.epoch0_rows(ap, len(rows))[:ap["batch_size"]]
    assert n_positions == ap["batch_size"] * ap["max_len"]
    got = np.concatenate([inputs for inputs, _ in alone])
    want = np.concatenate([packed.inputs[r][packed.ids[r] > 0]
                           for r in first])
    assert np.array_equal(got, want)


@pytest.fixture(scope="module")
def step_and_reference(tiny):
    """The tiny configuration's first step through the program and
    through the reference."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.models import seqrec

    ap = tiny["algorithm_params"]
    p = seqrec.SeqRecParams(**ap)
    _, truth = sessions_packed.generate(tiny, 7)
    coded, rows = packed_step.packed_rows(tiny, truth["sessions"])
    packed = seqrec.pack_sessions(
        [np.concatenate([i, t[-1:]]).tolist() for i, t in coded], p.max_len)
    first = packed_step.epoch0_rows(ap, len(rows))[:p.batch_size]
    optimizer = seqrec.make_optimizer(p)
    params = seqrec.init_params(None, tiny["n_items"], p)
    theta0 = jax.tree.map(np.asarray, params)
    with jax.default_matmul_precision("highest"):
        stats = jax.device_get(seqrec.make_train_step(None, p, optimizer)(
            params, optimizer.init(params), *(jnp.asarray(t[first]) for t in (
                packed.inputs, packed.targets, packed.ids,
                packed.positions)))[2])
    program = {"loss": float(stats["loss"]),
               **{key: {k: float(v) for k, v in stats[key].items()}
                  for key in ("grad_norm", "update_norm")},
               "expert_update_norm": np.asarray(stats["expert_update_norm"]),
               "load": np.asarray(stats["load"])}
    record = {"dropped": np.asarray(stats["dropped"]),
              "loss": [program["loss"], 0.9 * program["loss"]]}
    alone, n_positions = packed_step.first_batch(tiny, truth["sessions"])
    spec = ref.Spec.of(ap, recompute=True)
    grads = ref.loss_and_grads(theta0, alone, spec, n_positions)
    reference = packed_step.reference_numbers(theta0, alone, n_positions,
                                              spec, grads)
    return theta0, alone, n_positions, spec, program, reference, record


def failed(rows):
    return {r[0] for r in rows if not r[3]}


def test_the_sound_step_is_correct_at_the_tiny_limits(tiny,
                                                      step_and_reference):
    *_, program, reference, record = step_and_reference
    rows = packed_step.compare(program, reference, record, 0, tiny["limits"])
    assert not failed(rows), failed(rows)
    assert {r[0] for r in rows} == set(tiny["limits"])


CONTROLS = {
    "int8": (dict(precision="int8"), "seqrec_grad_norm_rel_err.head"),
    "window_512": (dict(fault="window_512"),
                   "seqrec_grad_norm_rel_err.window_attention"),
    "yarn_factor_64": (dict(fault="yarn_factor_64"),
                       "seqrec_grad_norm_rel_err.attention"),
    "yarn_on_sliding": (dict(fault="yarn_on_sliding"),
                        "seqrec_grad_norm_rel_err.window_attention"),
    "sigmoid_scores": (dict(fault="sigmoid_scores"),
                       "seqrec_grad_norm_rel_err.router"),
    "gates_not_normalised": (dict(fault="gates_not_normalised"),
                             "seqrec_grad_norm_rel_err.experts"),
}


def test_every_fault_of_the_reference_has_a_control():
    assert set(ref.FAULTS) <= set(CONTROLS)


@pytest.mark.parametrize("name", sorted(CONTROLS))
def test_a_control_fails_correct_by_its_row(name, tiny, step_and_reference):
    theta0, alone, n_positions, spec, _, reference, record = \
        step_and_reference
    over, row = CONTROLS[name]
    control = packed_step.reference_numbers(
        theta0, alone, n_positions, dataclasses.replace(spec, **over))
    rows = packed_step.compare(control, reference, record, 0, tiny["limits"])
    assert row in failed(rows), (name, failed(rows))


def test_the_optimizers_controls_fail_by_the_update(tiny,
                                                    step_and_reference):
    theta0, alone, n_positions, spec, _, reference, record = \
        step_and_reference
    grads = ref.loss_and_grads(theta0, alone, spec, n_positions)
    for over, rows_ in (
            (dict(learning_rate=10 * spec.learning_rate),
             {f"seqrec_update_norm_rel_err.{part}" for part in (
                 "attention", "embedding", "experts", "head", "norms",
                 "router", "window_attention")}),
            (dict(expert_not_updated=(0, 1)),
             {"seqrec_update_norm_rel_err.experts"})):
        control = packed_step.reference_numbers(
            theta0, alone, n_positions, dataclasses.replace(spec, **over),
            grads)
        assert failed(packed_step.compare(control, reference, record, 0,
                                          tiny["limits"])) == rows_


SHAPES = {"mixer": ["swa", "swa", "swa", "gqa"], "n_layers": 4, "steps": 8,
          "n_heads": 32, "n_kv_heads": 4, "head_dim": 128, "remat": True,
          "swa": {"heads": 32, "window": 1024}, "d_model": 2304,
          "moe_width": 896, "n_routed_experts": 64, "n_vocab": 24576,
          "tokens_per_step": 16384}


def test_the_counts_are_the_pairs_inside_sessions():
    """Two sessions of 1,500 and 10 positions: a full layer's n (n + 1)
    / 2 pairs each, a sliding layer's band inside each; two forward calls
    and one backward under remat; nothing for a row's padding."""
    shapes = {**SHAPES, "session_positions": [1500, 10]}
    pairs = 1500 * 1501 // 2 + 55
    band = 1024 * 1025 // 2 + 476 * 1024 + 55
    assert packed_attention_kernel.session_pairs([1500, 10]) == pairs
    assert packed_attention_kernel.session_pairs([1500, 10], 1024) == band
    per_pair = 2 * 2 * 2 * 128 + 2 * 5 * 128
    positions = 1510
    nbytes = (2 * (2 * 32 + 2 * 4) + 3 * 32 + 4 * 4) * positions * 128 * 4.0
    assert packed_attention_kernel.counts({"shapes": shapes}, {}, 3) == (
        32 * pairs * per_pair, nbytes)
    assert packed_window_attention_kernel.counts(
        {"shapes": shapes}, {}, 9) == (3 * 32 * band * per_pair, 3 * nbytes)
    # a program whose shapes carry no session lengths: nothing to read
    assert packed_attention_kernel.counts({"shapes": SHAPES}, {}, 3) is None
    assert packed_window_attention_kernel.counts(
        {"shapes": {**SHAPES, "swa": None}}, {}, 3) is None
    attention = 2304 * 4096 + 2 * 2304 * 512 + 4096 * 2304
    per_token = 4 * attention + 4 * 2304 * 64 + 2304 * 24576
    assert seqrec_packed_model.counts(shapes, 1000.0) == 6.0 * (
        positions * per_token + 1000.0 * 3 * 2304 * 896) \
        + 3 * 32 * (pairs + 3 * band) * 2 * 2 * 128


def test_the_cell_and_its_metrics_are_in_the_manifest():
    bench = manifest.load_benchmark()
    assert manifest.check(bench) == []
    cell = manifest.find_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "train-backtoback", 1)
    assert manifest.load_traffic(cell)["warm_jobs"] == 2
    ends = {m["name"] for m in manifest.metrics_of_cell(bench, CELL,
                                                        "end_to_end")}
    assert ends == {"train_wall_s", "setup_s"}
    layers = {m["name"]: m for m in manifest.metrics_of_cell(bench, CELL,
                                                             "per_layer")}
    assert set(NEW) <= set(layers) and not set(NOT_JOINED) & set(layers)
    for name in NEW:
        assert CELL in layers[name]["workloads"]
        assert layers[name]["moves"] == "train_wall_s"
    for name in ("packed_attention_kernel_roofline",
                 "packed_window_attention_kernel_roofline",
                 "seqrec_packed_mfu_pct"):
        assert layers[name]["unit"] == "%"
    assert {"seqrec_pad_pct", "moe_dropped_tokens", "scope_named_pct.train",
            "compiles_in_window.train", "step_scope_ms.window_attention",
            "step_scope_ms.attention", "expert_kernel_roofline",
            "attention_rows_layout_token_pct"} <= set(layers)


def test_the_new_readers_read_what_a_packed_train_counts():
    """Evidence as a packed train leaves it; a program without the
    counters (the parent) gives nothing and does not raise."""
    def reader(name):
        return manifest.load_layer_reader(name)

    after = {"pio_train_seqrec_packed_sessions_total": [[{}, 672.0]],
             "pio_train_seqrec_rows_total": [[{}, 16.0]],
             "pio_train_seqrec_packed_attention_pairs_total": [[{}, 30.0]],
             "pio_train_seqrec_packed_attention_block_pairs_total":
                 [[{}, 120.0]],
             "pio_train_seqrec_packed_window_pairs_total": [[{}, 10.0]],
             "pio_train_seqrec_packed_window_block_pairs_total":
                 [[{}, 80.0]]}
    evidence = {"registry_before": {}, "registry_after": after,
                "jobs": [{"spans": {"seqrec_pack": 0.25}},
                         {"spans": {"seqrec_pack": 0.75}}]}
    read = lambda name, ev: layer_readers.read(ev, reader(name))
    assert read("seqrec_sessions_per_row", evidence) == 42.0
    assert read("seqrec_pack_s", evidence) == 0.5
    assert read("packed_attention_score_fill_pct", evidence) == 25.0
    assert read("packed_window_attention_score_fill_pct", evidence) == 12.5
    bare = {"registry_before": {}, "registry_after": {}, "jobs": [
        {"spans": {}}], "trace": None, "shapes": {}, "device": {"kind": "x"}}
    for name in NEW:
        assert read(name, bare) is None
