"""The `seqrec-laguna-xs2-ep8` configuration: its file against the catalog
row and against the parameters it hands the program; its check's
controls, each failing `correct` by a named row; its counts and readers.
At the rehearsal's size on the CPU; PERF.md has the controls' readings on
the chip at the cell's size. What is asserted of `BENCHMARK.json` is what
it contains, never what it equals or how long a list is: later PRs
append."""
import dataclasses
import json
import os

import numpy as np
import pytest

from benchmarks.checks import seqrec_step
from benchmarks.checks import seqrec_window_reference as ref
from benchmarks.checks import seqrec_window_step as window_step
from benchmarks.counts import (
    gqa_attention_kernel, grouped_product, seqrec_window_model,
    window_attention_kernel,
)
from benchmarks.events import sessions_longhist
from benchmarks.lib import layer_readers, manifest

NAME = "seqrec-laguna-xs2-ep8"
CELL = "laguna-xs2-ep8.train"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("step_scope_ms.window_attention", "mixer_window_token_pct",
       "window_attention_kernel_fwd_ms", "window_attention_kernel_bwd_ms",
       "window_attention_kernel_roofline", "window_attention_block_fill_pct",
       "seqrec_window_mfu_pct")
SLOTS = "pio_train_seqrec_expert_tokens_total"
CUTS = {"num_hidden_layers": (40, 5), "num_experts": (256, 32),
        "vocab_size": (100_352, 12_544)}


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
            config["shared_expert_intermediate_size"],
            config["num_experts_per_tok"], config["sliding_window"],
            config["num_key_value_heads"]) == (2048, 128, 8192, 512, 512, 8,
                                               512, 8)
    # the held layers are the published lists' first five
    assert len(config["layer_types"]) == 40
    assert config["layer_types"][:5] == [
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention", "full_attention"]
    assert config["mlp_layer_types"][:5] == ["dense"] + ["sparse"] * 4
    assert config["num_attention_heads_per_layer"][:5] == [48, 64, 64, 64, 48]
    assert {"gating", "router", "qk_norm", "shared_expert"} \
        <= set(config["assumed"])


def test_the_program_is_handed_the_published_widths_and_the_share(config):
    from predictionio_tpu.models import seqrec

    ap = config["algorithm_params"]
    p = seqrec.SeqRecParams(**ap)
    p.check()
    kinds = {"full_attention": "gqa", "sliding_attention": "swa"}
    assert list(p.mixer_kinds()) == [
        kinds[t] for t in config["layer_types"][:p.n_layers]]
    assert [ffn for _, ffn in p.layer_kinds()] == [
        {"dense": "swiglu", "sparse": "moe"}[t]
        for t in config["mlp_layer_types"][:p.n_layers]]
    full, band = p.held_kind("gqa"), p.held_kind("swa")
    heads = config["num_attention_heads_per_layer"]
    rope = config["rope_parameters"]
    assert (p.d_model, full.heads, band.heads, full.kv_heads, band.kv_heads,
            full.head_dim, band.head_dim) == (
        config["hidden_size"], heads[0], heads[1],
        config["num_key_value_heads"], config["num_key_value_heads"],
        config["head_dim"], config["head_dim"])
    assert heads[0] == config["num_attention_heads"] == 48 and heads[1] == 64
    assert band.window == config["sliding_window"] and full.window is None
    yarn = rope["full_attention"]
    assert (full.theta, full.rotary_dim) == (
        yarn["rope_theta"], yarn["partial_rotary_factor"] * full.head_dim)
    assert dataclasses.asdict(full.scaling) == {
        "factor": yarn["factor"],
        "original_max_len": yarn["original_max_position_embeddings"],
        "beta_fast": yarn["beta_fast"], "beta_slow": yarn["beta_slow"],
        "attention_factor": yarn["attention_factor"]}
    assert yarn["attention_factor"] == pytest.approx(
        0.1 * np.log(yarn["factor"]) + 1.0)
    own = rope["sliding_attention"]
    assert (band.theta, band.rotary_dim, band.scaling) == (
        own["rope_theta"], own["partial_rotary_factor"] * band.head_dim, None)
    assert (p.dense_width(), p.n_routed_experts, p.experts_per_token,
            p.moe_width, p.n_shared_experts * p.moe_width,
            p.routed_scaling_factor, p.norm_eps, p.tied_head) == (
        config["intermediate_size"], config["published"]["num_experts"],
        config["num_experts_per_tok"], config["moe_intermediate_size"],
        config["shared_expert_intermediate_size"],
        config["moe_routed_scaling_factor"], config["rms_norm_eps"],
        config["tie_word_embeddings"])
    assert (p.attention_gate, p.qk_norm, p.router_scoring, p.norm_topk_prob,
            p.shared_expert_gate, p.bias_update_rate,
            p.balance_loss_alpha) == ("head", False, "sigmoid", True, False,
                                      0.0, 0.0)
    # the share: what is held of each published count
    assert (p.held_experts[1] - p.held_experts[0], p.n_layers,
            config["n_items"] + 1, p.tensor_ways) == (
        config["num_experts"], config["num_hidden_layers"],
        config["vocab_size"], 1)
    assert (p.max_len, p.batch_size, p.learning_rate, config["n_users"]) == (
        config["session_len"] - 1, 1, 1e-4, 8)
    assert p.max_len == 32 * band.window


def test_the_programs_own_parameter_count(config):
    import jax

    from predictionio_tpu.models import seqrec

    p = seqrec.SeqRecParams(**config["algorithm_params"])
    params = jax.eval_shape(
        lambda: seqrec.init_params(None, config["n_items"], p))
    count = lambda tree: sum(int(np.prod(x.shape))
                             for x in jax.tree.leaves(tree))
    assert count(params) == 691_624_960
    assert abs(count(params) - 691.6e6) < 0.001 * 691.6e6
    # this issue's arithmetic, a layer
    by_layer = [count(layer) for layer in params["layers"]]
    assert by_layer == pytest.approx(
        [79.79e6, 142.21e6, 142.21e6, 142.21e6, 133.79e6], rel=1e-3)
    assert count(params["layers"][1]["swa"]) == pytest.approx(37.88e6,
                                                              rel=1e-3)
    attention = {k: v for k, v in params["layers"][0].items()
                 if k in ("wq", "w_head_gate", "wk", "wv", "wo")}
    assert count(attention) == pytest.approx(29.46e6, rel=1e-3)
    assert count(params["emb"]) == count(params["head"]) == 12_544 * 2048


# -- the rehearsal: the check and its controls --------------------------------

@pytest.fixture(scope="module")
def releases(tiny):
    """(theta_0's release, the trained release, the sessions) of the
    rehearsal's train, through the program's own train."""
    from predictionio_tpu.models import seqrec

    _, truth = sessions_longhist.generate(tiny, 2**31 + 44)
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
    return (seqs, targets, spec, grads, window_step.reference_numbers(
        start.params, seqs, targets, spec, grads))


def rows_of(tiny, releases, reference, program=None, unmoved=None):
    start, trained, _ = releases
    seqs, targets, spec, grads, sound = reference
    if program is not None:
        program = window_step.reference_numbers(
            start.params, seqs, targets, dataclasses.replace(spec, **program),
            # a fault of the optimizer alone reads the sound gradients
            grads if set(program) <= {"learning_rate", "expert_not_updated"}
            else None)
    rows = window_step.compare(
        program or window_step.program_numbers(trained.record), sound,
        trained.record, window_step.groups_unmoved(
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
    assert parts == {"attention", "window_attention", "ffn", "router",
                     "experts", "shared_expert", "norms", "embedding", "head"}


def test_the_full_sizes_limits_name_the_same_rows(config):
    assert set(config["limits"]) == set(config["tiny"]["limits"])
    # every limit is a reading's: none left open
    assert all(0 <= v <= 1.0 for v in config["limits"].values())


#: each control in the program's place and a row it has to fail by
FAULT_ROWS = [
    # the next precision below the stated one, in the program's place
    ({"precision": "int8"}, "seqrec_grad_norm_rel_err.window_attention"),
    ({"fault": "window_ignored"}, "seqrec_grad_norm_rel_err.window_attention"),
    ({"fault": "window_plus_one"},
     "seqrec_grad_norm_rel_err.window_attention"),
    ({"fault": "window_minus_one"},
     "seqrec_grad_norm_rel_err.window_attention"),
    ({"fault": "tables_swapped"}, "seqrec_grad_norm_rel_err.window_attention"),
    ({"fault": "yarn_ramp_left_out"}, "seqrec_grad_norm_rel_err.attention"),
    ({"fault": "attention_factor_left_out"},
     "seqrec_grad_norm_rel_err.attention"),
    ({"fault": "gate_left_out"}, "seqrec_grad_norm_rel_err.attention"),
    ({"fault": "full_heads_everywhere"},
     "seqrec_grad_norm_rel_err.window_attention"),
    ({"fault": "scaling_factor_left_out"},
     "seqrec_grad_norm_rel_err.experts"),
]


@pytest.mark.parametrize("fault,row", FAULT_ROWS)
def test_a_fault_is_not_correct(tiny, releases, reference, fault, row):
    rows = rows_of(tiny, releases, reference, program=fault)
    assert not rows[row][3], rows[row]
    assert not rows["seqrec_loss_rel_err"][3]


def test_every_fault_of_the_reference_is_planted_above():
    assert set(ref.FAULTS) <= {fault.get("fault") for fault, _ in FAULT_ROWS}


def test_a_wrong_optimizer_or_an_unchanged_state_is_not_correct(
        tiny, releases, reference):
    rows = rows_of(tiny, releases, reference, program={
        "learning_rate": 10 * tiny["algorithm_params"]["learning_rate"]})
    assert failed(rows) == sorted(
        name for name in rows if name.startswith("seqrec_update_norm"))
    start, _, _ = releases
    same = window_step.groups_unmoved(start.params, start.params)
    assert same == len(ref.group_norms(start.params))
    assert failed(rows_of(tiny, releases, reference, unmoved=same)) == \
        ["seqrec_groups_unmoved"]


def test_an_expert_left_where_it_is_is_not_correct(tiny, releases,
                                                   reference):
    """Adamw's first step skipping one held expert (the first expert
    layer's, the one with the median of its held experts' tokens) reads
    that expert's share of the layer's held tokens, by the experts'
    update alone."""
    tokens = reference[4]["held_load"][0]
    expert = int(np.argsort(tokens, kind="stable")[(len(tokens) - 1) // 2])
    rows = rows_of(tiny, releases, reference,
                   program={"expert_not_updated": (0, expert)})
    assert failed(rows) == ["seqrec_update_norm_rel_err.experts"]
    assert rows["seqrec_update_norm_rel_err.experts"][1] == pytest.approx(
        tokens[expert] / tokens.sum(), rel=1e-3)


# -- the counts and the readers ------------------------------------------------

class _Run:
    """What `shapes` reads of a run: the configuration and a release."""

    def __init__(self, config, steps=8):
        ap = config["algorithm_params"]
        self.config = config
        self.instance = None
        record = {"loss": [0.0] * steps, "rows": [[0]] * steps}
        hyper = type("Hyper", (), {"max_len": ap["max_len"]})
        self._model = type("Model", (), {
            "record": record, "hyper": hyper,
            "params": {"emb": np.zeros((config["n_items"] + 1, 1))}})

    def load_model(self, _):
        return self._model


#: a session and head's pairs inside the band at 16,384 positions, W 512
BAND = 512 * 513 / 2 + (16384 - 512) * 512


def test_the_models_operations_by_hand(config):
    """6 per matrix parameter a token passes, the routed experts by their
    slots, the head once, the full layers' causal pairs at 48 heads and
    the sliding layers' band pairs at 64."""
    shapes = window_step.shapes(_Run(config))
    tokens, slots = 8 * 16384, 4 * 8 * 16384 * 8 * 32 / 256.0
    full = 2048 * 6144 + 2048 * 48 + 2 * 2048 * 1024 + 6144 * 2048
    sliding = 2048 * 8192 + 2048 * 64 + 2 * 2048 * 1024 + 8192 * 2048
    assert (full, sliding) == (29_458_432, 37_879_808)
    expert = 3 * 2048 * 512
    per_token = 2 * full + 3 * sliding + 3 * 2048 * 8192 \
        + 4 * (2048 * 256 + expert) + 2048 * 12_544
    pairs = 2 * 48 * 8 * 16384 ** 2 / 2 + 3 * 64 * 8 * BAND
    want = 6.0 * (tokens * per_token + slots * expert) + 3 * pairs * 4 * 128
    assert seqrec_window_model.counts(shapes, slots) == pytest.approx(want)
    # 49.3 TFLOP a step: 25.9 of dense products, 1.2 of the routed
    # experts', 19.8 of the full layers' pairs, 2.4 of the band's
    assert want / 8 == pytest.approx(4.93e13, rel=0.01)
    assert 6.0 * 16384 * per_token == pytest.approx(2.59e13, rel=0.01)
    assert 3 * 2 * 48 * 16384 ** 2 / 2 * 512 == pytest.approx(1.98e13,
                                                               rel=0.01)
    assert BAND == 8_257_792


def test_the_bands_kernel_contract_by_hand(config):
    shapes = window_step.shapes(_Run(config))
    slots = 4 * 8 * 16384 * 8 * 32 / 256.0
    evidence = {"shapes": shapes, "jobs": [{}, {}], "registry_before": {},
                "registry_after": {SLOTS: [[{"layer": "0"}, 2 * slots]]}}
    ops, nbytes = window_attention_kernel.counts(evidence, {}, 0)
    calls = 8 * 3               # a step and sliding layer
    assert ops == calls * 64 * BAND * (2 * 4 * 128 + 10 * 128)
    q_rows, kv_rows = 64 * 16384, 8 * 16384
    assert nbytes == calls * 128 * 4.0 * (
        2 * (2 * q_rows + 2 * kv_rows) + 3 * q_rows + 4 * kv_rows)
    # 1.22 TFLOP a layer and step: 6.2% of the whole triangle's
    assert ops / calls == pytest.approx(1.218e12, rel=1e-3)
    assert BAND / (16384 * 16385 / 2) == pytest.approx(0.0615, rel=1e-2)
    # the whole-causal kernels' count reads the full layers alone, at 48
    ops, _ = gqa_attention_kernel.counts(evidence, {}, 0)
    assert ops == 8 * 2 * (16384 * 16385 / 2 * 48) * (2 * 4 * 128 + 10 * 128)
    # the experts' kernels: twelve products a slot at 2048 x 512
    ops, nbytes = grouped_product.counts(evidence, {}, 0)
    assert ops == 12 * 2.0 * slots * 2048 * 512
    assert nbytes == 12 * 4.0 * (slots * (2048 + 512)
                                 + 4 * 8 * 32 * 2048 * 512)
    # a spec without a sliding layer is not this count's
    evidence["shapes"] = {**shapes, "mixer": "gqa"}
    assert window_attention_kernel.counts(evidence, {}, 0) is None


def test_the_new_readers_return_nothing_from_a_program_without_them():
    """The parent commit has no such scope, counter label, counter pair
    or kernel name: the metric is left out of the line and nothing
    raises."""
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
    # another sequence cell's step: mixers of other kinds only
    evidence["registry_after"] = {
        "pio_train_seqrec_mixer_tokens_total": [[{"mixer": "gdn"}, 300.0],
                                                [{"mixer": "gqa"}, 100.0]]}
    assert layer_readers.read(evidence, manifest.load_layer_reader(
        "mixer_window_token_pct")) == 0.0
    # a trace of another cell's kernels: the whole-causal names are not
    # the band's, and its shapes are not this count's
    evidence["trace"] = {"ops": [["flash_attention_pallas_fwd.1", 2, 0.01],
                                 ["flash_attention_pallas_bwd.1", 1, 0.02]]}
    evidence["shapes"] = {"mixer": ["gdn", "gqa"], "n_layers": 4, "steps": 8}
    for name in ("window_attention_kernel_fwd_ms",
                 "window_attention_kernel_bwd_ms",
                 "window_attention_kernel_roofline"):
        assert layer_readers.read(
            evidence, manifest.load_layer_reader(name)) is None, name


def test_the_new_readers_read_what_the_program_counts(config):
    shapes = window_step.shapes(_Run(config))
    positions, slots = 8 * 16384.0, 4 * 8 * 16384 * 8 * 32 / 256.0
    after = {"pio_train_seqrec_mixer_tokens_total": [
        [{"mixer": "swa"}, 2 * 3 * positions],
        [{"mixer": "gqa"}, 2 * 2 * positions]],
        "pio_train_seqrec_window_band_pairs_total": [[{}, 2 * 8 * 3 * BAND]],
        "pio_train_seqrec_window_block_pairs_total": [
            [{}, 2 * 8 * 3 * 63 * 512.0 * 512]],
        SLOTS: [[{"layer": "0"}, 2 * slots]]}
    evidence = {"jobs": [{"spans": {"seqrec_steps": 7.0}},
                         {"spans": {"seqrec_steps": 9.0}}],
                "registry_before": {}, "registry_after": after,
                "device": {"kind": "TPU v5 lite"}, "shapes": shapes,
                "trace": {"ops": [
                    ["window_attention_pallas_fwd.3_tpu_custom_call", 48,
                     0.24],
                    ["window_attention_pallas_bwd.1_tpu_custom_call", 24,
                     0.36],
                    ["flash_attention_pallas_fwd.3_tpu_custom_call", 32,
                     1.0]]}}
    read = lambda name: layer_readers.read(
        evidence, manifest.load_layer_reader(name))
    assert read("mixer_window_token_pct") == pytest.approx(60.0)
    assert read("window_attention_block_fill_pct") == pytest.approx(
        100 * BAND / (63 * 512 * 512))
    assert read("window_attention_kernel_fwd_ms") == pytest.approx(5.0)
    assert read("window_attention_kernel_bwd_ms") == pytest.approx(15.0)
    # 24 calls' contract over the band's kernels' 0.6 s, not the full ones'
    ops, _ = window_attention_kernel.counts(evidence, {}, 72)
    assert read("window_attention_kernel_roofline") == pytest.approx(
        100 * ops / 197e12 / 0.6, rel=1e-6)
    want = 100 * seqrec_window_model.counts(shapes, slots) / 8.0 / 197e12
    assert read("seqrec_window_mfu_pct") == pytest.approx(want)
    assert 0 < want < 100
    evidence["device"]["kind"] = "cpu"          # no peak, no share
    assert read("seqrec_window_mfu_pct") is None


def test_the_cell_lists_what_it_feeds_and_not_what_it_cannot():
    bench = manifest.load_benchmark()
    assert not manifest.check(bench)
    mine = {m["name"] for m in manifest.metrics_of_cell(bench, CELL,
                                                        "per_layer")}
    assert {*NEW, "attention_kernel_token_pct", "attention_kernel_fwd_ms",
            "attention_kernel_bwd_ms", "gqa_attention_kernel_roofline",
            "attention_rows_layout_token_pct", "expert_kernel_ms",
            "expert_kernel_token_pct", "expert_kernel_roofline",
            "moe_dropped_tokens", "moe_expert_load_max_over_mean",
            "seqrec_step_ms", "seqrec_steps_s", "seqrec_init_s",
            "seqrec_prepare_s", "seqrec_fetch_s", "seqrec_pad_pct",
            "step_scope_ms.attention", "step_scope_ms.router",
            "step_scope_ms.experts", "step_scope_ms.shared_expert",
            "step_scope_ms.ffn", "step_scope_ms.head_loss",
            "step_scope_ms.optimizer", "scope_named_pct.train",
            "hbm_peak_in_use_bytes.train", "hbm_peak_reserved_bytes.train",
            "device_idle_pct.train", "train_persist_s",
            "persist_device_fetched_pct", "persist_fetch_wait_s",
            "compiles_in_window.train", "xla_compiles_in_window.train",
            "ingest_scan_s"} <= mine
    # no other mixer, and the counts that assume latent attention, one
    # head count a model, latent experts or a looped stack
    assert not mine & {"seqrec_model_flops_pct", "attention_kernel_roofline",
                       "mha_attention_kernel_roofline",
                       "latent_expert_kernel_roofline",
                       "seqrec_hybrid_mfu_pct", "seqrec_conv_mfu_pct",
                       "seqrec_looped_mfu_pct", "seqrec_ssm_mfu_pct",
                       "mixer_linear_token_pct", "mixer_conv_token_pct",
                       "mixer_ssm_token_pct", "loop_repeat_token_pct",
                       "step_scope_ms.short_conv",
                       "step_scope_ms.linear_attention",
                       "step_scope_ms.state_space", "step_scope_ms.mtp",
                       "gdn_kernel_roofline", "als_solve_s"}
    assert CELL in next(m for m in bench["end_to_end"]
                        if m["name"] == "train_wall_s")["workloads"]
    cell = manifest.find_cell(bench, CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) == \
        (1, "train-backtoback", NAME)
    assert manifest.load_traffic(cell)["warm_jobs"] == 2
    assert len(cell["why"]) <= 200
    assert not any(c["chips"] == 4 for c in bench["workloads"])
