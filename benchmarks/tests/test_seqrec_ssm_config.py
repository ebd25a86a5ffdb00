"""The `seqrec-nemotron3-super-120b-a12b-tp8ep64` configuration: its file
against the catalog row and against the parameters it hands the program;
its check's controls, each failing `correct` by a named row; its counts
and readers. At the rehearsal's size on the CPU; PERF.md has the
controls' readings on the chip at the cell's size. What is asserted of
`BENCHMARK.json` is what it contains, never what it equals or how long a
list is: later PRs append."""
import dataclasses
import json
import os

import numpy as np
import pytest

from benchmarks.checks import seqrec_ssm_reference as ref
from benchmarks.checks import seqrec_ssm_step as ssm_step
from benchmarks.checks import seqrec_step
from benchmarks.counts import (
    gqa_attention_kernel, grouped_product, latent_grouped_product,
    seqrec_ssm_model,
)
from benchmarks.events import sessions_longhist
from benchmarks.lib import layer_readers, manifest

NAME = "seqrec-nemotron3-super-120b-a12b-tp8ep64"
CELL = "nemotron3-super-tp8ep64.train"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("step_scope_ms.state_space", "step_scope_ms.latent_projection",
       "step_scope_ms.mtp", "mixer_ssm_token_pct", "seqrec_ssm_mfu_pct",
       "latent_expert_kernel_roofline")
SLOTS = "pio_train_seqrec_expert_tokens_total"
CUTS = {"num_hidden_layers": (88, 11), "n_routed_experts": (512, 8),
        "num_attention_heads": (32, 4), "num_key_value_heads": (2, 1),
        "mamba_num_heads": (128, 16), "n_groups": (8, 1),
        "vocab_size": (131_072, 16_384)}


@pytest.fixture(scope="module")
def config():
    return manifest.load_config(manifest.load_benchmark(), NAME)


@pytest.fixture(scope="module")
def tiny(config):
    return {**config, **config["tiny"]}


def test_the_file_holds_the_catalog_row_but_for_the_seven_cuts(config):
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
            config["mamba_head_dim"], config["ssm_state_size"],
            config["moe_latent_size"], config["moe_intermediate_size"],
            config["moe_shared_expert_intermediate_size"],
            config["num_experts_per_tok"], config["conv_kernel"],
            config["chunk_size"]) == (4096, 128, 64, 128, 1024, 2688, 5376,
                                      22, 4, 128)
    # the held layers are one whole period of the published pattern
    pattern = config["hybrid_override_pattern"]
    assert len(pattern) == 88 and pattern[25:36] == "*EMEMEMEMEM"
    assert config["mtp_hybrid_override_pattern"] == "*E"


def test_the_program_is_handed_the_published_widths_and_the_share(config):
    from predictionio_tpu.models import seqrec

    ap = config["algorithm_params"]
    p = seqrec.SeqRecParams(**ap)
    p.check()
    kinds = {"*": "gqa", "E": "moe", "M": "ssm"}
    assert list(p.sublayers) == [
        kinds[c] for c in config["hybrid_override_pattern"][25:36]]
    assert list(p.mtp_layers) == [
        kinds[c] for c in config["mtp_hybrid_override_pattern"]]
    published = config["published"]
    assert (p.d_model, p.n_heads, p.n_kv_heads, p.head_dim) == (
        config["hidden_size"], published["num_attention_heads"],
        published["num_key_value_heads"], config["head_dim"])
    assert p.ssm == {
        "heads": published["mamba_num_heads"],
        "head_dim": config["mamba_head_dim"],
        "groups": published["n_groups"], "state": config["ssm_state_size"],
        "conv_kernel": config["conv_kernel"], "chunk": config["chunk_size"]}
    assert (p.n_routed_experts, p.experts_per_token, p.moe_width,
            p.moe_latent_size, p.n_shared_experts * p.moe_width,
            p.routed_scaling_factor, p.norm_topk_prob, p.norm_eps) == (
        published["n_routed_experts"], config["num_experts_per_tok"],
        config["moe_intermediate_size"], config["moe_latent_size"],
        config["moe_shared_expert_intermediate_size"],
        config["routed_scaling_factor"], config["norm_topk_prob"],
        config["norm_eps"])
    assert (p.expert_act, p.positions, p.qk_norm, p.attention_gate,
            p.tied_head) == ("relu2", "none", False, False,
                             config["tie_word_embeddings"])
    # the share: what is held of each published count
    ways = p.tensor_ways
    held = p.state_space()
    assert ways == 8
    assert (p.held(p.n_heads), p.held(p.n_kv_heads), held.heads, held.groups,
            p.held(p.n_shared_experts * p.moe_width),
            p.held_experts[1] - p.held_experts[0], p.n_layers,
            config["n_items"] + 1) == (
        config["num_attention_heads"], config["num_key_value_heads"],
        config["mamba_num_heads"], config["n_groups"], 5376 // ways,
        config["n_routed_experts"], config["num_hidden_layers"],
        config["vocab_size"])
    assert held.heads * held.head_dim // held.groups == 1024
    assert (p.max_len, p.batch_size, p.learning_rate, p.mtp_loss_weight) == (
        config["session_len"] - 1, 1, 1e-4, 0.1)


def test_the_programs_own_parameter_count(config):
    import jax

    from predictionio_tpu.models import seqrec

    p = seqrec.SeqRecParams(**config["algorithm_params"])
    params = jax.eval_shape(
        lambda: seqrec.init_params(None, config["n_items"], p))
    count = lambda tree: sum(int(np.prod(x.shape))
                             for x in jax.tree.leaves(tree))
    assert count(params) == 607_038_960
    assert abs(count(params) - 607e6) < 0.01 * 607e6
    by_kind = {kind: count(params["layers"][i])
               for i, kind in enumerate(p.sublayers)}
    # this issue's arithmetic: 13.7 M, 5.2 M, 16.0 + 44 M a layer
    assert by_kind["ssm"] == pytest.approx(13.7e6, rel=0.01)
    assert by_kind["gqa"] == pytest.approx(5.2e6, rel=0.01)
    assert by_kind["moe"] == pytest.approx(16.0e6 + 44.0e6, rel=0.01)
    assert count(params["mtp"]) == pytest.approx(99e6, rel=0.01)


# -- the rehearsal: the check and its controls --------------------------------

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
    return (seqs, targets, spec, grads, ssm_step.reference_numbers(
        start.params, seqs, targets, spec, grads))


def rows_of(tiny, releases, reference, program=None, unmoved=None):
    start, trained, _ = releases
    seqs, targets, spec, grads, sound = reference
    if program is not None:
        program = ssm_step.reference_numbers(
            start.params, seqs, targets, dataclasses.replace(spec, **program),
            # a fault of the optimizer alone reads the sound gradients
            grads if set(program) <= {"learning_rate", "expert_not_updated"}
            else None)
    rows = ssm_step.compare(
        program or ssm_step.program_numbers(trained.record), sound,
        trained.record, ssm_step.groups_unmoved(
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
    assert parts == {"attention", "state_space", "router", "experts",
                     "latent_projection", "shared_expert", "norms",
                     "embedding", "head", "mtp"}


def test_the_full_sizes_limits_name_the_same_rows(config):
    assert set(config["limits"]) == set(config["tiny"]["limits"])
    # every limit is a reading's: none left open
    assert all(0 <= v <= 1.0 for v in config["limits"].values())


@pytest.mark.parametrize("fault,row", [
    # the next precision below the stated one, in the program's place
    ({"precision": "int8"}, "seqrec_grad_norm_rel_err.state_space"),
    ({"decay_one": True}, "seqrec_grad_norm_rel_err.state_space"),
    ({"skip_left_out": True}, "seqrec_grad_norm_rel_err.state_space"),
    ({"norm_gate_left_out": True}, "seqrec_grad_norm_rel_err.state_space"),
    ({"latent_as_slice": True}, "seqrec_grad_norm_rel_err.experts"),
    ({"dropped_head": 0}, "seqrec_grad_norm_rel_err.state_space"),
    ({"relu_plain": True}, "seqrec_grad_norm_rel_err.experts"),
    ({"mtp_loss_weight": 0.0}, "seqrec_grad_norm_rel_err.mtp"),
    ({"mtp_wrong_item": True}, "seqrec_mtp_loss_rel_err"),
])
def test_a_fault_is_not_correct(tiny, releases, reference, fault, row):
    rows = rows_of(tiny, releases, reference, program=fault)
    assert not rows[row][3], rows[row]
    assert not rows["seqrec_loss_rel_err"][3]
    if "mtp_loss_weight" in fault:
        # the module's own loss is the sound one: its weight tells
        assert rows["seqrec_mtp_loss_rel_err"][3]


def test_a_wrong_optimizer_or_an_unchanged_state_is_not_correct(
        tiny, releases, reference):
    rows = rows_of(tiny, releases, reference, program={
        "learning_rate": 10 * tiny["algorithm_params"]["learning_rate"]})
    assert failed(rows) == sorted(
        name for name in rows if name.startswith("seqrec_update_norm"))
    start, _, _ = releases
    same = ssm_step.groups_unmoved(start.params, start.params)
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


def test_the_models_operations_by_hand(config):
    """6 per matrix parameter a token passes at the HELD sizes, the
    routed experts by their slots, the head twice, and the pair work."""
    shapes = ssm_step.shapes(_Run(config))
    assert shapes["held"] == {"n_heads": 4, "n_kv_heads": 1, "ssm_heads": 16,
                              "ssm_groups": 1, "shared_width": 672,
                              "experts": 8}
    assert [m or f for m, f in shapes["layers"]] == [
        "gqa", "moe", "ssm", "moe", "ssm", "moe", "ssm", "moe", "ssm", "moe",
        "ssm", "gqa", "moe"]
    tokens, slots = 8 * 8192, 6 * 8 * 2816.0
    ssm = 4096 * (2 * 1024 + 2 * 128) + 4096 * 16 + 4 * 1280 + 1024 * 4096
    gqa = 2 * 4096 * 512 + 2 * 4096 * 128
    moe = 4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 672
    per_token = 5 * ssm + 2 * gqa + 6 * moe + 2 * 4096 * 16_384 \
        + 2 * 4096 * 4096
    pairs = 8 * 4 * 8192 * 8193 / 2
    want = 6.0 * (tokens * per_token + slots * 2 * 1024 * 2688) \
        + 2 * pairs * 14 * 128 + 5 * tokens * 16 * 12 * 64 * 128
    assert seqrec_ssm_model.counts(shapes, slots) == pytest.approx(want)
    # 17.9 TFLOP a step (recomputation would add a forward pass)
    assert want / 8 == pytest.approx(1.79e13, rel=0.01)
    # the experts' kernels: eight products a slot at the latent's width
    evidence = {"shapes": shapes, "jobs": [{}, {}], "registry_before": {},
                "registry_after": {SLOTS: [[{"layer": "0"}, 2 * slots]]}}
    ops, nbytes = latent_grouped_product.counts(evidence, {}, 0)
    assert ops == 8 * 2.0 * slots * 1024 * 2688
    assert nbytes == 8 * 4.0 * (slots * (1024 + 2688)
                                + 6 * 8 * 8 * 1024 * 2688)
    # the SwiGLU experts' count has nothing to say of this spec; the
    # grouped-query kernels' reads the held heads and both attention layers
    assert grouped_product.counts(evidence, {}, 0) is None
    ops, _ = gqa_attention_kernel.counts(evidence, {}, 0)
    assert ops == 8 * 2 * (8192 * 8193 / 2 * 4) * (2 * 4 * 128 + 10 * 128)


def test_the_new_readers_return_nothing_from_a_program_without_them():
    """The parent commit has no such scope, counter label or shapes: the
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
    # another sequence cell's step: mixers of other kinds only
    evidence["registry_after"] = {
        "pio_train_seqrec_mixer_tokens_total": [[{"mixer": "gdn"}, 300.0],
                                                [{"mixer": "gqa"}, 100.0]]}
    assert layer_readers.read(evidence, manifest.load_layer_reader(
        "mixer_ssm_token_pct")) == 0.0
    # a trace of another cell's kernels: not this count's shapes
    evidence["trace"] = {"ops": [["grouped_product_pallas_rows.1", 2, 0.01]]}
    evidence["shapes"] = {"ffn": "moe", "steps": 8}
    assert layer_readers.read(evidence, manifest.load_layer_reader(
        "latent_expert_kernel_roofline")) is None


def test_the_new_readers_read_what_the_program_counts(config):
    shapes = ssm_step.shapes(_Run(config))
    positions, slots = 8 * 8192.0, 6 * 8 * 2816.0
    after = {"pio_train_seqrec_mixer_tokens_total": [
        [{"mixer": "ssm"}, 2 * 5 * positions],
        [{"mixer": "gqa"}, 2 * 2 * positions]],
        SLOTS: [[{"layer": "0"}, 2 * slots]]}
    evidence = {"jobs": [{"spans": {"seqrec_steps": 7.0}},
                         {"spans": {"seqrec_steps": 9.0}}],
                "registry_before": {}, "registry_after": after,
                "device": {"kind": "TPU v5 lite"}, "shapes": shapes}
    read = lambda name: layer_readers.read(
        evidence, manifest.load_layer_reader(name))
    assert read("mixer_ssm_token_pct") == pytest.approx(100 * 5 / 7)
    want = 100 * seqrec_ssm_model.counts(shapes, slots) / 8.0 / 197e12
    assert read("seqrec_ssm_mfu_pct") == pytest.approx(want)
    assert 0 < want < 100
    evidence["device"]["kind"] = "cpu"          # no peak, no share
    assert read("seqrec_ssm_mfu_pct") is None


def test_the_cell_lists_what_it_feeds_and_not_what_it_cannot():
    bench = manifest.load_benchmark()
    assert not manifest.check(bench)
    mine = {m["name"] for m in manifest.metrics_of_cell(bench, CELL,
                                                        "per_layer")}
    assert {*NEW, "attention_kernel_token_pct", "attention_kernel_fwd_ms",
            "attention_kernel_bwd_ms", "gqa_attention_kernel_roofline",
            "expert_kernel_ms", "expert_kernel_token_pct",
            "moe_dropped_tokens", "moe_expert_load_max_over_mean",
            "seqrec_step_ms", "seqrec_steps_s", "seqrec_init_s",
            "seqrec_prepare_s", "seqrec_fetch_s", "seqrec_pad_pct",
            "step_scope_ms.attention", "step_scope_ms.router",
            "step_scope_ms.experts", "step_scope_ms.shared_expert",
            "step_scope_ms.head_loss", "step_scope_ms.optimizer",
            "scope_named_pct.train", "hbm_peak_in_use_bytes.train",
            "hbm_peak_reserved_bytes.train", "device_idle_pct.train",
            "train_persist_s", "persist_device_fetched_pct",
            "persist_fetch_wait_s", "compiles_in_window.train",
            "xla_compiles_in_window.train", "ingest_scan_s"} <= mine
    # no dense feed-forward, no other mixer, and the counts that assume
    # SwiGLU experts, latent attention or a looped stack
    assert not mine & {"seqrec_model_flops_pct", "attention_kernel_roofline",
                       "mha_attention_kernel_roofline",
                       "expert_kernel_roofline", "seqrec_hybrid_mfu_pct",
                       "seqrec_conv_mfu_pct", "seqrec_looped_mfu_pct",
                       "mixer_linear_token_pct", "mixer_conv_token_pct",
                       "loop_repeat_token_pct", "step_scope_ms.ffn",
                       "step_scope_ms.short_conv",
                       "step_scope_ms.linear_attention",
                       "gdn_kernel_roofline", "als_solve_s"}
    assert CELL in next(m for m in bench["end_to_end"]
                        if m["name"] == "train_wall_s")["workloads"]
    cell = manifest.find_cell(bench, CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) == \
        (1, "train-backtoback", NAME)
    assert manifest.load_traffic(cell)["warm_jobs"] == 2
    assert len(cell["why"]) <= 200
