"""The check of a `sessionrec` train under a layer spec of single-sub-layer
layers (state-space layers, attention without positions, two-matrix
experts in a latent) with a multi-token-prediction module, one chip's
tensor and expert share (events/sessions_longhist.py): the first step of
the window's last train against the plain reference
(checks/seqrec_ssm_reference.py) at the same widths and the same share,
and what the whole train did to its state.

As checks/seqrec_step.py, whose batch-making it shares: the release
carries a record of each step (`SeqRecModel.record`); the first step
starts from the seeded weights theta_0, which the same train with
`epochs` 0 releases; its batch is made here from the generated sessions
and the configuration alone. The reference computes, at theta_0 on that
batch, with recomputation so that it fits: the loss, the module's own
loss, the gradient, the expert loads and, from its own gradient, adamw's
first step. Rows:

  seqrec_loss_rel_err         |loss - reference| / reference, step 1
                              (the main cross-entropy + mtp_loss_weight
                              x the module's)
  seqrec_mtp_loss_rel_err     the same of the module's own cross-entropy
                              (position t against item t + 2): a module
                              scored against the wrong item (the
                              reference's `mtp_wrong_item`) shows here
                              ten times as large as in a loss that
                              holds it a tenth
  seqrec_grad_norm_rel_err.<part>  the worst |norm - reference| /
                              reference among the part's parameter groups,
                              step 1; parts: embedding, head, mtp (the
                              module's two norms, its projection and its
                              last norm) and over the layers, the module's
                              among them, state_space, attention, router,
                              latent_projection, experts (the held ones),
                              shared_expert, norms. A part has its own
                              limit because its gradient has its own noise
                              under bfloat16 passes, and a fault in one
                              part must not hide under another's
  seqrec_update_norm_rel_err.<part>  the same of what step 1 added to the
                              parameters, against the reference's adamw
                              step; the part `experts` expert by expert
                              and weighed by the expert's tokens: the
                              worst over the expert layers of sum_e n_e
                              |norm_e - reference_e| / reference_e over
                              sum_e n_e, n_e the tokens the reference
                              routed to held expert e. (A hidden unit
                              that none of an expert's tokens switched on
                              has a gradient of exactly 0 and adamw
                              leaves it where it is, so the norm of an
                              expert's update counts the units its
                              tokens reached: of an expert that the
                              seeded router sends two tokens, one token
                              more or fewer under bfloat16 passes moves a
                              quarter of its 5.5 M entries. By the
                              layer's norm that is 1% and up to 5%, as
                              much as an expert that is not trained at
                              all; by token it is what it is to the
                              model, a few of a layer's 2,800 routed
                              slots, and an expert left where it is
                              reads its share of them)
  seqrec_expert_load_rel_err  sum |tokens - reference| over the experts of
                              every layer, over the routed slots, step 1
  seqrec_dropped_tokens       tokens routed here whose output is 0, all steps
  seqrec_groups_unmoved       parameter groups equal in theta_n and theta_0
  seqrec_last_over_first_loss the last step's loss over the first's

The selection bias stays at 0 (`bias_update_rate` 0), so there is no
bias row; a router's group holds it, and its matrix has to move. A number
that is not finite is not ok.
"""

from __future__ import annotations

import math

import numpy as np

from benchmarks.checks import seqrec_ssm_reference as ref
from benchmarks.checks.seqrec_step import first_batch


def program_numbers(record: dict) -> dict:
    """Step 1 of a release's record, as `compare` reads it."""
    return {"loss": record["loss"][0], "mtp_loss": record["mtp_loss"][0],
            "grad_norm": record["grad_norm"][0],
            "update_norm": record["update_norm"][0],
            "expert_update_norm": np.asarray(record["expert_update_norm"][0]),
            "load": np.asarray(record["load"][0])}


def reference_numbers(params, seqs, targets, spec: ref.Spec,
                      grads_of=None) -> dict:
    """What the reference makes of theta_0 and the batch; `grads_of`
    hands it a (loss, gradients, the rest) computed before, for a spec
    that differs in the optimizer alone."""
    loss, grads, rest = grads_of or ref.loss_and_grads(params, seqs, targets,
                                                       spec)
    update_norm, by_expert = ref.first_update_norms(params, grads, spec)
    lo, hi = spec.held_experts
    return {"loss": loss, "grad_norm": ref.group_norms(grads),
            "update_norm": update_norm, "expert_update_norm": by_expert,
            "held_load": np.asarray(rest["load"])[:, lo:hi], **rest}


def groups_unmoved(start, end) -> int:
    """Parameter groups in which no number differs between two
    releases' weights."""
    import jax

    moved = {}
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(start),
                            jax.tree.leaves(end)):
        name = ref.grad_group(path)
        moved[name] = moved.get(name, False) or not np.array_equal(a, b)
    return sum(1 for m in moved.values() if not m)


def compare(program: dict, reference: dict, record: dict, unmoved: int,
            limits: dict):
    """-> rows of (name, value, limit, ok)."""
    def rel(a, b):
        """|a - b| / |b|; a number that is missing or not finite is
        infinitely far."""
        err = abs(a - b) / abs(b) if b else math.inf
        return err if math.isfinite(err) else math.inf

    def worst_by_part(key):
        worst = {}
        for group, norm in reference[key].items():
            part = "norms" if group == "final_norm" else group.split(".")[-1]
            worst[part] = max(worst.get(part, 0.0), rel(
                program[key].get(group, math.nan), norm))
        return sorted(worst.items())

    load = float(np.abs(program["load"] - reference["load"]).sum()
                 / reference["load"].sum())
    tokens = reference["held_load"]                # [expert layer, held]
    theirs = reference["expert_update_norm"]
    with np.errstate(divide="ignore", invalid="ignore"):
        # an expert without a token weighs nothing, whatever it reads
        off = np.where(tokens > 0, tokens * np.abs(
            program["expert_update_norm"] - theirs) / theirs, 0.0)
    experts_update = float((off.sum(-1) / tokens.sum(-1)).max())
    values = {
        "seqrec_loss_rel_err": rel(program["loss"], reference["loss"]),
        "seqrec_mtp_loss_rel_err": rel(program["mtp_loss"],
                                       reference["mtp_loss"]),
        **{f"seqrec_grad_norm_rel_err.{part}": err
           for part, err in worst_by_part("grad_norm")},
        **{f"seqrec_update_norm_rel_err.{part}": err
           for part, err in worst_by_part("update_norm")},
        # in the place of the layers' whole norms
        "seqrec_update_norm_rel_err.experts": experts_update,
        "seqrec_expert_load_rel_err": load,
        "seqrec_dropped_tokens": float(np.asarray(record["dropped"]).sum()),
        "seqrec_groups_unmoved": float(unmoved),
        "seqrec_last_over_first_loss": record["loss"][-1] / record["loss"][0],
    }
    return [(name, float(value), limits[name],
             bool(math.isfinite(value) and value <= limits[name]))
            for name, value in values.items()]


def check(run):
    cfg = run.config
    trained = run.load_model(run.instance)
    start = run.load_model(run.train_again({"epochs": 0}))
    seqs, targets = first_batch(cfg, run.truth["sessions"])
    spec = ref.Spec.of(cfg["algorithm_params"], recompute=True)
    reference = reference_numbers(start.params, seqs, targets, spec)
    return compare(program_numbers(trained.record), reference,
                   trained.record,
                   groups_unmoved(start.params, trained.params),
                   cfg["limits"])


def run_layers(algorithm_params: dict):
    """[mixer or None, feed-forward or None] of every layer a step runs:
    the stack's, then the module's."""
    ap = algorithm_params
    period = ap["sublayers"]
    kinds = [period[i % len(period)] for i in range(ap["n_layers"])] \
        + list(ap["mtp_layers"])
    return [[kind, None] if kind in ("gqa", "ssm") else [None, kind]
            for kind in kinds]


def shapes(run):
    """What the count functions read: the spec and the sizes of a train
    and, for counts/seqrec_ssm_model.py and counts/latent_grouped_
    product.py, `layers` (every layer a step runs, the module's after
    the stack's) and `held` (the sizes this tensor and expert share
    holds). `n_heads`, `n_kv_heads`, `mixer` and `n_layers` are given as
    counts/gqa_attention_kernel.py reads them: the HELD query and
    key/value heads (what the kernels are called with) and each run
    layer's mixer by name, the module's attention layer among them."""
    ap = run.config["algorithm_params"]
    ways = ap["tensor_ways"]
    model = run.load_model(run.instance)
    steps = len(model.record["loss"])
    layers = run_layers(ap)
    held = {"n_heads": ap["n_heads"] // ways,
            "n_kv_heads": max(1, ap["n_kv_heads"] // ways),
            "ssm_heads": ap["ssm"]["heads"] // ways,
            "ssm_groups": ap["ssm"]["groups"] // ways,
            "shared_width": ap["n_shared_experts"] * ap["moe_width"] // ways,
            "experts": ap["held_experts"][1] - ap["held_experts"][0]}
    return {**ap, "layers": layers, "held": held,
            "n_heads": held["n_heads"], "n_kv_heads": held["n_kv_heads"],
            "mixer": [mixer or "none" for mixer, _ in layers],
            "n_layers": len(layers),
            "n_vocab": int(model.params["emb"].shape[0]), "steps": steps,
            "tokens_per_step": len(model.record["rows"][0])
            * model.hyper.max_len if steps else 0}
