"""The check of a `sessionrec` train under a layer spec of sliding-window
and full attention layers (head counts and rotary tables by kind, a
per-head gate) with a leading dense layer and sigmoid-routed experts
beside a shared one, one chip's expert share (events/
sessions_longhist.py): the first step of the window's last train against
the plain reference (checks/seqrec_window_reference.py) at the same
widths and the same share, and what the whole train did to its state.

As checks/seqrec_step.py, whose batch-making it shares: the release
carries a record of each step (`SeqRecModel.record`); the first step
starts from the seeded weights theta_0, which the same train with
`epochs` 0 releases; its batch is made here from the generated sessions
and the configuration alone. The reference computes, at theta_0 on that
batch, with recomputation so that it fits: the loss, the gradient, the
expert loads and, from its own gradient, adamw's first step. Rows:

  seqrec_loss_rel_err         |loss - reference| / reference, step 1
  seqrec_grad_norm_rel_err.<part>  the worst |norm - reference| /
                              reference among the part's parameter groups,
                              step 1; parts: embedding, head and over the
                              layers attention (the full layers),
                              window_attention (the sliding ones), ffn
                              (the dense layer), router, experts (the
                              held ones), shared_expert, norms. A part has
                              its own limit because its gradient has its
                              own noise under bfloat16 passes, and a fault
                              in one part must not hide under another's:
                              the band's edge and the sliding layers'
                              rotary table show in window_attention, YaRN
                              in attention
  seqrec_update_norm_rel_err.<part>  the same of what step 1 added to the
                              parameters, against the reference's adamw
                              step; the part `experts` expert by expert
                              and weighed by the expert's tokens: the
                              worst over the expert layers of sum_e n_e
                              |norm_e - reference_e| / reference_e over
                              sum_e n_e, n_e the tokens the reference
                              routed to held expert e (adamw's first step
                              moves an entry by the rate's sign step or,
                              where its gradient is 0, not at all: an
                              expert that gets no token, or a token more
                              or fewer than the reference's, moves the
                              layer's whole norm by a count, not by
                              rounding noise; by token it weighs what it
                              is to the model. PERF.md section 2)
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

from benchmarks.checks import seqrec_window_reference as ref
from benchmarks.checks.seqrec_step import first_batch


def program_numbers(record: dict) -> dict:
    """Step 1 of a release's record, as `compare` reads it."""
    return {"loss": record["loss"][0], "grad_norm": record["grad_norm"][0],
            "update_norm": record["update_norm"][0],
            "expert_update_norm": np.asarray(record["expert_update_norm"][0]),
            "load": np.asarray(record["load"][0])}


def reference_numbers(params, seqs, targets, spec: ref.Spec,
                      grads_of=None) -> dict:
    """What the reference makes of theta_0 and the batch; `grads_of`
    hands it a (loss, gradients, loads) computed before, for a spec that
    differs in the optimizer alone."""
    loss, grads, load = grads_of or ref.loss_and_grads(params, seqs, targets,
                                                       spec)
    update_norm, by_expert = ref.first_update_norms(params, grads, spec)
    lo, hi = spec.held_experts
    return {"loss": loss, "grad_norm": ref.group_norms(grads),
            "update_norm": update_norm, "expert_update_norm": by_expert,
            "load": np.asarray(load),
            "held_load": np.asarray(load)[:, lo:hi]}


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


def shapes(run):
    """What the count functions read: the spec and the sizes of a train.
    `n_heads` and `mixer` are the full layers' heads and every layer's
    mixer by name, as counts/gqa_attention_kernel.py reads them (it
    counts the "gqa" layers alone); the sliding layers' sizes are under
    `swa`, as counts/window_attention_kernel.py and counts/
    seqrec_window_model.py read them."""
    ap = run.config["algorithm_params"]
    model = run.load_model(run.instance)
    steps = len(model.record["loss"])
    return {**ap, "n_vocab": int(model.params["emb"].shape[0]),
            "steps": steps,
            "tokens_per_step": len(model.record["rows"][0])
            * model.hyper.max_len if steps else 0}
