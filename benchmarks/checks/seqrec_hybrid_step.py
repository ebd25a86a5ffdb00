"""The check of a `sessionrec` train under a layer spec of linear
attention (the gated delta rule) and gated grouped-query attention in one
period, with softmax-routed experts (events/sessions_longhist.py): the
first step of the window's last train against the plain reference
(checks/seqrec_hybrid_reference.py) at the same widths, and what the
whole train did to its state.

As checks/seqrec_step.py, whose batch-making it shares: the release
carries a record of each step (`SeqRecModel.record`); the first step
starts from the seeded weights theta_0, which the same train with
`epochs` 0 releases; its batch is made here from the generated sessions
and the configuration alone. The reference computes, at theta_0 on that
batch, with recomputation so that it fits: the loss, its gradient, the
expert loads and, from its own gradient, adamw's first step. Rows:

  seqrec_loss_rel_err         |loss - reference| / reference, step 1
  seqrec_grad_norm_rel_err.<part>  the worst |norm - reference| /
                              reference among the part's parameter groups,
                              step 1; parts: embedding, head, and over the
                              layers linear_attention, attention, router,
                              experts (the held ones), shared_expert (with
                              its gate), norms. A part has its own limit
                              because its gradient has its own noise under
                              bfloat16 passes, and a fault in one part must
                              not hide under another's
  seqrec_update_norm_rel_err.<part>  the same of what step 1 added to the
                              parameters, against the reference's adamw
                              step: the optimizer's learning rate, moments
                              and what it leaves alone
  seqrec_expert_load_rel_err  sum |tokens - reference| over the experts of
                              every layer, over the routed slots, step 1
  seqrec_dropped_tokens       tokens routed here whose output is 0, all steps
  seqrec_groups_unmoved       parameter groups equal in theta_n and theta_0
  seqrec_last_over_first_loss the last step's loss over the first's

There is no selection bias under this spec (the program keeps one at 0)
and no dense layer, so neither a bias row nor an `ffn` part. A number
that is not finite is not ok.
"""

from __future__ import annotations

import math

import numpy as np

from benchmarks.checks import seqrec_hybrid_reference as ref
from benchmarks.checks.seqrec_step import first_batch, program_numbers


def reference_numbers(params, seqs, targets, spec: ref.Spec,
                      grads_of=None) -> dict:
    """What the reference makes of theta_0 and the batch; `grads_of`
    hands it a (loss, gradients, loads) computed before, for a spec that
    differs in the optimizer alone."""
    loss, grads, load = grads_of or ref.loss_and_grads(params, seqs, targets,
                                                       spec)
    return {"loss": loss, "grad_norm": ref.group_norms(grads),
            "update_norm": ref.first_update_norms(params, grads, spec),
            "load": load}


def groups_unmoved(start, end) -> int:
    """Parameter groups in which no number differs between two
    releases' weights (a router's group holds the selection bias, which
    stays at 0: its matrix has to move)."""
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
    values = {
        "seqrec_loss_rel_err": rel(program["loss"], reference["loss"]),
        **{f"seqrec_grad_norm_rel_err.{part}": err
           for part, err in worst_by_part("grad_norm")},
        **{f"seqrec_update_norm_rel_err.{part}": err
           for part, err in worst_by_part("update_norm")},
        "seqrec_expert_load_rel_err": load,
        "seqrec_dropped_tokens": float(np.asarray(record["dropped"]).sum()),
        "seqrec_groups_unmoved": float(unmoved),
        "seqrec_last_over_first_loss": record["loss"][-1] / record["loss"][0],
    }
    return [(name, value, limits[name],
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
    """What counts/seqrec_hybrid_model.py and counts/
    gqa_attention_kernel.py read: the spec and the sizes of a train."""
    model = run.load_model(run.instance)
    steps = len(model.record["loss"])
    return {**run.config["algorithm_params"],
            "n_vocab": int(model.params["emb"].shape[0]), "steps": steps,
            "tokens_per_step": len(model.record["rows"][0])
            * model.hyper.max_len if steps else 0}
