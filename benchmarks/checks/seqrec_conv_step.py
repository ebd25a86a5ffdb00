"""The check of a `sessionrec` train under a layer spec of gated short
convolutions and ungated grouped-query attention, a leading dense layer,
sigmoid-routed experts behind a selection bias and a tied head
(events/sessions_longhist.py): the first step of the window's last train
against the plain reference (checks/seqrec_conv_reference.py) at the same
widths, and what the whole train did to its state.

As checks/seqrec_step.py, whose batch-making, bias row and comparison it
shares: the release carries a record of each step (`SeqRecModel.record`);
the first step starts from the seeded weights theta_0, which the same
train with `epochs` 0 releases; its batch is made here from the generated
sessions and the configuration alone. The reference computes, at theta_0
on that batch, with recomputation so that it fits: the loss, its
gradient, the expert loads and, from its own gradient, adamw's first
step. Rows:

  seqrec_loss_rel_err         |loss - reference| / reference, step 1
  seqrec_grad_norm_rel_err.<part>  the worst |norm - reference| /
                              reference among the part's parameter groups,
                              step 1; parts: embedding (the tied table:
                              it is the head too, so there is no `head`
                              part), and over the layers short_conv,
                              attention, ffn (the dense layer), router,
                              experts (the held ones; there is no shared
                              expert), norms. A part has its own limit
                              because its gradient has its own noise under
                              bfloat16 passes, and a fault in one part must
                              not hide under another's
  seqrec_update_norm_rel_err.<part>  the same of what step 1 added to the
                              parameters, against the reference's adamw
                              step (a router's group holds its selection
                              bias, moved by the load)
  seqrec_expert_load_rel_err  sum |tokens - reference| over the experts of
                              every layer, over the routed slots, step 1
  seqrec_router_bias_err      the largest |b_n - what the recorded loads of
                              all steps make of b_0 = 0| over the routers
  seqrec_dropped_tokens       tokens routed here whose output is 0, all steps
  seqrec_groups_unmoved       parameter groups equal in theta_n and theta_0
  seqrec_last_over_first_loss the last step's loss over the first's

A number that is not finite is not ok.
"""

from __future__ import annotations

import numpy as np

from benchmarks.checks import seqrec_conv_reference as ref
# `shapes(run)`, which the harness asks this file for, is seqrec_step's:
# the spec and the sizes of a train, as counts/seqrec_conv_model.py and
# counts/gqa_attention_kernel.py read them
from benchmarks.checks.seqrec_step import (  # noqa: F401
    compare, first_batch, program_numbers, router_bias_err, shapes,
)


def reference_numbers(params, seqs, targets, spec: ref.Spec,
                      grads_of=None) -> dict:
    """What the reference makes of theta_0 and the batch; `grads_of`
    hands it a (loss, gradients, loads) computed before, for a spec that
    differs in the optimizer alone."""
    loss, grads, load = grads_of or ref.loss_and_grads(params, seqs, targets,
                                                       spec)
    return {"loss": loss, "grad_norm": ref.group_norms(grads),
            "update_norm": ref.first_update_norms(params, grads, load, spec),
            "load": load}


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


def check(run):
    cfg = run.config
    trained = run.load_model(run.instance)
    start = run.load_model(run.train_again({"epochs": 0}))
    record = trained.record
    seqs, targets = first_batch(cfg, run.truth["sessions"])
    spec = ref.Spec.of(cfg["algorithm_params"], recompute=True)
    reference = reference_numbers(start.params, seqs, targets, spec)
    return compare(program_numbers(record), reference, record,
                   groups_unmoved(start.params, trained.params),
                   router_bias_err(trained.params, record, spec),
                   cfg["limits"])

