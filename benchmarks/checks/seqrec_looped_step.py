"""The check of a `sessionrec` train under a layer spec of a looped
decoder (a sandwich-normed multi-head-attention stack run `n_loops`
times over the same weights, an exit gate after each pass, the loss an
expectation over the exits; events/sessions_longhist.py): the first step
of the window's last train against the plain reference
(checks/seqrec_looped_reference.py) at the same widths, and what the
whole train did to its state.

As checks/seqrec_step.py, whose batch-making it shares: the release
carries a record of each step (`SeqRecModel.record`); the first step
starts from the seeded weights theta_0, which the same train with
`epochs` 0 releases; its batch is made here from the generated sessions
and the configuration alone. The reference computes, at theta_0 on that
batch, with recomputation so that it fits: the loss, each pass's own
cross-entropy and exit share, the gradient and, from its own gradient,
adamw's first step. Rows:

  seqrec_loss_rel_err         |loss - reference| / reference, step 1
  seqrec_loop_loss_rel_err.<pass>  the same of pass <pass>'s own
                              cross-entropy (0 = the first pass): a pass
                              left out or run on other weights shows
                              here, pass by pass
  seqrec_exit_share_err.<pass>  |mean probability of leaving at the pass
                              - reference|, step 1
  seqrec_grad_norm_rel_err.<part>  the worst |norm - reference| /
                              reference among the part's parameter groups,
                              step 1; parts: embedding, head, exit_gate
                              and over the layers attention, ffn, norms
                              (a layer's four and the last norm). A
                              shared weight's gradient is the sum over
                              the passes: one pass's part dropped shows
                              in every layer's groups
  seqrec_update_norm_rel_err.<part>  the same of what step 1 added to the
                              parameters, against the reference's adamw
                              step
  seqrec_groups_unmoved       parameter groups equal in theta_n and theta_0

There is no row of the last step's loss over the first's, which the other
sequence checks have: at the whole vocabulary a train of 65,536 tokens
shows the model each of 49,151 items 1.3 times, every step trains on
another session, and the loss falls by 0.15 to 0.33% over four steps
where a session's own loss moves by 0.1% (PERF.md section 2): a limit of
1 would leave no room above the reading. A state left unchanged reads
by `seqrec_groups_unmoved`, a wrong optimizer by the update rows.

A number that is not finite is not ok.
"""

from __future__ import annotations

import math

import numpy as np

from benchmarks.checks import seqrec_looped_reference as ref
from benchmarks.checks.seqrec_step import first_batch


def program_numbers(record: dict) -> dict:
    """Step 1 of a release's record, as `compare` reads it."""
    return {key: record[key][0] for key in (
        "loss", "grad_norm", "update_norm", "loop_loss", "exit_share")}


def reference_numbers(params, seqs, targets, spec: ref.Spec,
                      grads_of=None) -> dict:
    """What the reference makes of theta_0 and the batch; `grads_of`
    hands it a (loss, gradients, passes) computed before, for a spec that
    differs in the optimizer alone."""
    loss, grads, passes = grads_of or ref.loss_and_grads(params, seqs,
                                                         targets, spec)
    return {"loss": loss, "grad_norm": ref.group_norms(grads),
            "update_norm": ref.first_update_norms(params, grads, spec),
            **passes}


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


def compare(program: dict, reference: dict, unmoved: int, limits: dict):
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

    def by_pass(key, err):
        """A pass the program lacks is infinitely far."""
        got = np.asarray(program[key], np.float64).reshape(-1)
        return {n: err(got[n], want) if n < len(got) else math.inf
                for n, want in enumerate(reference[key].tolist())}

    values = {
        "seqrec_loss_rel_err": rel(program["loss"], reference["loss"]),
        **{f"seqrec_loop_loss_rel_err.{n}": err
           for n, err in by_pass("loop_loss", rel).items()},
        **{f"seqrec_exit_share_err.{n}": err for n, err in by_pass(
            "exit_share", lambda a, b: abs(a - b)).items()},
        **{f"seqrec_grad_norm_rel_err.{part}": err
           for part, err in worst_by_part("grad_norm")},
        **{f"seqrec_update_norm_rel_err.{part}": err
           for part, err in worst_by_part("update_norm")},
        "seqrec_groups_unmoved": float(unmoved),
    }
    return [(name, float(value), limits[name],
             bool(math.isfinite(value) and value <= limits[name]))
            for name, value in values.items()]


def check(run):
    cfg = run.config
    trained = run.load_model(run.instance)
    start = run.load_model(run.train_again({"epochs": 0}))
    record = trained.record
    seqs, targets = first_batch(cfg, run.truth["sessions"])
    spec = ref.Spec.of(cfg["algorithm_params"], recompute=True)
    reference = reference_numbers(start.params, seqs, targets, spec)
    return compare(program_numbers(record), reference,
                   groups_unmoved(start.params, trained.params),
                   cfg["limits"])


def shapes(run):
    """What the count functions read (counts/seqrec_looped_model.py,
    counts/mha_attention_kernel.py): the spec, `n_loops` with it, and the
    sizes of a train."""
    model = run.load_model(run.instance)
    steps = len(model.record["loss"])
    return {**run.config["algorithm_params"],
            "n_vocab": int(model.params["emb"].shape[0]), "steps": steps,
            "tokens_per_step": len(model.record["rows"][0])
            * model.hyper.max_len if steps else 0}
