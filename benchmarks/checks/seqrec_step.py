"""The check of a `sessionrec` train under a layer spec of latent
attention and routed experts (events/sessions_longhist.py): the first
step of the window's last train against the plain reference
(checks/seqrec_reference.py) at the same widths, and what the whole train
did to its state.

The release carries a record of each step (`SeqRecModel.record`): the
loss, by parameter group the gradient's norm and the norm of what the
step added to the parameters, the tokens the router sent to each expert,
the tokens dropped. The first step starts from the seeded weights
theta_0, which the same train with `epochs` 0 releases (the program is
deterministic; no other weights are taken from it). Its batch is made
here from the generated sessions and the configuration alone: the items
coded by their rank as text, the sessions in the data source's order
(by user id as text), the batch the first `batch_size` of epoch 0's
seeded shuffle; neither the release's vocabulary nor its record says
which sessions were trained on. The reference computes, at theta_0 on
that batch, with recomputation so that it fits: the loss, its gradient,
the expert loads and, from its own gradient, adamw's first step. Rows:

  seqrec_loss_rel_err         |loss - reference| / reference, step 1
  seqrec_grad_norm_rel_err.<part>  the worst |norm - reference| /
                              reference among the part's parameter groups,
                              step 1; parts: embedding, head, and over the
                              layers attention, router, experts (the held
                              ones), shared_expert, ffn (the dense layer),
                              norms. A part has its own limit because its
                              gradient has its own noise under bfloat16
                              passes, and a fault in one part (an expert
                              left out) must not hide under another's
  seqrec_update_norm_rel_err.<part>  the same of what step 1 added to the
                              parameters, against the reference's adamw
                              step (a router's group holds its selection
                              bias, moved by the load): the optimizer's
                              learning rate, moments and what it leaves alone
  seqrec_expert_load_rel_err  sum |tokens - reference| over the experts of
                              every layer, over the routed slots, step 1
  seqrec_router_bias_err      the largest |b_n - what the recorded loads of
                              all steps make of b_0 = 0| over the routers:
                              the bias update's rate and sign, and that
                              nothing else (adamw, its decay) moves a bias
  seqrec_dropped_tokens       tokens routed here whose output is 0, all steps
  seqrec_groups_unmoved       parameter groups equal in theta_n and theta_0
  seqrec_last_over_first_loss the last step's loss over the first's

A number that is not finite is not ok.
"""

from __future__ import annotations

import math

import numpy as np

from benchmarks.checks import seqrec_reference as ref


def program_order(sessions: np.ndarray) -> np.ndarray:
    """The generated sessions (row u = user u + 1) in the order the
    template's data source hands them to the model: by user id as text."""
    ids = np.asarray([str(u + 1) for u in range(len(sessions))])
    return sessions[np.argsort(ids, kind="stable")]


def epoch0_rows(algorithm_params: dict, n_sessions: int) -> np.ndarray:
    """The sessions of epoch 0's batches, one after another: the seeded
    shuffle `train_seqrec` documents."""
    order = np.arange(n_sessions)
    np.random.default_rng(algorithm_params["seed"]).shuffle(order)
    return order


def coded_batch(sessions: np.ndarray, rows, max_len: int):
    """(inputs, targets) [len(rows), max_len] of the sessions `rows`
    (in the data source's order), an item's code its rank among all the
    sessions' items as text, from 1."""
    items = sorted({str(it) for it in np.unique(sessions).tolist()})
    code = {it: i + 1 for i, it in enumerate(items)}
    picked = program_order(sessions)[np.asarray(rows)][:, -(max_len + 1):]
    coded = np.asarray([[code[str(it)] for it in row]
                        for row in picked.tolist()], np.int32)
    return coded[:, :-1], coded[:, 1:]


def first_batch(config: dict, sessions: np.ndarray):
    ap = config["algorithm_params"]
    rows = epoch0_rows(ap, len(sessions))[:min(ap["batch_size"],
                                               len(sessions))]
    return coded_batch(sessions, rows, ap["max_len"])


def program_numbers(record: dict) -> dict:
    """Step 1 of a release's record, as `compare` reads it."""
    return {"loss": record["loss"][0], "grad_norm": record["grad_norm"][0],
            "update_norm": record["update_norm"][0],
            "load": np.asarray(record["load"][0])}


def reference_numbers(params, seqs, targets, spec: ref.Spec) -> dict:
    loss, grads, load = ref.loss_and_grads(params, seqs, targets, spec)
    return {"loss": loss, "grad_norm": ref.group_norms(grads),
            "update_norm": ref.first_update_norms(params, grads, load, spec),
            "load": load}


def router_bias_err(params, record: dict, spec: ref.Spec) -> float:
    """The largest distance of a released selection bias from what the
    recorded loads of every step make of a bias of 0."""
    worst = 0.0
    routers = [layer["router_bias"] for layer in params["layers"]
               if "router_bias" in layer]
    for n, released in enumerate(routers):
        bias = np.zeros(len(released))
        for load in record["load"]:
            bias = ref.bias_after_step(bias, load[n], spec.bias_update_rate)
        worst = max(worst, float(np.abs(np.asarray(released) - bias).max()))
    return worst


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
            bias_err: float, limits: dict):
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
        "seqrec_router_bias_err": float(bias_err),
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
    record = trained.record
    seqs, targets = first_batch(cfg, run.truth["sessions"])
    spec = ref.Spec.of(cfg["algorithm_params"], recompute=True)
    reference = reference_numbers(start.params, seqs, targets, spec)
    return compare(program_numbers(record), reference, record,
                   groups_unmoved(start.params, trained.params),
                   router_bias_err(trained.params, record, spec),
                   cfg["limits"])


def shapes(run):
    """What counts/seqrec_model.py reads: the spec and the sizes of a
    train."""
    model = run.load_model(run.instance)
    steps = len(model.record["loss"])
    return {**run.config["algorithm_params"],
            "n_vocab": int(model.params["emb"].shape[0]), "steps": steps,
            "tokens_per_step": len(model.record["rows"][0])
            * model.hyper.max_len if steps else 0}
