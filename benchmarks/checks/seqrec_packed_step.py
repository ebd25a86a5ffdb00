"""The check of a `sessionrec` train on PACKED rows under a layer spec of
sliding-window and full attention layers over grouped query heads with
softmax-routed experts in every layer, one chip's expert share (events/
sessions_packed.py): the first step of the window's last train against
the plain reference (checks/seqrec_packed_reference.py), which never
packs: it runs every session of the step's rows alone, from position 0,
and adds up. So what the program does at a session's boundary (a key of
the session before, a position that did not restart, a target across
it) is compared with a computation that has no boundary.

As checks/seqrec_window_step.py, whose comparison this is: the release
carries a record of each step (`SeqRecModel.record`); the first step
starts from the seeded weights theta_0, which the same train with
`epochs` 0 releases; its batch is made HERE from the generated sessions
and the configuration alone: the items coded by their rank as text, the
sessions in the data source's order (by user id as text), laid into rows
of `max_len` by first-fit over the sessions in decreasing length (this
file's own few lines, `rows_of`: the rule the configuration states, not
the program's code), the batch the first `batch_size` ROWS of epoch 0's
seeded shuffle. Neither the release's vocabulary nor its record says
which sessions were trained on. Rows (`seqrec_window_step.compare`):

  seqrec_loss_rel_err         |loss - reference| / reference, step 1
  seqrec_grad_norm_rel_err.<part>  the worst |norm - reference| /
                              reference among the part's parameter groups,
                              step 1; parts: embedding, head and over the
                              layers attention (the full layer),
                              window_attention (the sliding ones), router,
                              experts (the held ones), norms: a session's
                              boundary, the band's edge and the restart
                              of positions show in window_attention and
                              attention, YaRN in attention alone
  seqrec_update_norm_rel_err.<part>  the same of what step 1 added to the
                              parameters, against the reference's adamw
                              step; the part `experts` expert by expert
                              and weighed by the expert's tokens
  seqrec_expert_load_rel_err  sum |tokens - reference| over the experts of
                              every layer, over the routed slots, step 1
                              (a row's padding positions are routed too,
                              on both sides)
  seqrec_dropped_tokens       tokens routed here whose output is 0, all steps
  seqrec_groups_unmoved       parameter groups equal in theta_n and theta_0
  seqrec_last_over_first_loss the last step's loss over the first's

A number that is not finite is not ok.
"""

from __future__ import annotations

from typing import List, Sequence

import numpy as np

from benchmarks.checks import seqrec_packed_reference as ref
from benchmarks.checks.seqrec_step import epoch0_rows
from benchmarks.checks.seqrec_window_step import (compare, groups_unmoved,
                                                  program_numbers)


def program_order(sessions: Sequence[np.ndarray]) -> List[np.ndarray]:
    """The generated sessions (entry u = user u + 1) in the order the
    template's data source hands them to the model: by user id as text."""
    return [sessions[u] for u in sorted(range(len(sessions)),
                                        key=lambda u: str(u + 1))]


def rows_of(spans: Sequence[int], max_len: int) -> List[List[int]]:
    """First-fit over the sessions in decreasing length (ties in the
    order given): each into the first row with room for its `spans`
    positions -> the sessions of each row, in order."""
    room: List[int] = []
    rows: List[List[int]] = []
    for i in sorted(range(len(spans)), key=lambda i: -spans[i]):
        row = next((r for r, left in enumerate(room) if left >= spans[i]),
                   len(room))
        if row == len(room):
            room.append(max_len)
            rows.append([])
        room[row] -= spans[i]
        rows[row].append(i)
    return rows


def coded_sessions(sessions: Sequence[np.ndarray], max_len: int):
    """[(inputs, targets)] a session, in the data source's order: an
    item's code its rank among all the sessions' items as text, from 1;
    a session's last max_len + 1 items, shifted."""
    items = sorted({str(it) for s in sessions for it in s.tolist()})
    code = {it: i + 1 for i, it in enumerate(items)}
    out = []
    for s in program_order(sessions):
        coded = np.asarray([code[str(it)] for it in s.tolist()[
            -(max_len + 1):]], np.int32)
        out.append((coded[:-1], coded[1:]))
    return out


def packed_rows(config: dict, sessions: Sequence[np.ndarray]):
    """(the coded sessions in the data source's order, the sessions of
    each packed row)."""
    coded = coded_sessions(sessions, config["algorithm_params"]["max_len"])
    return coded, rows_of([len(inputs) for inputs, _ in coded],
                          config["algorithm_params"]["max_len"])


def first_batch(config: dict, sessions: Sequence[np.ndarray]):
    """-> (the first step's sessions [(inputs, targets)], each alone; the
    positions of its rows)."""
    ap = config["algorithm_params"]
    coded, rows = packed_rows(config, sessions)
    batch = epoch0_rows(ap, len(rows))[:min(ap["batch_size"], len(rows))]
    return [coded[i] for r in batch for i in rows[r]], \
        len(batch) * ap["max_len"]


def reference_numbers(params, sessions, n_positions: int, spec: ref.Spec,
                      grads_of=None) -> dict:
    """What the reference makes of theta_0 and the step's sessions;
    `grads_of` hands it a (loss, gradients, loads) computed before, for a
    spec that differs in the optimizer alone."""
    loss, grads, load = grads_of or ref.loss_and_grads(
        params, sessions, spec, n_positions)
    update_norm, by_expert = ref.first_update_norms(params, grads, spec)
    lo, hi = spec.held_experts
    return {"loss": loss, "grad_norm": ref.group_norms(grads),
            "update_norm": update_norm, "expert_update_norm": by_expert,
            "load": np.asarray(load),
            "held_load": np.asarray(load)[:, lo:hi]}


def check(run):
    cfg = run.config
    trained = run.load_model(run.instance)
    start = run.load_model(run.train_again({"epochs": 0}))
    sessions, n_positions = first_batch(cfg, run.truth["sessions"])
    spec = ref.Spec.of(cfg["algorithm_params"], recompute=True)
    reference = reference_numbers(start.params, sessions, n_positions, spec)
    return compare(program_numbers(trained.record), reference,
                   trained.record,
                   groups_unmoved(start.params, trained.params),
                   cfg["limits"])


def shapes(run):
    """What the count functions read: the spec and the sizes of a train,
    and `session_positions`: the positions of every session the train's
    rows held (a train's multiset of session lengths, from the generated
    sessions by the record's `sessions`), which counts/packed_*.py and
    counts/seqrec_packed_model.py count the pairs inside sessions from."""
    ap = run.config["algorithm_params"]
    model = run.load_model(run.instance)
    record = model.record
    steps = len(record["loss"])
    spans = [min(len(s), ap["max_len"] + 1) - 1
             for s in program_order(run.truth["sessions"])]
    return {**ap, "n_vocab": int(model.params["emb"].shape[0]),
            "steps": steps,
            "tokens_per_step": len(record["rows"][0]) * model.hyper.max_len
            if steps else 0,
            "session_positions": [spans[i] for step in record["sessions"]
                                  for row in step for i in row]}

