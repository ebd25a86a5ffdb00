"""The check of an explicit-ALS train (`recommendation` template) over
events/ratings.py: the release's last two half-sweeps against their
float64 closed forms (lib/reference.compare_train, which says what each
number is held against).

A train of n iterations ends with U_n = user half-sweep of V_(n-1) and
V_n = item half-sweep of U_n. The release shows U_n and V_n; the same
train (same events, deterministic) run for n-1 iterations shows V_(n-1).
"""

from __future__ import annotations

import numpy as np

from benchmarks.lib import reference


def _factors(model):
    """(U, V) of a released ALSModel, rows in numeric id order (the
    program sorts its vocabularies of "1".."n" as strings)."""
    def order(vocab):
        return np.argsort(np.asarray(vocab).astype(np.int64), kind="stable")
    return model.U[order(model.user_vocab)], model.V[order(model.item_vocab)]


def check(run):
    cfg, truth = run.config, run.truth
    U, V = _factors(run.load_model(run.instance))
    shorter = run.train_again(
        {"num_iterations": cfg["algorithm_params"]["num_iterations"] - 1})
    _, V_prev = _factors(run.load_model(shorter))
    return reference.compare_train(
        U, V, V_prev, truth["users"], truth["items"], truth["ratings"],
        cfg["reg"], run.seed, cfg["limits"])


def shapes(run):
    model = run.load_model(run.instance)
    return {"n_users": int(model.U.shape[0]), "n_items": int(model.V.shape[0]),
            "rank": int(model.U.shape[1]),
            "num_iterations": run.config["algorithm_params"]["num_iterations"]}
