#!/usr/bin/env python3
"""Readings the output check's limits are set from (PERF.md section 2):
over a dozen seeds, in one process on the chip, the numbers the sound
program gives and the numbers the lower-precision controls give (the
reference, one precision step down, put in the program's place), at the
cells' own sizes. The program side runs the engine's own train
(`ALSAlgorithm.train`) and the model's own scorer (`ALSModel._score_topk`),
the same compiled programs the cells' windows drive. A development tool:
no cell runs it.

    python3 benchmarks/tools/limits_probe.py train|score <config> <seed>...
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "tpu")

import numpy as np  # noqa: E402

from benchmarks.lib import datagen, manifest, reference  # noqa: E402

BIG = {k: 1e9 for k in (
    "topk_score_rms", "topk_rank_gap", "als_user_residual_p90",
    "als_user_residual_max", "als_item_residual_p90",
    "als_item_residual_max", "als_train_rmse")}


def out(doc):
    print("LIMITS " + json.dumps(doc), flush=True)


def numbers(rows):
    return {r[0]: r[1] for r in rows}


def train(cfg, seeds):
    """Per seed: the program's train of n and of n-1 iterations (the
    second shows the item factors the last user half-sweep read), the
    five numbers compare_train holds, the same with both last
    half-sweeps replaced by the int8 / bfloat16 closed forms (the
    controls), with the state unchanged, and with one user left out."""
    from predictionio_tpu.engines.recommendation import (
        ALSAlgorithm, AlgorithmParams, PreparedData, RatingColumns,
    )
    from predictionio_tpu.utils.device import enable_compile_cache
    from predictionio_tpu.workflow.context import WorkflowContext

    enable_compile_cache()
    ctx = WorkflowContext.create(mode="Training", batch="")
    algo = cfg["algorithm_params"]
    n = algo["num_iterations"]
    algos = [ALSAlgorithm(AlgorithmParams(rank=algo["rank"], num_iterations=it,
                                          reg=cfg["reg"]))
             for it in (n, n - 1)]
    for seed in seeds:
        users, items, ratings = datagen.rating_events(
            cfg["n_users"], cfg["n_items"], cfg["n_events"], seed,
            cfg.get("structure_seed", 0))
        data = PreparedData(ratings=None, columns=RatingColumns(
            users=(users + 1).astype(str), items=(items + 1).astype(str),
            values=ratings.astype(np.float32)))
        factors, walls = [], []
        for algo in algos:
            t0 = time.perf_counter()
            model = algo.train(ctx, data)
            walls.append(time.perf_counter() - t0)
            factors.append(
                (model.U[np.argsort(model.user_vocab.astype(np.int64))],
                 model.V[np.argsort(model.item_vocab.astype(np.int64))]))
        (U, V), (_, V_prev) = factors
        args = (users, items, ratings, cfg["reg"], seed, BIG)
        doc = {"mode": "train", "seed": seed, "algo_train_wall_s": walls,
               "sound": numbers(reference.compare_train(U, V, V_prev, *args))}
        for precision in ("bfloat16", "int8"):
            u_rows, v_rows = reference.control_train(
                U, V_prev, users, items, ratings, cfg["reg"], seed,
                V.shape[0], precision)
            doc["control_" + precision] = numbers(reference.compare_train(
                U, V, V_prev, *args, U_rows=u_rows, V_rows=v_rows))
        rng = np.random.default_rng(seed)
        V0 = (rng.standard_normal(V.shape) / np.sqrt(V.shape[1])).astype(np.float32)
        doc["state_unchanged"] = numbers(reference.compare_train(
            np.zeros_like(U), V0, V0, *args))
        # the user half-sweep skips one sampled user (its row keeps the
        # factors of the iteration before: here, of the shorter train)
        U_skip = U.copy()
        u = reference.train_sample(U.shape[0], seed, "user")[7]
        U_skip[u] = factors[1][0][u]
        doc["one_user_left_out"] = numbers(reference.compare_train(
            U_skip, V, V_prev, *args))
        out(doc)


def score(cfg, seeds, rows=256, num=10):
    from predictionio_tpu.models.als import ALSModel
    from predictionio_tpu.utils.device import enable_compile_cache

    enable_compile_cache()
    ids_u = datagen.entity_ids(cfg["n_users"], "u")
    ids_i = datagen.entity_ids(cfg["n_items"], "i")
    for seed in seeds:
        gen = datagen.factors(cfg["n_users"], cfg["n_items"],
                              cfg["algorithm_params"]["rank"], seed)
        model = ALSModel(user_vocab=ids_u, item_vocab=ids_i, U=gen["U"], V=gen["V"])
        users = datagen.query_users(cfg["n_users"], 1.0, rows, seed)
        _, scores, idx, _ = model._score_topk(
            [(ids_u[u], num, (), None) for u in users.tolist()])
        u_rows = gen["U"][users]
        doc = {"mode": "score", "seed": seed, "config": cfg["name"],
               "host_lane": bool(model._use_host(rows, False))}
        for name, (i, s) in {
                "sound": (idx, scores),
                "control_bfloat16": reference.control_topk(u_rows, gen["V"], num, "bfloat16"),
                "control_int8": reference.control_topk(u_rows, gen["V"], num, "int8")}.items():
            r = reference.compare_topk(u_rows, gen["V"], np.asarray(i).tolist(),
                                       np.asarray(s).tolist(), num, BIG)
            doc[name] = {r[0][0]: r[0][1], r[1][0]: r[1][1], r[2][0]: r[2][1]}
        out(doc)
        del model


if __name__ == "__main__":
    with open(os.path.join(manifest.BENCH_DIR, "configs",
                           sys.argv[2] + ".json")) as f:
        cfg = json.load(f)
    {"train": train, "score": score}[sys.argv[1]](cfg, [int(s) for s in sys.argv[3:]])
