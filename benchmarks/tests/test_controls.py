"""`correct` has been shown to fail: the reference computed one
precision step below what the configuration states (the control), put
in the program's place, comes out as not correct -- at a size a test can
hold; PERF.md has the same readings on the chip at the cells' own sizes.
And a run whose timed path is broken underneath comes out as not correct.
"""
import contextlib
import io
import json
import os

import numpy as np
import pytest

from benchmarks import run
from benchmarks.lib import datagen, manifest, reference

ROOT = manifest.ROOT


def limits_of(config: str, size: str = "chip") -> dict:
    """The configuration's limits; `tiny` takes the rehearsal's (a toy
    rank does not fit its ratings as rank 64 does, so the RMSE limit is
    the only one that differs)."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           config + ".json")) as f:
        cfg = json.load(f)
    return cfg["limits"] if size == "chip" else cfg["tiny"]["limits"]


@pytest.mark.parametrize("seed", [11, 12, 2**31 + 13])
def test_scoring_control_is_not_correct(seed):
    """At the chip's limits: exact answers pass, answers from operands
    rounded to bfloat16 (what the TPU's default float32 matmul does)
    pass, the int8 control does not."""
    limits = limits_of("rec-msd-r128")
    gen = datagen.factors(4000, 20_000, 128, seed)
    users = datagen.query_users(4000, 1.0, 128, seed)
    u = gen["U"][users]
    for precision, want in (("float32", True), ("bfloat16", True),
                            ("int8", False)):
        idx, scores = reference.control_topk(u, gen["V"], 10, precision)
        rows = reference.compare_topk(u, gen["V"], idx, scores, 10, limits)
        assert all(r[3] for r in rows) is want, (precision, rows)


def test_scoring_comparison_catches_an_altered_answer():
    limits = limits_of("rec-msd-r128")
    gen = datagen.factors(500, 3000, 32, 4)
    u = gen["U"][:16]
    idx, scores = reference.topk_exact(u, gen["V"], 10)
    bad = idx.copy()
    bad[3, 9] = (bad[3, 9] + 1500) % 3000          # one wrong item
    rows = {r[0]: r for r in reference.compare_topk(
        u, gen["V"], bad, scores, 10, limits)}
    assert not rows["topk_rank_gap"][3]
    short = [row[:9] for row in idx.tolist()]       # a row one item short
    rows = {r[0]: r for r in reference.compare_topk(
        u, gen["V"], short, [s[:9] for s in scores.tolist()], 10, limits)}
    assert rows["topk_short_rows"][1] == 16 and not rows["topk_short_rows"][3]


def _als_numpy(users, items, ratings, n_users, n_items, k, iters, reg, seed):
    """A plain float64 ALS-WR, the order of models/als.py: users, then
    items, each iteration."""
    rng = np.random.default_rng(seed)
    V = rng.standard_normal((n_items, k)) / np.sqrt(k)
    U = np.zeros((n_users, k))

    def side(opposite, seg, tgt, n):
        out = np.zeros((n, k))
        for s in range(n):
            rows = np.nonzero(seg == s)[0]
            F = opposite[tgt[rows]]
            A = F.T @ F + reg * max(len(rows), 1) * np.eye(k)
            out[s] = np.linalg.solve(A, F.T @ ratings[rows])
        return out

    for _ in range(iters):
        V_prev = V
        U = side(V, users, items, n_users)
        V = side(U, items, users, n_items)
    return U, V, V_prev


@pytest.mark.parametrize("seed", [21, 22, 23])
def test_train_control_is_not_correct(seed):
    limits = limits_of("rec-ml20m-r64", "tiny")
    assert {k: v for k, v in limits.items() if "residual" in k} == {
        k: v for k, v in limits_of("rec-ml20m-r64").items() if "residual" in k}
    users, items, ratings = datagen.rating_events(300, 200, 6000, seed)
    U, V, V_prev = (x.astype(np.float32) for x in _als_numpy(
        users, items, ratings, 300, 200, 8, 6, 0.01, seed))
    args = (users, items, ratings, 0.01, seed, limits)
    rows = reference.compare_train(U, V, V_prev, *args)
    assert all(r[3] for r in rows), rows
    # the control: both last half-sweeps from operands rounded to int8
    u_ctl, v_ctl = reference.control_train(U, V_prev, users, items, ratings,
                                           0.01, seed, 200, "int8")
    for swap in ({"U_rows": u_ctl}, {"V_rows": v_ctl}):
        side = "user" if "U_rows" in swap else "item"
        rows = {r[0]: r for r in reference.compare_train(
            U, V, V_prev, *args, **swap)}
        assert not rows[f"als_{side}_residual_p90"][3], rows
    # a step that returns its state unchanged: the seeded start
    rng = np.random.default_rng(seed)
    V0 = (rng.standard_normal((200, 8)) / np.sqrt(8)).astype(np.float32)
    rows = {r[0]: r for r in reference.compare_train(
        np.zeros_like(U), V0, V0, *args)}
    assert not rows["als_train_rmse"][3]
    assert not rows["als_item_residual_max"][3]
    assert not rows["als_user_residual_max"][3]
    # a user half-sweep that skips one sampled user
    U_skip = U.copy()
    U_skip[reference.train_sample(300, seed, "user")[5]] = 0.0
    rows = {r[0]: r for r in reference.compare_train(U_skip, V, V_prev, *args)}
    assert not rows["als_user_residual_max"][3]
    assert rows["als_user_residual_p90"][3]
    # a rating the ingest dropped: the program solved without it
    keep = np.ones(len(ratings), bool)
    keep[np.nonzero(users == reference.train_sample(300, seed, "user")[9])[0][0]] = False
    Ud, Vd, Vd_prev = (x.astype(np.float32) for x in _als_numpy(
        users[keep], items[keep], ratings[keep], 300, 200, 8, 6, 0.01, seed))
    rows = {r[0]: r for r in reference.compare_train(Ud, Vd, Vd_prev, *args)}
    assert not rows["als_user_residual_max"][3], rows


BREAKS = {
    # a train step that returns its state unchanged
    "state_unchanged": """
import numpy as np
import predictionio_tpu.engines.recommendation as rec
def unchanged(mesh, data, params, checkpointer=None):
    rng = np.random.default_rng(params.seed)
    V = rng.standard_normal((data.n_items, params.rank)) / np.sqrt(params.rank)
    return np.zeros((data.n_users, params.rank), np.float32), V.astype(np.float32)
rec.train_als = unchanged
""",
    # a user half-sweep that leaves out a part of the batch
    "users_left_out": """
import numpy as np
import predictionio_tpu.engines.recommendation as rec
sound = rec.train_als
def partial(mesh, data, params, checkpointer=None):
    U, V = sound(mesh, data, params, checkpointer)
    U = np.array(U)
    U[: len(U) // 8] = 0.0
    return U, V
rec.train_als = partial
""",
}


def last_line(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", sorted(BREAKS))
def test_a_broken_timed_path_comes_out_not_correct(fault, tmp_path,
                                                   monkeypatch):
    """The whole harness but the look for a chip (--tiny), with the path
    the window drives broken underneath it."""
    breaker = tmp_path / "bench_break.py"
    breaker.write_text(BREAKS[fault])
    sound = run.child_argv

    def broken(spec):
        argv = sound(spec)
        code = (f"import sys; sys.path.insert(0, {str(tmp_path)!r}); "
                f"sys.path.insert(0, {ROOT!r}); import bench_break; "
                "import benchmarks.child as c; "
                "sys.exit(c.main(['child', sys.argv[1]]))")
        return [argv[0], "-c", code, argv[2]]

    monkeypatch.setattr(run, "child_argv", broken)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = run.main(["--workload", "ml20m-r64.train", "--seed", "77",
                       "--seconds", "1", "--tiny"])
    assert rc == 0
    line = last_line(buf.getvalue())
    assert line["correct"] is False
    assert "CHECK" in buf.getvalue() and "NOT OK" in buf.getvalue()
