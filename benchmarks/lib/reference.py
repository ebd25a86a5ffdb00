"""Plain references and the comparisons that decide `correct`.

numpy only, float64 where it matters, none of the program's ops. Each
comparison returns rows of (name, value, limit, ok): every number that
is compared is printed beside its limit in every run.

The controls put the reference, computed one precision step below what
the configuration states, in the program's place: they must come out as
not correct (tests/test_controls.py; PERF.md has the chip readings).
"""

from __future__ import annotations

from typing import Callable, Dict, List, Sequence, Tuple

import numpy as np

Row = Tuple[str, float, float, bool]


def _row(name: str, value: float, limit: float) -> Row:
    value = float(value)
    return (name, value, float(limit), bool(np.isfinite(value)
                                            and value <= limit))


# -- precision steps for the controls ---------------------------------------

def to_bf16(x: np.ndarray) -> np.ndarray:
    """Round f32 to bfloat16 (nearest even) and back, in numpy."""
    bits = np.ascontiguousarray(x, dtype=np.float32).view(np.uint32)
    rounded = bits + np.uint32(0x7FFF) + ((bits >> np.uint32(16))
                                          & np.uint32(1))
    return (rounded & np.uint32(0xFFFF0000)).view(np.float32)


def to_int8(x: np.ndarray) -> np.ndarray:
    """Symmetric per-row int8 quantisation and back."""
    scale = np.max(np.abs(x), axis=1, keepdims=True) / 127.0
    scale = np.where(scale > 0, scale, 1.0)
    return (np.round(x / scale) * scale).astype(np.float32)


PRECISIONS: Dict[str, Callable[[np.ndarray], np.ndarray]] = {
    "float32": lambda x: np.asarray(x, np.float32),
    "bfloat16": to_bf16,
    "int8": to_int8,
}


# -- scoring (serving and batchpredict) --------------------------------------

def topk_exact(u_rows: np.ndarray, V: np.ndarray, num: int
               ) -> Tuple[np.ndarray, np.ndarray]:
    """Reference answers: full f64 scores and a full sort."""
    scores = u_rows.astype(np.float64) @ V.astype(np.float64).T
    idx = np.argsort(-scores, axis=1, kind="stable")[:, :num]
    return idx, np.take_along_axis(scores, idx, axis=1)


def control_topk(u_rows: np.ndarray, V: np.ndarray, num: int,
                 precision: str) -> Tuple[np.ndarray, np.ndarray]:
    """The reference in a lower precision: what a scorer that rounds its
    operands to `precision` would serve."""
    q = PRECISIONS[precision]
    return topk_exact(q(u_rows), q(V), num)


def compare_topk(u_rows: np.ndarray, V: np.ndarray,
                 served_idx: Sequence[Sequence[int]],
                 served_scores: Sequence[Sequence[float]],
                 num: int, limits: Dict[str, float],
                 block: int = 64) -> List[Row]:
    """Hold served top-`num` lists to the f64 reference.

    topk_score_rms: root mean square, over every served (row, item), of
      (served score - reference score of that item) / (||u|| ||v_item||),
      the natural scale of a rounding error. The number the arithmetic's
      precision moves; steady from seed to seed where the widest single
      error is not.
    topk_rank_gap: widest gap by which a served item's reference score
      lies below the reference's num-th best, on the same scale: 0 when
      every served item belongs to the true top-`num`, small for a
      near-tie swapped within the arithmetic's error, large for an item
      that does not belong. Held against an altered answer, not against
      precision.
    topk_short_rows: rows that did not carry `num` distinct known items.
    """
    V64 = V.astype(np.float64)
    v_norm = np.linalg.norm(V64, axis=1)
    sq_sum, n_scores, gap = 0.0, 0, 0.0
    short = 0
    for lo in range(0, len(u_rows), block):
        u = u_rows[lo:lo + block].astype(np.float64)
        ref = u @ V64.T
        kth = np.partition(ref, -num, axis=1)[:, -num]
        u_norm = np.linalg.norm(u, axis=1)
        for r in range(len(u)):
            idx = np.asarray(served_idx[lo + r], dtype=np.int64)
            sc = np.asarray(served_scores[lo + r], dtype=np.float64)
            if len(idx) != num or len(set(idx.tolist())) != num \
                    or (idx < 0).any() or (idx >= len(V)).any():
                short += 1
                continue
            scale = u_norm[r] * v_norm[idx]
            true = ref[r, idx]
            sq_sum += float(np.sum(((sc - true) / scale) ** 2))
            n_scores += num
            gap = max(gap, float(np.max(np.maximum(kth[r] - true, 0.0)
                                        / scale)))
    rms = float(np.sqrt(sq_sum / n_scores)) if n_scores else float("inf")
    return [_row("topk_score_rms", rms, limits["topk_score_rms"]),
            _row("topk_rank_gap", gap, limits["topk_rank_gap"]),
            _row("topk_short_rows", short, 0)]


# -- explicit ALS (training) -------------------------------------------------
#
# models/als.py runs, in each iteration, the USER half-sweep from the item
# factors and then the ITEM half-sweep from the new user factors (ALS-WR):
#   (sum_j F_j F_j^T + reg * n_s * I) x_s = sum_j r_j F_j
# over the ratings j of segment s (a user or an item), F the opposite
# side's factors. A train of n iterations therefore ends with
#   U_n = user half-sweep of V_(n-1),   V_n = item half-sweep of U_n,
# and both last solves are closed forms of things the run can show: the
# release holds U_n and V_n, and the same train run for n-1 iterations
# (the program is deterministic) shows V_(n-1).

def _rows_of(seg: np.ndarray, sample: np.ndarray):
    """For each sampled segment, the indices of its ratings."""
    order = np.argsort(seg, kind="stable")
    sorted_seg = seg[order]
    lo = np.searchsorted(sorted_seg, sample, side="left")
    hi = np.searchsorted(sorted_seg, sample, side="right")
    return [order[a:b] for a, b in zip(lo, hi)]


def half_sweep(opposite: np.ndarray, seg: np.ndarray, tgt: np.ndarray,
               ratings: np.ndarray, sample: np.ndarray, reg: float,
               precision: str = "float64") -> np.ndarray:
    """The closed form of one half-sweep for the sampled segments, from
    float64 normal equations; `precision` rounds the operands (factors
    and ratings) first: the control."""
    if precision != "float64":
        opposite = PRECISIONS[precision](np.asarray(opposite, np.float32))
    F = opposite.astype(np.float64)
    k = F.shape[1]
    out = np.zeros((len(sample), k))
    for j, rows in enumerate(_rows_of(seg, sample)):
        Fj = F[tgt[rows]]
        r = ratings[rows].astype(np.float64)
        if precision != "float64":
            r = PRECISIONS[precision](r[None, :].astype(np.float32))[0] \
                .astype(np.float64)
        A = Fj.T @ Fj + reg * max(len(rows), 1) * np.eye(k)
        out[j] = np.linalg.solve(A, Fj.T @ r)
    return out


def residuals(opposite: np.ndarray, solved_rows: np.ndarray,
              seg: np.ndarray, tgt: np.ndarray, ratings: np.ndarray,
              sample: np.ndarray, reg: float) -> np.ndarray:
    """||A_s x_s - b_s|| / ||b_s|| per sampled segment: the float64 normal
    equations of a half-sweep, built from the `opposite` factors it read,
    at the rows `solved_rows` it wrote."""
    F = opposite.astype(np.float64)
    k = F.shape[1]
    out = np.zeros(len(sample))
    for j, rows in enumerate(_rows_of(seg, sample)):
        Fj = F[tgt[rows]]
        rhs = Fj.T @ ratings[rows].astype(np.float64)
        A = Fj.T @ Fj + reg * max(len(rows), 1) * np.eye(k)
        out[j] = np.linalg.norm(A @ solved_rows[j].astype(np.float64) - rhs) \
            / max(np.linalg.norm(rhs), 1e-30)
    return out


def train_sample(n: int, seed: int, side: str, size: int = 512) -> np.ndarray:
    rng = np.random.default_rng([seed, 0xA15, int(side == "user")])
    return np.sort(rng.choice(n, min(size, n), replace=False))


def compare_train(U: np.ndarray, V: np.ndarray, V_prev: np.ndarray,
                  users: np.ndarray, items: np.ndarray, ratings: np.ndarray,
                  reg: float, seed: int, limits: Dict[str, float],
                  n_ratings_sampled: int = 200_000,
                  U_rows=None, V_rows=None) -> List[Row]:
    """Hold a train's release (U, V), and the item factors V_prev the
    same train shows one iteration earlier, to the closed forms of its
    last two half-sweeps. Ratings are the generated ones, not the
    program's view of them, so a rating the ingest drops shows too.

    als_user_residual_p90 / _max: over 512 seeded users, the 90th
      percentile and the worst of ||A u - b|| / ||b||, the last USER
      half-sweep's normal equations (built from V_prev) at the release's
      user factors: the Pallas solve over all users.
    als_item_residual_p90 / _max: the same over 512 seeded items for the
      last ITEM half-sweep (built from U) at the release's item factors.
      The residual, not the distance to the float64 solution: at reg 0.01
      the systems are ill-conditioned and a sound float32 solve sits 7%
      off the float64 solution in the median item (my chip run, PR 23),
      more than int8 operands move it; the residual does not carry the
      conditioning and separates the two. The percentile is the number
      precision moves; the worst is held against a part of the batch
      left out or broken.
    als_train_rmse: RMSE of U V^T on a seeded sample of the ratings;
      held against a train that returns its state unchanged.

    U_rows / V_rows replace the sampled rows of U / V (the controls).
    """
    su = train_sample(U.shape[0], seed, "user")
    si = train_sample(V.shape[0], seed, "item")
    res_u = residuals(V_prev, U[su] if U_rows is None else U_rows,
                      users, items, ratings, su, reg)
    res_i = residuals(U, V[si] if V_rows is None else V_rows,
                      items, users, ratings, si, reg)
    rng = np.random.default_rng([seed, 0xA16])
    pick = rng.choice(len(ratings), min(n_ratings_sampled, len(ratings)),
                      replace=False)
    pred = np.einsum("nk,nk->n", U[users[pick]].astype(np.float64),
                     V[items[pick]].astype(np.float64))
    rmse = float(np.sqrt(np.mean((pred - ratings[pick]) ** 2)))
    rows = []
    for side, res in (("user", res_u), ("item", res_i)):
        rows.append(_row(f"als_{side}_residual_p90",
                         float(np.quantile(res, 0.9)),
                         limits[f"als_{side}_residual_p90"]))
        rows.append(_row(f"als_{side}_residual_max", float(np.max(res)),
                         limits[f"als_{side}_residual_max"]))
    rows.append(_row("als_train_rmse", rmse, limits["als_train_rmse"]))
    return rows


def control_train(U, V_prev, users, items, ratings, reg, seed, n_items,
                  precision):
    """(U_rows, V_rows): the sampled rows both last half-sweeps would
    have written with operands in `precision`, on the samples
    compare_train draws."""
    su = train_sample(U.shape[0], seed, "user")
    si = train_sample(n_items, seed, "item")
    return (half_sweep(V_prev, users, items, ratings, su, reg, precision),
            half_sweep(U, items, users, ratings, si, reg, precision))
