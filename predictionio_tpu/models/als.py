"""Blockwise ALS matrix factorization on a device mesh.

The TPU-native replacement for MLlib ALS (`ALS.run`/`ALS.trainImplicit`
invoked by the reference templates at examples/scala-parallel-recommendation/
customize-serving/src/main/scala/ALSAlgorithm.scala:51-85 and
examples/scala-parallel-similarproduct/.../ALSAlgorithm.scala:60). Design
follows the ALX pattern (PAPERS.md): users and items are sharded in
contiguous blocks over the mesh's "data" axis; each half-sweep gathers the
opposite (replicated) factor matrix, assembles per-segment normal equations
with sorted segment-sums, and solves them as one batched Cholesky on the MXU.

Where Spark ALS shuffles rating blocks between executors every sweep, here
the COO ratings are resident on device (sorted twice: by user and by item)
and the only cross-device traffic is the factor all-gather XLA inserts when
the sharded sweep output feeds the next sweep's replicated input — exactly
the collective-over-ICI layout SURVEY.md section 2.9 P3 prescribes.

Explicit feedback uses ALS-WR weighted-lambda regularization (MLlib's
scheme); implicit feedback implements Hu-Koren-Volinsky confidence weighting
(c = 1 + alpha * r) with the shared V^T V Gramian trick.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from predictionio_tpu.core.params import Params
from predictionio_tpu.data.bimap import vocab_index
from predictionio_tpu.obs import jax_stats, train_stats
from predictionio_tpu.obs.tracing import span
from predictionio_tpu.ops.bucketing import bucket_size, pad_rows as _pad_rows
from predictionio_tpu.ops.fn_cache import shape_cached_fn
from predictionio_tpu.ops.linalg import batched_spd_solve
from predictionio_tpu.ops.segment import (
    block_gram_rhs, row_predict_add, rows_gram_rhs, segment_count,
)
from predictionio_tpu.ops.topk import host_topk as _host_topk
from predictionio_tpu.utils.device import bytes_in_use

logger = logging.getLogger("pio.als")

#: selectable training solvers: "full" = one K x K normal-equations solve
#: per row per half-sweep (the classic ALS step); "subspace" = iALS++
#: block coordinate descent over rank blocks (arXiv:2110.14044)
SOLVERS = ("full", "subspace")


@dataclasses.dataclass
class ALSParams(Params):
    """Hyperparameters (template ALSAlgorithmParams parity: rank,
    numIterations, lambda, seed; implicit adds alpha)."""

    rank: int = 10
    num_iterations: int = 10
    reg: float = 0.01
    alpha: float = 1.0
    implicit_prefs: bool = False
    weighted_reg: bool = True   # ALS-WR: lambda scaled by per-entity count
    seed: int = 3
    #: rows per lax.scan chunk — bounds the gather/matmul buffer (the padded
    #: row length itself is a data-layout knob on ALSData.build)
    chunk_size: int = 8192
    #: "full" (per-row K x K solve) or "subspace" (iALS++ block coordinate
    #: descent: per outer iteration sweep rank blocks of `block_size`,
    #: solving b x b systems against the frozen remainder — O(r * b^2)
    #: per row instead of O(r^3), the win compounding as rank grows)
    solver: str = "full"
    #: rank-block width of the subspace solver (ignored by "full")
    block_size: int = 16


def validate_solver(params: "ALSParams") -> None:
    """Loud failure on a typo'd solver config — a silent fallback would
    fake the full path's perf numbers under a subspace label (or vice
    versa)."""
    if params.solver not in SOLVERS:
        raise ValueError(
            f"unknown ALS solver {params.solver!r}: expected one of "
            f"{'|'.join(SOLVERS)}")
    if params.solver == "subspace" and params.block_size < 1:
        raise ValueError(
            f"block_size must be >= 1, got {params.block_size}")


def block_starts(rank: int, block_size: int) -> Tuple[int, ...]:
    """Static start offsets of the rank blocks one subspace sweep solves.

    Blocks are `block_size` wide; when rank is not divisible the LAST
    block is shifted left to end at `rank` (so it overlaps its
    predecessor instead of shrinking — every block keeps one static b x b
    shape, and re-solving the overlap columns is still exact coordinate
    descent). rank <= block_size degrades to one block == the full solve.
    """
    b = max(1, min(block_size, rank))
    return tuple(sorted({min(s, rank - b) for s in range(0, rank, b)}))


# ---------------------------------------------------------------------------
# Host-side data layout (ALX-style padded rows)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ShardedRows:
    """Ratings packed into padded per-segment rows, split across shards.

    Row r holds up to L ratings of ONE segment (heavy segments span several
    consecutive rows); shard s owns contiguous segments
    [s * seg_per_shard, (s+1) * seg_per_shard). This layout turns Gramian
    assembly into batched [L, K] matmuls on the MXU with one small combine
    scatter per row — the ALX layout (PAPERS.md) — instead of per-rating
    scatter-adds.
    """

    tgt: np.ndarray   # int32 [D, R, L] — opposite-side factor rows
    val: np.ndarray   # float32 [D, R, L] — rating values
    w: np.ndarray     # float32 [D, R, L] — weights (0 = padding)
    seg: np.ndarray   # int32 [D, R] — LOCAL segment id of each row (sorted)
    seg_per_shard: int
    n_segments: int   # padded total (n_shards * seg_per_shard)
    row_len: int


def _auto_row_len(nnz: int, n_segments: int) -> int:
    mean = max(1.0, nnz / max(n_segments, 1))
    return int(min(512, max(16, 1 << int(np.ceil(np.log2(mean))))))


def _row_positions(seg_local: np.ndarray, row_len: int,
                   seg_per_shard: int):
    """Packing positions for sorted-by-segment ratings: (rrow, col,
    n_rows, row_seg), where element j lands at [rrow[j], col[j]] of an
    [n_rows, row_len] padded-row array. Shared by the training build and
    the eval sweep's auxiliary columns (fold ids packed into the SAME
    layout). n == 0 degrades to one all-padding row (rrow/col None)."""
    n = len(seg_local)
    if n == 0:
        return None, None, 1, np.full((1,), seg_per_shard - 1, np.int32)
    # the input is SORTED by segment (both callers sort first), so the
    # group structure falls out of one linear diff pass — np.unique would
    # re-sort 20M elements it already received in order
    new_seg = np.empty(n, bool)
    new_seg[0] = True
    np.not_equal(seg_local[1:], seg_local[:-1], out=new_seg[1:])
    first_idx = np.flatnonzero(new_seg)            # [U] group starts
    uniq = seg_local[first_idx]
    counts = np.diff(np.append(first_idx, n))
    rows_per = -(-counts // row_len)
    row_start = np.concatenate([[0], np.cumsum(rows_per)])
    inv = np.cumsum(new_seg) - 1                   # group id per element
    pos = np.arange(n) - first_idx[inv]
    rrow = row_start[inv] + pos // row_len
    col = pos % row_len
    n_rows = int(row_start[-1])
    row_seg = np.repeat(uniq, rows_per).astype(np.int32)
    return rrow, col, n_rows, row_seg


def _build_rows(seg_local: np.ndarray, tgt: np.ndarray, val: np.ndarray,
                weights: Optional[np.ndarray], row_len: int,
                seg_per_shard: int):
    """Pack one shard's (sorted-by-segment) ratings into padded rows."""
    rrow, col, n_rows, row_seg = _row_positions(seg_local, row_len,
                                                seg_per_shard)
    tgt_out = np.zeros((n_rows, row_len), np.int32)
    val_out = np.zeros((n_rows, row_len), np.float32)
    w_out = np.zeros((n_rows, row_len), np.float32)
    if rrow is not None:
        tgt_out[rrow, col] = tgt
        val_out[rrow, col] = val
        w_out[rrow, col] = weights if weights is not None else 1.0
    return tgt_out, val_out, w_out, row_seg


def _bucket_rows(r_max: int) -> int:
    """Bucket the padded row count so near-identical datasets (k-fold
    splits of one rating set differ by ~1/k rows) share ONE compiled
    program — without this an eval sweep pays folds x ranks separate XLA
    compiles, minutes on a TPU; padding rows carry w=0 and fold into the
    padding segment, so the math is unchanged. Single definition: the
    single-process and distributed builders MUST round identically or
    their programs stop sharing the jit cache."""
    return max(256, -(-r_max // 256) * 256)


def _stack_parts(per_shard, r_max: int, row_len: int, seg_per_shard: int):
    """Stack per-shard `_build_rows` outputs into the padded [S, R, L]
    (+[S, R] seg) arrays — shared by shard_rows and build_distributed."""
    n = len(per_shard)

    def _stack(idx, fill, dtype, shape_tail):
        out = np.full((n, r_max) + shape_tail, fill, dtype=dtype)
        for s, parts in enumerate(per_shard):
            a = parts[idx]
            out[s, :a.shape[0]] = a
        return out

    seg_out = np.full((n, r_max), seg_per_shard - 1, np.int32)
    for s, (_, _, _, rs) in enumerate(per_shard):
        seg_out[s, :rs.shape[0]] = rs
    return (_stack(0, 0, np.int32, (row_len,)),
            _stack(1, 0.0, np.float32, (row_len,)),
            _stack(2, 0.0, np.float32, (row_len,)),
            seg_out)


def shard_rows(seg_idx: np.ndarray, tgt_idx: np.ndarray, values: np.ndarray,
               n_segments: int, n_shards: int,
               weights: Optional[np.ndarray] = None,
               row_len: Optional[int] = None) -> ShardedRows:
    """Sort by segment, split at shard boundaries, pack into padded rows."""
    order = np.argsort(seg_idx, kind="stable")
    seg_s = seg_idx[order].astype(np.int64)
    tgt_s = tgt_idx[order].astype(np.int32)
    val_s = values[order].astype(np.float32)
    w_s = weights[order].astype(np.float32) if weights is not None else None
    nnz = len(seg_s)
    if row_len is None:
        row_len = _auto_row_len(nnz, n_segments)

    seg_per_shard = -(-max(n_segments, 1) // n_shards)
    bounds = np.searchsorted(
        seg_s, np.arange(1, n_shards) * seg_per_shard, side="left")
    starts = np.concatenate([[0], bounds, [nnz]]).astype(np.int64)

    per_shard = []
    for s in range(n_shards):
        lo, hi = int(starts[s]), int(starts[s + 1])
        per_shard.append(_build_rows(
            seg_s[lo:hi] - s * seg_per_shard, tgt_s[lo:hi], val_s[lo:hi],
            w_s[lo:hi] if w_s is not None else None, row_len, seg_per_shard))
    r_max = _bucket_rows(max(t.shape[0] for t, _, _, _ in per_shard))
    tgt, val, w, seg = _stack_parts(per_shard, r_max, row_len, seg_per_shard)
    return ShardedRows(
        tgt=tgt, val=val, w=w, seg=seg,
        seg_per_shard=seg_per_shard,
        n_segments=n_shards * seg_per_shard,
        row_len=row_len,
    )


@dataclasses.dataclass
class ALSData:
    """Device-ready training layout: padded rows sorted both ways + dims."""

    by_user: ShardedRows    # seg=user, tgt=item
    by_item: ShardedRows    # seg=item, tgt=user
    n_users: int
    n_items: int
    n_users_pad: int
    n_items_pad: int
    nnz: int
    #: digest of the PRE-shard COO triples — mesh-shape independent, so a
    #: checkpoint fingerprint built from it survives resuming on a
    #: different device count (the padded row layout does not)
    digest: str = ""

    @classmethod
    def build(cls, user_idx: np.ndarray, item_idx: np.ndarray,
              ratings: np.ndarray, n_users: int, n_items: int,
              n_shards: int, row_len: Optional[int] = None) -> "ALSData":
        with span("als_pack"):
            by_user = shard_rows(user_idx, item_idx, ratings, n_users,
                                 n_shards, row_len=row_len)
            by_item = shard_rows(item_idx, user_idx, ratings, n_items,
                                 n_shards, row_len=row_len)
            data = cls(by_user=by_user, by_item=by_item,
                       n_users=n_users, n_items=n_items,
                       n_users_pad=by_user.n_segments,
                       n_items_pad=by_item.n_segments,
                       nnz=int(len(ratings)),
                       digest=coo_digest(user_idx, item_idx, ratings))
        train_stats.observe_row_fill(data)
        return data

    def put(self, mesh: Mesh) -> "ALSData":
        """Commit the row arrays to the mesh ONCE (sharded over "data",
        matching the half-sweep in_specs), so repeated `train_als` calls —
        warm-up, timed runs, eval sweeps over hyperparams — reuse resident
        device buffers instead of re-uploading the whole rating set per
        call (~0.5 GB of padded rows at ML-20M scale).

        Multi-process (jax.distributed) runs assemble the global arrays
        from each process's local shard rows without gathering anywhere
        (SURVEY §2.9 P2 sharded input loading; the JdbcRDD-partition
        analog)."""
        row_sh = NamedSharding(mesh, P("data", None, None))
        seg_sh = NamedSharding(mesh, P("data", None))
        moving = [a for rows in (self.by_user, self.by_item) for a, sh in (
            (rows.tgt, row_sh), (rows.val, row_sh), (rows.w, row_sh),
            (rows.seg, seg_sh))
            if not (isinstance(a, jax.Array) and a.sharding == sh)]
        if not moving:
            return self             # already resident HERE (idempotent)
        with span("als_put"):
            out = self._put(mesh, row_sh, seg_sh)
        train_stats.als_put_bytes().inc(sum(a.nbytes for a in moving))
        return out

    def _put(self, mesh: Mesh, row_sh, seg_sh) -> "ALSData":
        """The transfer itself, through its block_until_ready."""
        multiproc = jax.process_count() > 1
        if multiproc:
            # the local-slice math below requires the standard layouts:
            # one shard row per mesh position, and each process's devices
            # occupying a CONTIGUOUS run of mesh.devices.flat (the order
            # jax.devices() yields on multi-host). Anything else would
            # silently mis-assemble training data — fail loudly instead.
            n_rows = self.by_user.tgt.shape[0]
            assert n_rows == mesh.devices.size, (
                f"data built for {n_rows} shards but mesh has "
                f"{mesh.devices.size} devices — build with "
                "n_shards=mesh.devices.size for multi-process put()")
            lo, hi = _process_shard_range(mesh)

        def commit_one(arr, sharding):
            if isinstance(arr, jax.Array):
                if arr.sharding == sharding:
                    return arr      # already resident HERE (idempotent)
                if multiproc:
                    raise ValueError(
                        "ALSData is resident on a different mesh; "
                        "re-putting across meshes is not supported in "
                        "multi-process runs")
                return jax.device_put(arr, sharding)   # reshard
            if not multiproc:
                return jax.device_put(arr, sharding)
            return jax.make_array_from_process_local_data(
                sharding, np.ascontiguousarray(arr[lo:hi]), arr.shape)

        def commit(rows: ShardedRows) -> ShardedRows:
            return dataclasses.replace(
                rows,
                tgt=commit_one(rows.tgt, row_sh),
                val=commit_one(rows.val, row_sh),
                w=commit_one(rows.w, row_sh),
                seg=commit_one(rows.seg, seg_sh))

        out = dataclasses.replace(self, by_user=commit(self.by_user),
                                  by_item=commit(self.by_item))
        jax.block_until_ready([
            out.by_user.tgt, out.by_user.val, out.by_user.w, out.by_user.seg,
            out.by_item.tgt, out.by_item.val, out.by_item.w, out.by_item.seg])
        return out


# ---------------------------------------------------------------------------
# Device sweeps
# ---------------------------------------------------------------------------

def _half_sweep_dyn(opposite: jax.Array, row_tgt, row_seg, row_val, row_w,
                    seg_per_shard: int, *, reg, alpha,
                    implicit_prefs: bool, weighted_reg: bool,
                    alpha_is_zero: bool, chunk_rows: int) -> jax.Array:
    """Solve this side's factors for one shard. opposite is the full
    (replicated) opposite-side factor matrix; rows are the padded ALX
    layout. ``reg``/``alpha`` may be python floats OR traced scalars —
    the device-batched eval sweep vmaps this body over a candidate axis
    of (reg, alpha) values, so only the program-SHAPING flags
    (implicit_prefs / weighted_reg / alpha_is_zero) are static."""
    if implicit_prefs:
        # Hu-Koren-Volinsky: preference p = [r > 0], confidence
        # c = 1 + alpha * |r| (negative r = confident dislike, the
        # similarproduct LikeAlgorithm convention).
        # A_s = V^T V + sum (c-1) f f^T + lam I ; b_s = sum c p f
        # One row pass: gram weights (c-1); rhs values c*p/(c-1) so that
        # value * weight = c * p exactly. alpha == 0 degenerates to c = 1
        # (unweighted implicit), where the gram correction vanishes and the
        # rhs is a plain preference sum — use a direct pass for that case.
        with jax.named_scope("als_gram"):
            gram_all = opposite.T @ opposite             # [K, K] MXU
            p = jnp.where(row_val > 0, 1.0, 0.0)
            if alpha_is_zero:
                gram, rhs, cnt = rows_gram_rhs(
                    opposite, row_tgt, row_seg, row_val=p, row_w=row_w,
                    num_segments=seg_per_shard, chunk_rows=chunk_rows)
                gram = jnp.zeros_like(gram)  # (c-1) = 0; keep only the rhs
            else:
                cm1 = alpha * jnp.abs(row_val)           # c - 1
                vals = jnp.where(
                    cm1 > 0, (1.0 + cm1) * p / jnp.maximum(cm1, 1e-12), 0.0)
                gram, rhs, _ = rows_gram_rhs(
                    opposite, row_tgt, row_seg,
                    row_val=vals, row_w=row_w * cm1,
                    num_segments=seg_per_shard, chunk_rows=chunk_rows)
                cnt = segment_count(row_seg, row_w.sum(axis=1),
                                    seg_per_shard)
        with jax.named_scope("als_reg"):
            A = gram_all[None, :, :] + gram
            lam = reg * jnp.where(weighted_reg, jnp.maximum(cnt, 1.0), 1.0)
            A = A + lam[:, None, None] * jnp.eye(opposite.shape[1],
                                                 dtype=A.dtype)
        with jax.named_scope("als_solve"):
            return batched_spd_solve(A, rhs)
    with jax.named_scope("als_gram"):
        gram, rhs, cnt = rows_gram_rhs(
            opposite, row_tgt, row_seg, row_val=row_val, row_w=row_w,
            num_segments=seg_per_shard, chunk_rows=chunk_rows)
    with jax.named_scope("als_reg"):
        lam = reg * jnp.where(weighted_reg, jnp.maximum(cnt, 1.0), 1.0)
        A = gram + lam[:, None, None] * jnp.eye(opposite.shape[1],
                                                dtype=gram.dtype)
    with jax.named_scope("als_solve"):
        return batched_spd_solve(A, rhs)


def _half_sweep(opposite: jax.Array, row_tgt, row_seg, row_val, row_w,
                seg_per_shard: int, params: ALSParams,
                chunk_rows: int) -> jax.Array:
    """Static-params wrapper over `_half_sweep_dyn` (the training path)."""
    return _half_sweep_dyn(
        opposite, row_tgt, row_seg, row_val, row_w, seg_per_shard,
        reg=params.reg, alpha=params.alpha,
        implicit_prefs=params.implicit_prefs,
        weighted_reg=params.weighted_reg,
        alpha_is_zero=(params.alpha == 0), chunk_rows=chunk_rows)


def _global_gram(opposite: jax.Array, axis: Optional[str],
                 n_shards: int) -> jax.Array:
    """The K x K Gramian of the full opposite factor matrix, computed ONCE
    per half-sweep (the implicit solver's V^T V term). On a mesh the
    contraction is SHARDED: each device reduces its slice of the
    (replicated) rows and one psum of the tiny [K, K] result combines —
    the ALX sharded-Gramian layout (arXiv:2112.02194)."""
    if axis is None or n_shards <= 1:
        return opposite.T @ opposite
    f, k = opposite.shape
    per = -(-f // n_shards)
    op = jnp.pad(opposite, ((0, per * n_shards - f), (0, 0)))
    i = jax.lax.axis_index(axis)
    sl = jax.lax.dynamic_slice(op, (i * per, 0), (per, k))
    return jax.lax.psum(sl.T @ sl, axis)


def _half_sweep_subspace_dyn(x_prev: jax.Array, opposite: jax.Array,
                             row_tgt, row_seg, row_val, row_w,
                             seg_per_shard: int, *, reg, alpha,
                             implicit_prefs: bool, weighted_reg: bool,
                             alpha_is_zero: bool, chunk_rows: int,
                             block_size: int, axis: Optional[str] = None,
                             mesh_shards: int = 1) -> jax.Array:
    """Block coordinate descent half-sweep (iALS++, arXiv:2110.14044).

    Instead of one K x K normal-equations solve per row, sweep rank
    blocks of width b: for each block, solve every row's b x b system
    against the frozen remainder of its own factors (``x_prev``, updated
    block by block), with the per-rating predictions maintained
    incrementally. Per-half-sweep cost drops from
    ``nnz*K^2 + S*K^3`` to ``nnz*K*b + S*K*b^2`` — and the batched
    Cholesky shrinks from [S, K, K] (whose K-step recurrence rewrites
    the whole buffer every step, the HBM-bandwidth wall at K >= 64) to
    [S, b, b].

    Cached once per half-sweep and reused by every block solve: the
    per-segment weight counts (the ALS-WR lambda scaling) and, for
    implicit feedback, the global Gramian V^T V (sharded over the mesh
    via `_global_gram`) whose b-column slices feed each block. ``reg`` /
    ``alpha`` may be traced (the eval sweep vmaps them); only
    block_size and the mode flags shape the program.
    """
    k = opposite.shape[1]
    b = max(1, min(block_size, k))
    starts = block_starts(k, block_size)
    # block buffers are [C, L, b] vs the full path's [C, L, K]: larger
    # chunks for the same memory budget -> fewer scan steps
    chunk_b = chunk_rows * max(1, k // b)

    # ---- per-half-sweep cache: built once, reused by every block solve
    with jax.named_scope("als_reg"):
        cnt = segment_count(row_seg, row_w.sum(axis=1), seg_per_shard)
        lam = reg * jnp.where(weighted_reg, jnp.maximum(cnt, 1.0), 1.0)
    if implicit_prefs:
        with jax.named_scope("als_gram"):
            gram_all = _global_gram(opposite, axis, mesh_shards)  # [K, K]
        p = jnp.where(row_val > 0, 1.0, 0.0)
        if alpha_is_zero:
            # c = 1 everywhere: the per-rating Gramian term vanishes
            gram_w = jnp.zeros_like(row_w)
            rhs_val = row_w * p
        else:
            cm1 = alpha * jnp.abs(row_val)                     # c - 1
            gram_w = row_w * cm1
            rhs_val = row_w * (1.0 + cm1) * p
    else:
        gram_all = None
        gram_w = row_w
        rhs_val = row_w * row_val

    pred = row_predict_add(
        opposite, x_prev, row_tgt, row_seg,
        jnp.zeros_like(row_val), chunk_rows=chunk_rows)
    eye_b = jnp.eye(b, dtype=opposite.dtype)

    x = x_prev
    for j, s in enumerate(starts):
        f_b = jax.lax.slice_in_dim(opposite, s, s + b, axis=1)
        x_b = jax.lax.slice_in_dim(x, s, s + b, axis=1)
        with jax.named_scope("als_gram"):
            gram, rhs = block_gram_rhs(
                f_b, x_b, row_tgt, row_seg, pred, rhs_val, gram_w,
                num_segments=seg_per_shard, chunk_rows=chunk_b)
            if implicit_prefs:
                # dense all-items term from the CACHED global Gramian:
                # A += G[B,B]; rhs -= (x G)[:,B] - x_B G[B,B]
                g_col = jax.lax.slice_in_dim(gram_all, s, s + b, axis=1)
                g_bb = jax.lax.slice_in_dim(g_col, s, s + b, axis=0)
                gram = gram + g_bb[None, :, :]
                rhs = rhs - (x @ g_col - x_b @ g_bb)
        with jax.named_scope("als_reg"):
            A = gram + lam[:, None, None] * eye_b
        with jax.named_scope("als_solve"):
            y = batched_spd_solve(A, rhs)
        if j + 1 < len(starts):
            # fold this block's delta into the running predictions (the
            # LAST block's update feeds nothing, so skip its pass)
            pred = row_predict_add(f_b, y - x_b, row_tgt, row_seg, pred,
                                   chunk_rows=chunk_b)
        x = jax.lax.dynamic_update_slice_in_dim(x, y, s, axis=1)
    return x


def _make_sweeps(mesh: Mesh, data_dims, params: ALSParams):
    """Build the shard_map'd user/item half-sweeps for the given mesh.

    The full solver's sweeps take (opposite, rows...); the subspace
    solver's additionally take this side's PREVIOUS factors — sharded
    like the output, since block coordinate descent updates rank blocks
    of each shard's own rows against the frozen remainder."""
    from jax import shard_map

    validate_solver(params)
    n_users_pad, n_items_pad, ups, ips = data_dims[:4]
    axis = "data"
    chunk = params.chunk_size
    n_shards = int(mesh.devices.size)

    # check_vma=False: the generic row kernel mixes replicated factor
    # inputs with device-varying row chunks inside lax.scan; correctness is
    # covered by the single-vs-8-device equivalence test
    row_spec = P(axis, None, None)
    seg_spec = P(axis, None)

    if params.solver == "subspace":
        def sub_kwargs():
            return dict(
                reg=params.reg, alpha=params.alpha,
                implicit_prefs=params.implicit_prefs,
                weighted_reg=params.weighted_reg,
                alpha_is_zero=(params.alpha == 0), chunk_rows=chunk,
                block_size=params.block_size, axis=axis,
                mesh_shards=n_shards)

        def user_block(Up, V, tgt, seg, val, w):
            return _half_sweep_subspace_dyn(
                Up[0], V, tgt[0], seg[0], val[0], w[0], ups,
                **sub_kwargs())[None]

        def item_block(Vp, U, tgt, seg, val, w):
            return _half_sweep_subspace_dyn(
                Vp[0], U, tgt[0], seg[0], val[0], w[0], ips,
                **sub_kwargs())[None]

        specs = (P(axis, None, None), P(), row_spec, seg_spec,
                 row_spec, row_spec)
        user_sweep = shard_map(
            user_block, mesh=mesh, in_specs=specs,
            out_specs=P(axis, None, None), check_vma=False)
        item_sweep = shard_map(
            item_block, mesh=mesh, in_specs=specs,
            out_specs=P(axis, None, None), check_vma=False)
        return user_sweep, item_sweep

    def user_block(V, tgt, seg, val, w):
        # one shard: [1, R, L] row blocks -> local users [ups, K]
        return _half_sweep(V, tgt[0], seg[0], val[0], w[0], ups, params, chunk)[None]

    def item_block(U, tgt, seg, val, w):
        return _half_sweep(U, tgt[0], seg[0], val[0], w[0], ips, params, chunk)[None]

    user_sweep = shard_map(
        user_block, mesh=mesh,
        in_specs=(P(), row_spec, seg_spec, row_spec, row_spec),
        out_specs=P(axis, None, None), check_vma=False)
    item_sweep = shard_map(
        item_block, mesh=mesh,
        in_specs=(P(), row_spec, seg_spec, row_spec, row_spec),
        out_specs=P(axis, None, None), check_vma=False)
    return user_sweep, item_sweep


def _make_chunk_core(mesh: Mesh, data_dims, params: ALSParams, iters: int):
    """Shared iteration body: (by_user, by_item, V) -> (U, V) after `iters`
    alternating sweeps. Both the straight and the checkpointed paths run
    exactly this, so they cannot drift apart."""
    n_users_pad, n_items_pad, ups, ips = data_dims[:4]
    k = params.rank
    n_shards = n_users_pad // ups
    user_sweep, item_sweep = _make_sweeps(mesh, data_dims, params)
    subspace = params.solver == "subspace"

    def chunk(by_user, by_item, U, V):
        # U rides the chunk boundary: the full solver's first user sweep
        # overwrites it (so a zero U is merely conventional there), but
        # the subspace solver REFINES it — dropping it between
        # checkpointing chunks would cold-restart block descent per chunk
        # and make results depend on checkpointer.interval
        u_tgt, u_seg, u_val, u_w = by_user
        i_tgt, i_seg, i_val, i_w = by_item

        def body(_, carry):
            U, V = carry
            if subspace:
                # block coordinate descent refines each side's factors in
                # place: the previous values flow in sharded alongside
                # the (replicated) opposite side
                U = user_sweep(U.reshape(n_shards, ups, k), V,
                               u_tgt, u_seg, u_val, u_w
                               ).reshape(n_users_pad, k)
                V = item_sweep(V.reshape(n_shards, ips, k), U,
                               i_tgt, i_seg, i_val, i_w
                               ).reshape(n_items_pad, k)
            else:
                U = user_sweep(V, u_tgt, u_seg, u_val, u_w
                               ).reshape(n_users_pad, k)
                V = item_sweep(U, i_tgt, i_seg, i_val, i_w
                               ).reshape(n_items_pad, k)
            return (U, V)

        return jax.lax.fori_loop(0, iters, body, (U, V))

    return chunk


def make_train_fn(mesh: Mesh, data_dims, params: ALSParams):
    """Build the jitted full training function for the given mesh.

    Returns train(by_user_arrays, by_item_arrays, key) -> (U, V), where the
    per-shard COO arrays are sharded over the mesh's "data" axis and the
    factor matrices flow replicated-in / sharded-out; XLA inserts the
    all-gather between half-sweeps (collectives over ICI).
    """
    n_users_pad, n_items_pad, _, _, n_items = data_dims
    k = params.rank
    chunk = _make_chunk_core(mesh, data_dims, params, params.num_iterations)

    def train(by_user, by_item, key):
        with jax.named_scope("als_init"):
            V = (jax.random.normal(key, (n_items_pad, k), jnp.float32)
                 / jnp.sqrt(jnp.asarray(k, jnp.float32)))
            # padding item rows start (and stay) zero: random pad rows
            # would pollute the implicit solvers' global V^T V Gramian —
            # the full sweep zeroes them exactly on its first item solve,
            # but block coordinate descent only decays them, and
            # snapshot/resume truncates at n_items, so nonzero pads would
            # make a resumed run diverge from the uninterrupted one
            V = jnp.where((jnp.arange(n_items_pad) < n_items)[:, None], V,
                          0.0)
            U0 = jnp.zeros((n_users_pad, k), jnp.float32)
        return chunk(by_user, by_item, U0, V)

    return jax.jit(train)


def make_chunk_fn(mesh: Mesh, data_dims, params: ALSParams, iters: int):
    """Like make_train_fn but runs `iters` iterations from a given
    (U, V) — the unit of mid-training checkpointing (train_als drives
    the outer loop, snapshotting between chunks; U matters to the
    subspace solver, which refines it, and is inert to the full solver,
    whose first sweep overwrites it)."""
    return jax.jit(_make_chunk_core(mesh, data_dims, params, iters))


#: compile-ledger family of the training path: one entry per distinct
#: (mesh, data dims, hyperparams, chunking) program — for the subspace
#: solver that means one per (rank, block_size) family on fixed data, the
#: bound the solver tests assert via `fn_cache.family_keys`
TRAIN_FAMILY = "als_train"
#: the `jax.named_scope`s of a half-sweep: the train program publishes
#: which of its instructions belongs to which (`ops/fn_cache`), and a
#: capture's device time reads by these names (`pio profile`)
TRAIN_SCOPES = ("als_init", "als_gram", "als_reg", "als_solve")


def _cached_train_fn(mesh: Mesh, data_dims, params: ALSParams,
                     chunk_iters: Optional[int] = None):
    """Memoized jitted train fns — rebuilding the closures on every call
    would force a re-trace per training run (FastEvalEngine's
    compilation-cache analog; the key is everything that shapes the
    compiled program). Registered in the shared `ops/fn_cache` ledger so
    training compiles surface as ``pio_jax_compile_total{family=
    als_train}``, with the same bounded-LRU protection for long-running
    servers retraining on growing data. The key leaves out the padded
    row count, so it says nothing about whether a dispatch compiles:
    `train_als` asks the compiler's own count for that."""
    from predictionio_tpu.ops.fn_cache import mesh_cached_fn

    def build():
        if chunk_iters is None:
            return make_train_fn(mesh, data_dims, params)
        return make_chunk_fn(mesh, data_dims, params, chunk_iters)

    # block_size only shapes SUBSPACE programs; normalizing it to 0 for
    # "full" keeps full-solver trains that merely carry different resolved
    # block sizes (e.g. a PIO_ALS_BLOCK_SIZE override on a full box) on
    # ONE compiled program and ONE ledger entry — mirroring the eval
    # sweep's group_candidates
    key_params = (dataclasses.replace(params, block_size=0)
                  if params.solver == "full" else params)
    key = (data_dims, dataclasses.astuple(key_params), chunk_iters)
    return mesh_cached_fn(TRAIN_FAMILY, mesh, key, build,
                          scopes=TRAIN_SCOPES)


def _process_shard_range(mesh: Mesh) -> Tuple[int, int]:
    """This process's contiguous run [lo, hi) of mesh shard rows (one row
    per device along the flattened mesh). Asserts the layout every
    multi-process path requires: process-contiguous device order."""
    import jax

    me = jax.process_index()
    rows_mine = [i for i, d in enumerate(mesh.devices.flat)
                 if d.process_index == me]
    lo, hi = min(rows_mine), max(rows_mine) + 1
    assert len(rows_mine) == hi - lo, (
        "mesh interleaves processes along the shard axis "
        f"(process {me} owns rows {rows_mine}); multi-process data "
        "layouts require process-contiguous device order")
    return lo, hi


def build_distributed(mesh: Mesh, user_idx: np.ndarray,
                      item_idx: np.ndarray, ratings: np.ndarray,
                      n_users: int, n_items: int,
                      row_len: Optional[int] = None) -> ALSData:
    """Assemble mesh-committed ALSData from PER-PROCESS event shards.

    The full partitioned input pipeline (SURVEY §2.9 P2 + P4): each
    process passes only the ratings its own storage shard produced
    (`find_columnar(shard=(p, P))`, the JDBCPEvents.scala:89-101
    partition-read analog), rows are re-keyed to their segment owners by
    ONE `lax.all_to_all` per side (parallel/shuffle.py — the Spark
    shuffle as an XLA collective), and each process packs + commits only
    its own padded row blocks. No process ever materializes the global
    rating set; peak host memory is the local shard + its exchange bins.

    Single-process meshes degrade to `ALSData.build(...).put(mesh)`.
    """
    user_idx = np.ascontiguousarray(user_idx, np.int32)
    item_idx = np.ascontiguousarray(item_idx, np.int32)
    ratings = np.ascontiguousarray(ratings, np.float32)
    n_shards = int(mesh.devices.size)
    if jax.process_count() == 1:
        return ALSData.build(user_idx, item_idx, ratings, n_users,
                             n_items, n_shards, row_len=row_len).put(mesh)

    with span("als_pack"):
        data = _build_distributed(mesh, user_idx, item_idx, ratings,
                                  n_users, n_items, row_len)
    train_stats.observe_row_fill(data)
    return data


def _build_distributed(mesh: Mesh, user_idx, item_idx, ratings,
                       n_users: int, n_items: int,
                       row_len: Optional[int]) -> ALSData:
    """The multi-process body of `build_distributed`: exchange, pack and
    commit this process's rows."""
    from predictionio_tpu.parallel.shuffle import allgather_object, \
        exchange_rows

    n_shards = int(mesh.devices.size)
    lo, hi = _process_shard_range(mesh)
    shards_per_proc = hi - lo
    # global sizes ride one tiny metadata all-gather
    meta = allgather_object({
        "nnz": int(len(ratings)),
        "hash": _coo_hash_commutative(user_idx, item_idx, ratings)})
    nnz = sum(m["nnz"] for m in meta)
    digest = _combine_coo_hashes(meta, nnz)
    if row_len is None:
        row_len = _auto_row_len(nnz, max(n_users, n_items))

    payload = np.stack([user_idx, item_idx,
                        ratings.view(np.int32)], axis=1)

    # each shard row's owner read off the mesh itself — never inferred
    # from arithmetic, which would silently drop rows on meshes with
    # uneven devices-per-process or non-ascending process order
    proc_of_shard = np.asarray(
        [d.process_index for d in mesh.devices.flat], np.int32)

    def one_side(n_segments: int, seg_col: int, tgt_col: int):
        seg_per_shard = -(-max(n_segments, 1) // n_shards)
        shard_of = np.minimum(payload[:, seg_col] // seg_per_shard,
                              n_shards - 1)
        mine = exchange_rows(proc_of_shard[shard_of], payload)
        seg = mine[:, seg_col]
        assert seg.size == 0 or (
            seg.min() >= lo * seg_per_shard
            and seg.max() < hi * seg_per_shard), (
            "exchange delivered segments outside this process's shard "
            "range — shard ownership mapping is inconsistent")
        order = np.argsort(seg, kind="stable")
        seg_s = seg[order].astype(np.int64)
        tgt_s = mine[order, tgt_col]
        val_s = mine[order, 2].view(np.float32)
        # pack each OWNED shard's rows (the local slice of shard_rows,
        # with the row-count bucketing agreed globally via all-gather)
        bounds = np.searchsorted(
            seg_s, (lo + np.arange(shards_per_proc + 1)) * seg_per_shard)
        parts = []
        for j in range(shards_per_proc):
            a, b = int(bounds[j]), int(bounds[j + 1])
            parts.append(_build_rows(
                seg_s[a:b] - (lo + j) * seg_per_shard, tgt_s[a:b],
                val_s[a:b], None, row_len, seg_per_shard))
        r_local = max(t.shape[0] for t, _, _, _ in parts)
        r_max = _bucket_rows(max(allgather_object(r_local)))
        tgt, val, w, seg = _stack_parts(parts, r_max, row_len,
                                        seg_per_shard)

        def commit(local, tail):
            # specs spelled exactly as ALSData.put writes them, so put()'s
            # idempotence check recognizes these arrays as already resident
            spec = P("data", None, None) if tail else P("data", None)
            return jax.make_array_from_process_local_data(
                NamedSharding(mesh, spec), np.ascontiguousarray(local),
                (n_shards, r_max) + tail)

        return ShardedRows(
            tgt=commit(tgt, (row_len,)),
            val=commit(val, (row_len,)),
            w=commit(w, (row_len,)),
            seg=commit(seg, ()),
            seg_per_shard=seg_per_shard,
            n_segments=n_shards * seg_per_shard,
            row_len=row_len)

    by_user = one_side(n_users, 0, 1)
    by_item = one_side(n_items, 1, 0)
    out = ALSData(by_user=by_user, by_item=by_item,
                  n_users=n_users, n_items=n_items,
                  n_users_pad=by_user.n_segments,
                  n_items_pad=by_item.n_segments,
                  nnz=nnz, digest=digest)
    jax.block_until_ready([
        out.by_user.tgt, out.by_user.val, out.by_user.w, out.by_user.seg,
        out.by_item.tgt, out.by_item.val, out.by_item.w, out.by_item.seg])
    return out


def _coo_hash_commutative(user_idx, item_idx, ratings) -> int:
    """Per-process contribution to an order- AND partition-independent
    dataset hash: a commutative sum of per-row mixes (splitmix64-style),
    so the combined digest is identical however rows are spread across
    processes. Weaker than blake2b over sorted rows but still sensitive
    to any single changed rating — enough for checkpoint fingerprints."""
    with np.errstate(over="ignore"):
        h = (user_idx.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)
             ^ item_idx.astype(np.uint64) * np.uint64(0xC2B2AE3D27D4EB4F)
             ^ ratings.view(np.uint32).astype(np.uint64)
             * np.uint64(0x165667B19E3779F9))
        h ^= h >> np.uint64(31)
        h *= np.uint64(0xFF51AFD7ED558CCD)
        h ^= h >> np.uint64(29)
        return int(h.sum(dtype=np.uint64))


def _combine_coo_hashes(meta, nnz: int) -> str:
    total = np.uint64(0)
    with np.errstate(over="ignore"):
        for m in meta:
            total += np.uint64(m["hash"])
    return f"coo-{nnz}-{int(total):016x}"


def coo_digest(user_idx: np.ndarray, item_idx: np.ndarray,
               ratings: np.ndarray) -> str:
    """Identity hash of the FULL rating set (canonical dtypes, so int32
    vs int64 inputs digest identically). Full, not sampled: a checkpoint
    resumed against data where even one rating changed must retrain.

    The hash is a commutative sum of per-row mixes, so it is independent
    of row ORDER and of how rows are PARTITIONED across processes —
    single-process `ALSData.build` and multi-host `build_distributed`
    digest the same data identically, which the als_fingerprint
    mesh-shape-independence contract requires."""
    u = np.ascontiguousarray(np.asarray(user_idx).reshape(-1), np.int64)
    i = np.ascontiguousarray(np.asarray(item_idx).reshape(-1), np.int64)
    r = np.ascontiguousarray(np.asarray(ratings).reshape(-1), np.float32)
    return f"coo-{len(r)}-{_coo_hash_commutative(u, i, r):016x}"


def als_fingerprint(data: ALSData, params: ALSParams) -> str:
    """Identity of a training run for checkpoint-resume safety: math-shaping
    hyperparams (num_iterations/chunk_size excluded — more iterations on the
    same run IS the resume use case; solver/block_size excluded too — both
    solvers minimize the same objective and V is the complete state, so a
    snapshot survives switching solvers mid-run) + dataset stats + the
    mesh-independent
    COO digest (NOT the padded row layout, which varies with shard count —
    snapshots must survive resuming on a different mesh shape). A crashed
    run restarted with different reg/seed/alpha/implicit_prefs, or against
    different ratings of the same shape, retrains from scratch."""
    import hashlib

    h = hashlib.blake2b(digest_size=16)
    h.update(repr((params.rank, params.reg, params.alpha,
                   params.implicit_prefs, params.weighted_reg,
                   params.seed)).encode())
    h.update(np.asarray([data.nnz, data.n_users, data.n_items],
                        np.int64).tobytes())
    h.update(data.digest.encode())
    return h.hexdigest()


def train_als(mesh: Mesh, data: ALSData, params: ALSParams,
              checkpointer=None) -> Tuple[np.ndarray, np.ndarray]:
    """Train and return host (U [n_users, K], V [n_items, K]).

    With a `workflow.checkpoint.Checkpointer`, iterations run in chunks of
    `checkpointer.interval`, snapshotting the item factors between chunks
    (the ALS state is fully determined by V — each sweep recomputes U from
    it); a crashed/preempted run resumes from the latest snapshot, even on
    a different mesh shape (snapshots hold unpadded host arrays)."""
    import time

    from predictionio_tpu.obs.train_stats import (
        als_block_sweeps, als_gramian_cache_hits, als_half_sweep_seconds,
    )

    validate_solver(params)
    n_shards = int(np.prod(mesh.devices.shape))
    assert data.by_user.tgt.shape[0] == n_shards, \
        f"data built for {data.by_user.tgt.shape[0]} shards, mesh has {n_shards}"
    # commit the rows to the mesh (idempotent): every caller then feeds
    # identically-sharded resident arrays, so one (params, dims) pair
    # compiles exactly once per process regardless of entry path, and
    # repeated calls never re-upload
    data = data.put(mesh)
    mesh_devices = [d for d in mesh.devices.flat
                    if d.process_index == jax.process_index()]
    logger.info("device bytes in use after ALSData.put: %s",
                bytes_in_use(mesh_devices))
    multihost = jax.process_count() > 1

    def gather_host(arr, n_rows):
        """Full host copy of a (possibly cross-host-sharded) factor
        matrix — every host needs it for serving/persistence."""
        if multihost:
            from jax.experimental.multihost_utils import process_allgather

            return np.asarray(process_allgather(arr, tiled=True))[:n_rows]
        return np.asarray(jax.device_get(arr))[:n_rows]

    dims = (data.n_users_pad, data.n_items_pad,
            data.by_user.seg_per_shard, data.by_item.seg_per_shard,
            data.n_items)
    key = jax.random.PRNGKey(params.seed)
    bu = (data.by_user.tgt, data.by_user.seg, data.by_user.val, data.by_user.w)
    bi = (data.by_item.tgt, data.by_item.seg, data.by_item.val, data.by_item.w)

    solve_s = 0.0    # device-dispatch wall only, excluding snapshot I/O
    # whether a timed dispatch paid trace+compile is the compiler's word
    # (its own events, counted before and after), not the fn_cache
    # ledger's: jit retraces under an unchanged ledger key when a shape
    # the key leaves out changes
    jax_stats.listen_to_compiler()
    compiled = False

    def dispatch(fn, *args):
        """One blocked, timed dispatch of a train program."""
        nonlocal solve_s, compiled
        with span("als_solve"):
            compiles_before = jax_stats.backend_compile_count()
            t0 = time.perf_counter()
            U, V = fn(bu, bi, *args)
            jax.block_until_ready(V)
            solve_s += time.perf_counter() - t0
            compiled |= jax_stats.backend_compile_count() > compiles_before
        return U, V

    iters_run = params.num_iterations
    if checkpointer is None:
        U, V = dispatch(_cached_train_fn(mesh, dims, params), key)
    else:
        k = params.rank
        fp = als_fingerprint(data, params)
        snap = checkpointer.latest(fingerprint=fp)
        it = 0
        V = None
        U = None     # subspace snapshots carry U too (BCD state is (U, V))
        if multihost:
            # the resume decision must be IDENTICAL on every host or the
            # SPMD programs diverge (some resuming, some from scratch);
            # process 0's snapshot is authoritative — snapshot dirs are
            # per-host paths, not guaranteed shared
            from jax.experimental.multihost_utils import (
                broadcast_one_to_all)

            ok = snap is not None and snap[1].get("V") is not None \
                and snap[1]["V"].shape == (data.n_items, k) \
                and snap[0] < params.num_iterations
            # only subspace snapshots carry U; gating on the solver (a
            # host-uniform static) avoids allocating + broadcasting an
            # n_users x k zero buffer on every full-solver train start
            want_u = params.solver == "subspace"
            has_u = want_u and ok and snap[1].get("U") is not None \
                and snap[1]["U"].shape == (data.n_users, k)
            meta = np.zeros(3, np.int64)
            v_buf = np.zeros((data.n_items, k), np.float32)
            u_buf = (np.zeros((data.n_users, k), np.float32) if want_u
                     else np.zeros((0, k), np.float32))
            if jax.process_index() == 0 and ok:
                meta[:] = (1, snap[0], int(has_u))
                v_buf[:] = np.asarray(snap[1]["V"], np.float32)
                if has_u:
                    u_buf[:] = np.asarray(snap[1]["U"], np.float32)
            meta, v_buf, u_buf = broadcast_one_to_all((meta, v_buf, u_buf))
            if int(meta[0]):
                it = int(meta[1])
                V = jnp.zeros((data.n_items_pad, k), jnp.float32)
                V = V.at[:data.n_items].set(jnp.asarray(v_buf))
                if int(meta[2]):
                    U = jnp.zeros((data.n_users_pad, k), jnp.float32)
                    U = U.at[:data.n_users].set(jnp.asarray(u_buf))
        elif snap is not None and snap[1].get("V") is not None \
                and snap[1]["V"].shape == (data.n_items, k) \
                and snap[0] < params.num_iterations:
            # a snapshot at/past the target (stale run with fewer iters)
            # would skip the loop and leave U zeroed — retrain instead
            it, state = snap
            V = jnp.zeros((data.n_items_pad, k), jnp.float32)
            V = V.at[:data.n_items].set(jnp.asarray(state["V"]))
            if state.get("U") is not None \
                    and state["U"].shape == (data.n_users, k):
                U = jnp.zeros((data.n_users_pad, k), jnp.float32)
                U = U.at[:data.n_users].set(jnp.asarray(state["U"]))
        if V is None:
            V = (jax.random.normal(key, (data.n_items_pad, k), jnp.float32)
                 / jnp.sqrt(jnp.asarray(k, jnp.float32)))
            # same pad-row zeroing as make_train_fn's init: the chunked
            # run must start from the identical state
            V = jnp.where((jnp.arange(data.n_items_pad)
                           < data.n_items)[:, None], V, 0.0)
        if U is None:
            U = jnp.zeros((data.n_users_pad, k), jnp.float32)
        iters_run = params.num_iterations - it
        # the full solver's state is V alone (each sweep recomputes U
        # exactly); block coordinate descent refines BOTH sides, so its
        # snapshots carry U too — resume stays bit-equivalent to the
        # uninterrupted run
        snap_u = params.solver == "subspace"
        while it < params.num_iterations:
            n = min(checkpointer.interval, params.num_iterations - it)
            U, V = dispatch(
                _cached_train_fn(mesh, dims, params, chunk_iters=n), U, V)
            it += n
            if it < params.num_iterations:
                if multihost:
                    # V is sharded across hosts: snapshot the gathered
                    # copy, and only process 0 writes (every process
                    # writing the same file would race)
                    state = {"V": gather_host(V, data.n_items)}
                    if snap_u:
                        state["U"] = gather_host(U, data.n_users)
                    if jax.process_index() == 0:
                        checkpointer.save(it, state, fingerprint=fp)
                else:
                    state = {"V": V[:data.n_items]}
                    if snap_u:
                        state["U"] = U[:data.n_users]
                    checkpointer.save(it, state, fingerprint=fp)

    # half-sweep accounting (host-side: the sweeps run fused inside one
    # device loop, so per-sweep numbers are derived, not sampled; only
    # solve-dispatch wall counts — snapshot gathers/writes between chunks
    # must not inflate the kernel's timing, and a cold dispatch's
    # trace+compile would drown the per-solver comparison the histogram
    # exists for, so compiling runs observe nothing)
    logger.info("device bytes in use after train: %s; U shards %s, "
                "V shards %s", bytes_in_use(mesh_devices),
                [tuple(s.data.shape) for s in U.addressable_shards],
                [tuple(s.data.shape) for s in V.addressable_shards])
    half_sweeps = max(1, 2 * iters_run)
    if not compiled:
        als_half_sweep_seconds().observe(
            solve_s / half_sweeps, solver=params.solver)
    if params.solver == "subspace":
        n_blocks = len(block_starts(params.rank, params.block_size))
        als_block_sweeps().inc(half_sweeps * n_blocks)
        # the per-half-sweep Gramian/count cache serves every block solve
        # after the first without a rebuild
        als_gramian_cache_hits().inc(half_sweeps * max(0, n_blocks - 1))
    with span("als_fetch"):
        U_host = gather_host(U, data.n_users)
        V_host = gather_host(V, data.n_items)
    train_stats.als_fetch_bytes().inc(U_host.nbytes + V_host.nbytes)
    return U_host, V_host


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames="num")
def _topk_scores_batch(user_vecs: jax.Array, V: jax.Array, mask: jax.Array,
                       num: int) -> Tuple[jax.Array, jax.Array]:
    scores = user_vecs @ V.T                    # [B, n_items] MXU matmul
    scores = jnp.where(mask, -jnp.inf, scores)
    return jax.lax.top_k(scores, num)


@functools.partial(jax.jit, static_argnames="num")
def _topk_scores_batch_nomask(user_vecs: jax.Array, V: jax.Array,
                              num: int) -> Tuple[jax.Array, jax.Array]:
    """No-exclusion fast path: skips the [B, n_items] mask build AND its
    host->device transfer — plain `{"user": ..., "num": N}` queries (the
    reference quickstart shape,
    tests/pio_tests/scenarios/quickstart_test.py:86) never carry
    black/white lists."""
    return jax.lax.top_k(user_vecs @ V.T, num)


#: measured seconds for one tiny jitted dispatch + fetch on the default
#: backend — the fixed per-request cost of touching the device at all.
#: Serving compares it against the host-BLAS cost of the same scoring
#: matmul and sends the batch wherever it finishes sooner
#: (dispatch-latency-aware serving; the reference's in-heap serial loop
#: CreateServer.scala:508-510 pays zero dispatch cost). The crossover
#: was calibrated against a link that no longer exists; not
#: re-measured. Re-probed when the scorer MODE changes: a stale
#: measurement taken under a different kernel regime would mis-route
#: batches for the rest of the process. Tests/benches that FORCE the
#: device lane assign ``_DEVICE_ROUNDTRIP_S = 0.0`` directly (leaving
#: the mode marker alone), which pins the value across modes.
_DEVICE_ROUNDTRIP_S: Optional[float] = None
_DEVICE_ROUNDTRIP_MODE: Optional[str] = None


def device_roundtrip_s() -> float:
    global _DEVICE_ROUNDTRIP_S, _DEVICE_ROUNDTRIP_MODE
    from predictionio_tpu.ops.scoring import process_scorer_config

    mode = process_scorer_config().mode
    if _DEVICE_ROUNDTRIP_S is None or (
            _DEVICE_ROUNDTRIP_MODE is not None
            and _DEVICE_ROUNDTRIP_MODE != mode):
        import time

        # pio: ignore[PIO001]: one-shot roundtrip probe; result memoized in _DEVICE_ROUNDTRIP_S
        probe = jax.jit(lambda a: jax.lax.top_k(a @ a.T, 4))
        x = np.ones((8, 8), np.float32)
        jax.block_until_ready(probe(x))          # compile outside the clock
        t0 = time.perf_counter()
        for _ in range(3):
            jax.device_get(probe(x))
        _DEVICE_ROUNDTRIP_S = (time.perf_counter() - t0) / 3
        _DEVICE_ROUNDTRIP_MODE = mode
    return _DEVICE_ROUNDTRIP_S


#: rough host matmul+argpartition throughput (flop/s) for the crossover
#: estimate; measured lazily the first time a model serves from host.
_HOST_FLOPS: Optional[float] = None


def _host_flops() -> float:
    global _HOST_FLOPS
    if _HOST_FLOPS is None:
        import time

        u = np.ones((16, 32), np.float32)
        v = np.ones((2048, 32), np.float32)
        _host_topk(u @ v.T, 10)                  # warm the BLAS path
        t0 = time.perf_counter()
        _host_topk(u @ v.T, 10)
        dt = max(time.perf_counter() - t0, 1e-7)
        _HOST_FLOPS = 2.0 * u.shape[0] * v.shape[0] * v.shape[1] / dt
    return _HOST_FLOPS


@dataclasses.dataclass
class ALSModel:
    """Trained factors + id maps (template ALSModel.scala:33-80 analog).

    Picklable pytree-of-numpy; recommend() runs the scoring matvec jitted.
    """

    user_vocab: np.ndarray   # sorted distinct user ids (index = row of U)
    item_vocab: np.ndarray   # sorted distinct item ids (index = row of V)
    U: np.ndarray            # [n_users, K]
    V: np.ndarray            # [n_items, K]

    def __getstate__(self):
        d = dict(self.__dict__)
        d.pop("_resident", None)      # device arrays never hit the checkpoint
        d.pop("_scorer_cache", None)  # quantized residency rebuilds on load
        return d

    @property
    def V_device(self) -> jax.Array:
        """Item factors resident on device across requests (SURVEY §2.9 P7:
        serve-time model residency). Re-uploaded only when V is swapped."""
        cached = getattr(self, "_resident", None)
        if cached is None or cached[0] is not self.V:
            cached = (self.V, jax.device_put(np.asarray(self.V)))
            self._resident = cached
        return cached[1]

    def user_index(self, user_id: str) -> Optional[int]:
        return vocab_index(self.user_vocab, user_id)

    def item_index(self, item_id: str) -> Optional[int]:
        return vocab_index(self.item_vocab, item_id)

    def predict_rating(self, user_id: str, item_id: str) -> Optional[float]:
        ui, ii = self.user_index(user_id), self.item_index(item_id)
        if ui is None or ii is None:
            return None
        return float(self.U[ui] @ self.V[ii])

    def _query_mask(self, exclude_items: Tuple[str, ...],
                    allow_items) -> np.ndarray:
        mask = np.zeros(len(self.item_vocab), dtype=bool)
        for it in exclude_items:
            ii = self.item_index(it)
            if ii is not None:
                mask[ii] = True
        if allow_items is not None:
            allow = np.ones(len(self.item_vocab), dtype=bool)
            for it in allow_items:
                ii = self.item_index(it)
                if ii is not None:
                    allow[ii] = False
            mask |= allow
        return mask

    def recommend(self, user_id: str, num: int,
                  exclude_items: Tuple[str, ...] = (),
                  allow_items: Optional[Tuple[str, ...]] = None):
        """Top-num (item_id, score), optionally excluding/allowlisting."""
        return self.recommend_batch(
            [(user_id, num, exclude_items, allow_items)])[0]

    def _use_host(self, n_rows: int, any_mask: bool) -> bool:
        """Route the batch to host BLAS when the estimated host scoring
        time undercuts one probed device round-trip
        (`device_roundtrip_s`): small catalogs serve from host; catalogs
        where the [B,N]@[N,K] matmul dominates go to the MXU. Masked
        batches lean host-ward because the device path also pays the
        [B, n_items] mask transfer. The 1.5x mask factor was calibrated
        against a link that no longer exists; not re-measured.

        Host BLAS materializes full f32 scores, i.e. it IS the exact
        scorer — so it only competes in exact mode. A non-exact scorer
        mode (ops/scoring) always routes device: the operator chose
        quantized residency for a catalog scale where the host crossover
        is irrelevant, and splitting a fused deployment's traffic across
        an exact host lane would make answers depend on batch size."""
        from predictionio_tpu.ops.scoring import holder_scorer_config

        cfg = holder_scorer_config(self)
        if cfg.mode != "exact":
            return False
        if int(getattr(cfg, "shards", 1) or 1) > 1:
            # model-parallel serving: the catalog is declared bigger than
            # one device (ops/scoring.ShardedScorer shards even exact
            # mode), so the single-host materialized path must not win
            # the crossover
            return False
        flops = 2.0 * n_rows * len(self.item_vocab) * self.U.shape[1]
        host_s = flops / _host_flops()
        device_s = device_roundtrip_s() * (1.5 if any_mask else 1.0)
        return host_s < device_s

    def _fused_scorer(self):
        """The cached ops/scoring scorer for the CURRENT process scorer
        mode, or None when exact (or when the built scorer's parity
        gate demoted it to exact). Keyed on V's identity like
        `V_device`, so a fold-in apply that swaps V requantizes on the
        next scored batch — the pre-swap warm drive in practice."""
        from predictionio_tpu.ops import scoring

        scorer = scoring.scorer_for(self, self.V)
        if scorer is None or not scorer.active:
            return None
        return scorer

    def recommend_batch(self, requests):
        """Batched recommend: one [B,K]@[K,N] matmul + top_k for B queries.

        requests: sequence of (user_id, num, exclude_items, allow_items).
        Returns a list parallel to requests; [] for unknown users. This is
        the batch behind query-server micro-batching (SURVEY §2.9 P7) — the
        reference serves queries one at a time in a serial loop
        (CreateServer.scala:508). The batch runs on device (MXU matmul +
        top_k) or host BLAS, whichever the measured dispatch-latency
        crossover says is faster (`_use_host`).
        """
        out = [[] for _ in requests]
        scored = self._score_topk(requests)
        if scored is None:
            return out
        rows, scores, idx, _k = scored
        n_items = len(self.item_vocab)
        # vectorized result assembly: ONE finite-mask + ONE tolist (C-level
        # float conversion) + per-row vocab gathers instead of a Python
        # isfinite/str/float call per recommended item — on a big offline
        # batch the per-item churn here was costing more than the matmul
        finite = np.isfinite(scores)
        score_rows = scores.tolist()
        for b, j in enumerate(rows):
            want = min(requests[j][1], n_items)
            names = self.item_vocab[idx[b][:want]]
            fin_b, s_b = finite[b], score_rows[b]
            out[j] = [(str(names[t]), s_b[t])
                      for t in range(want) if fin_b[t]]
        return out

    def recommend_batch_arrays(self, requests):
        """`recommend_batch` as flat columns — the offline-throughput
        assembly (workflow/batch_predict.py arrow lane). Returns
        ``(items, scores, counts)``: request ``j`` owns the slice
        ``sum(counts[:j]) : sum(counts[:j+1])`` of the flat ``items``
        (object ndarray of item ids) and ``scores`` (float64 ndarray;
        float32 scores widened exactly as Python ``float()`` does, so
        values match the list path bit for bit). Never materializes a
        per-item Python tuple — at batch-scoring rates that churn costs
        more than the matmul; counts are 0 for unknown users."""
        counts = np.zeros(len(requests), dtype=np.int64)
        scored = self._score_topk(requests)
        empty = np.asarray([], dtype=object)
        if scored is None:
            return empty, np.asarray([], dtype=np.float64), counts
        rows, scores, idx, k = scored
        n_items = len(self.item_vocab)
        want = np.fromiter(
            (min(requests[j][1], n_items) for j in rows),
            dtype=np.int64, count=len(rows))
        take = np.isfinite(scores) & (np.arange(k)[None, :] < want[:, None])
        counts[np.asarray(rows)] = take.sum(axis=1)
        return (self.item_vocab[idx[take]],
                scores[take].astype(np.float64), counts)

    def _score_topk(self, requests):
        """Shared scoring core of the recommend_batch family: validate,
        gather known users, run the host-BLAS or bucketed-device matmul +
        top-k. Returns (rows, scores[B,k], idx[B,k], k) over the known-user
        rows, or None when no request has a known user."""
        n_items = len(self.item_vocab)
        for _u, num, _ex, _allow in requests:
            if num < 0:
                raise ValueError(f"num must be >= 0, got {num}")
        rows, uidx = [], []
        any_mask = False
        for j, (user_id, _num, ex, allow) in enumerate(requests):
            ui = self.user_index(user_id)
            if ui is not None:
                rows.append(j)
                uidx.append(ui)
                if ex or allow is not None:
                    any_mask = True
        if not rows:
            return None
        k = min(max(min(requests[j][1], n_items) for j in rows), n_items)
        u_batch = self.U[np.asarray(uidx)]

        if self._use_host(len(rows), any_mask):
            scores = u_batch @ self.V.T                  # [B, N] host BLAS
            if any_mask:
                for b, j in enumerate(rows):
                    m = self._query_mask(requests[j][2], requests[j][3])
                    scores[b, m] = -np.inf
            scores, idx = _host_topk(scores, k)
        elif (scorer := self._fused_scorer()) is not None:
            # fused/quantized/two-stage streaming kernel (ops/scoring):
            # the [B, n_items] score matrix never materializes, and the
            # seen-items mask folds into the tiles as a -inf sentinel,
            # so masked and unmasked batches ride ONE kernel family
            mask = None
            if any_mask:
                mask = np.stack(
                    [self._query_mask(requests[j][2], requests[j][3])
                     for j in rows])
            scores, idx = scorer.topk(u_batch, k, mask=mask)
        else:
            # bucket B and k to powers of two (ops/bucketing — the rule
            # the serving micro-batcher shares) so this scorer compiles a
            # handful of shapes instead of one per (batch, num) combo; an
            # un-bucketed jit would stall whole batches on recompiles
            b_pad = bucket_size(len(rows))
            k_pad = min(bucket_size(k), n_items)
            u_batch = _pad_rows(u_batch, b_pad)
            rank = u_batch.shape[1]
            if any_mask:
                mask = np.stack(
                    [self._query_mask(requests[j][2], requests[j][3])
                     for j in rows]
                    + [np.ones(n_items, bool)] * (b_pad - len(rows)))
                # shape_cached_fn returns the SAME shared jit (compiles
                # live in jit's cache); its build counter is the
                # per-bucket compile ledger pio_jax_compile_total reads
                fn = shape_cached_fn(
                    "als_topk_masked", (b_pad, k_pad, n_items, rank),
                    lambda: _topk_scores_batch)
                scores, idx = fn(jnp.asarray(u_batch), self.V_device,
                                 jnp.asarray(mask), k_pad)
            else:
                fn = shape_cached_fn(
                    "als_topk", (b_pad, k_pad, n_items, rank),
                    lambda: _topk_scores_batch_nomask)
                scores, idx = fn(jnp.asarray(u_batch), self.V_device,
                                 k_pad)
            scores, idx = jax.device_get((scores, idx))  # one fetch
            scores = scores[:len(rows), :k]
            idx = idx[:len(rows), :k]
        return rows, scores, idx, k


def rmse(model_U: np.ndarray, model_V: np.ndarray, user_idx: np.ndarray,
         item_idx: np.ndarray, ratings: np.ndarray) -> float:
    """Held-out RMSE of r_hat = u . v (the judged metric)."""
    pred = np.einsum("nk,nk->n", model_U[user_idx], model_V[item_idx])
    return float(np.sqrt(np.mean((pred - ratings) ** 2)))


# ---------------------------------------------------------------------------
# Online fold-in (deploy/foldin.py): batched single-side row solves
# ---------------------------------------------------------------------------

#: compile-ledger family of the online fold-in solver: one entry per
#: distinct (factor shape, segment bucket, row bucket, row_len, mode)
#: program — bounded by the power-of-two bucket ladders, never by the
#: number of applies (the als_topk discipline applied to fold-in)
FOLDIN_FAMILY = "als_foldin"


@functools.partial(
    jax.jit, static_argnames=("num_segments", "implicit_prefs",
                              "weighted_reg", "alpha_is_zero", "chunk_rows"))
def _foldin_solve(factors, gram_all, row_tgt, row_seg, row_val, row_w,
                  reg, alpha, *, num_segments: int, implicit_prefs: bool,
                  weighted_reg: bool, alpha_is_zero: bool,
                  chunk_rows: int) -> jax.Array:
    """Solve `num_segments` rows' normal equations against the frozen
    `factors` in one batched program — `_half_sweep_dyn`'s math with the
    global Gramian PASSED IN (``gram_all``, cached per serving unit by
    :class:`FoldInSolver`) instead of recomputed per dispatch, which is
    what makes a 2-second apply cadence affordable on a large catalog.
    Explicit feedback ignores ``gram_all`` (pass zeros)."""
    if implicit_prefs:
        p = jnp.where(row_val > 0, 1.0, 0.0)
        if alpha_is_zero:
            # c = 1 everywhere: the per-rating Gramian term vanishes
            _, rhs, cnt = rows_gram_rhs(
                factors, row_tgt, row_seg, row_val=p, row_w=row_w,
                num_segments=num_segments, chunk_rows=chunk_rows)
            A = jnp.broadcast_to(
                gram_all, (num_segments,) + gram_all.shape)
        else:
            cm1 = alpha * jnp.abs(row_val)               # c - 1
            vals = jnp.where(cm1 > 0,
                             (1.0 + cm1) * p / jnp.maximum(cm1, 1e-12), 0.0)
            gram, rhs, _ = rows_gram_rhs(
                factors, row_tgt, row_seg,
                row_val=vals, row_w=row_w * cm1,
                num_segments=num_segments, chunk_rows=chunk_rows)
            cnt = segment_count(row_seg, row_w.sum(axis=1), num_segments)
            A = gram_all[None, :, :] + gram
        lam = reg * jnp.where(weighted_reg, jnp.maximum(cnt, 1.0), 1.0)
        A = A + lam[:, None, None] * jnp.eye(factors.shape[1], dtype=A.dtype)
        return batched_spd_solve(A, rhs)
    gram, rhs, cnt = rows_gram_rhs(
        factors, row_tgt, row_seg, row_val=row_val, row_w=row_w,
        num_segments=num_segments, chunk_rows=chunk_rows)
    lam = reg * jnp.where(weighted_reg, jnp.maximum(cnt, 1.0), 1.0)
    A = gram + lam[:, None, None] * jnp.eye(factors.shape[1],
                                            dtype=gram.dtype)
    return batched_spd_solve(A, rhs)


_GRAM_FN = jax.jit(lambda v: v.T @ v)


class FoldInSolver:
    """Device-batched online fold-in against one frozen factor matrix.

    The composable unit iALS++ (arXiv:2110.14044) and ALX
    (arXiv:2112.02194) both build on: with the opposite side's factors
    frozen, each pending row (a user with fresh events, or an item with
    fresh raters) is an independent K x K least-squares solve — so B
    pending rows batch into ONE device program: gather each row's rated
    columns from `factors` (the ALX padded-row layout, reusing the
    training path's `_row_positions` packing + `rows_gram_rhs` Gramian
    assembly), add the per-unit cached global Gramian (implicit
    feedback's V^T V term, computed once per serving unit, not per
    apply), and run one batched Cholesky.

    Shapes are bucketed to powers of two (segment count AND packed row
    count) and registered in the ``als_foldin`` fn_cache family, so a
    server folding every few seconds compiles a bucket ladder once and
    then never again — the compile ledger stays bounded however long the
    event stream runs.
    """

    def __init__(self, factors: np.ndarray, params: ALSParams,
                 row_len: int = 32, factors_device=None):
        self.params = params
        self.row_len = max(1, int(row_len))
        host = np.ascontiguousarray(np.asarray(factors), np.float32)
        self._shape = host.shape
        #: resident device copy — callers with an already-resident array
        #: (ALSModel.V_device) pass it to skip the upload
        self._dev = (factors_device if factors_device is not None
                     else jax.device_put(host))
        self._gram = None        # lazy [K, K] V^T V (implicit) / zeros

    @property
    def rank(self) -> int:
        return self._shape[1]

    def _gram_dev(self):
        if self._gram is None:
            if self.params.implicit_prefs:
                self._gram = _GRAM_FN(self._dev)
            else:
                self._gram = jnp.zeros((self.rank, self.rank), jnp.float32)
        return self._gram

    def solve(self, rated, values, weights=None) -> np.ndarray:
        """Solve rows for B segments: ``rated[i]`` holds segment i's
        rated opposite-side indices (int), ``values[i]`` the rating
        values, optional ``weights[i]`` per-rating weights (default 1).
        Returns host float32 [B, K]. A segment with zero ratings solves
        to the zero row — callers should skip empties instead of
        applying them."""
        b = len(rated)
        if b != len(values):
            raise ValueError(f"rated/values length mismatch: {b} vs "
                             f"{len(values)}")
        if b == 0:
            return np.zeros((0, self.rank), np.float32)
        counts = np.fromiter((len(r) for r in rated), dtype=np.int64,
                             count=b)
        if weights is not None and [len(w) for w in weights] != \
                counts.tolist():
            raise ValueError("weights must parallel rated per segment")
        seg = np.repeat(np.arange(b, dtype=np.int64), counts)
        total = int(counts.sum())
        if total:
            tgt = np.concatenate([np.asarray(r) for r in rated]
                                 ).astype(np.int32)
            val = np.concatenate([np.asarray(v) for v in values]
                                 ).astype(np.float32)
            w = (np.concatenate([np.asarray(x) for x in weights]
                                ).astype(np.float32)
                 if weights is not None
                 else np.ones(total, np.float32))
            bad = (tgt < 0) | (tgt >= self._shape[0])
            if bad.any():
                raise ValueError(
                    f"rated indices out of range [0, {self._shape[0]})")
        else:
            tgt = np.zeros(0, np.int32)
            val = np.zeros(0, np.float32)
            w = np.zeros(0, np.float32)
        b_pad = bucket_size(b)
        rrow, col, n_rows, row_seg = _row_positions(seg, self.row_len,
                                                    b_pad)
        r_pad = bucket_size(max(n_rows, 1))
        row_tgt = np.zeros((r_pad, self.row_len), np.int32)
        row_val = np.zeros((r_pad, self.row_len), np.float32)
        row_w = np.zeros((r_pad, self.row_len), np.float32)
        # pad rows aim at the LAST (padding) segment with weight 0, so
        # row_seg stays sorted and the pads contribute nothing
        seg_arr = np.full((r_pad,), b_pad - 1, np.int32)
        seg_arr[:n_rows] = row_seg
        if rrow is not None:
            row_tgt[rrow, col] = tgt
            row_val[rrow, col] = val
            row_w[rrow, col] = w
        p = self.params
        key = (self._shape, b_pad, r_pad, self.row_len,
               p.implicit_prefs, p.weighted_reg, p.alpha == 0)
        # shape_cached_fn returns the SAME shared jit (executables live
        # in jit's cache); its build counter is the per-bucket compile
        # ledger pio_jax_compile_total{family=als_foldin} reads
        fn = shape_cached_fn(FOLDIN_FAMILY, key, lambda: _foldin_solve)
        out = fn(self._dev, self._gram_dev(), jnp.asarray(row_tgt),
                 jnp.asarray(seg_arr), jnp.asarray(row_val),
                 jnp.asarray(row_w), p.reg, p.alpha,
                 num_segments=b_pad, implicit_prefs=p.implicit_prefs,
                 weighted_reg=p.weighted_reg,
                 alpha_is_zero=(p.alpha == 0), chunk_rows=1024)
        return np.asarray(jax.device_get(out))[:b]
