"""Random-forest classifier, vectorized over trees on the device.

The tree model behind the classification template's RandomForest variant
(examples/scala-parallel-classification/add-algorithm/src/main/scala/
RandomForestAlgorithm.scala — MLlib `RandomForest.trainClassifier` with
numClasses/numTrees/featureSubsetStrategy/impurity/maxDepth/maxBins).

TPU-native design — nothing like MLlib's per-node task queues:

  * features are quantized once on host into `max_bins` quantile bins
    (MLlib's binning), so split search is integer histogramming;
  * every tree is a COMPLETE binary array of depth `max_depth` grown
    breadth-first: at level d all 2^d nodes of ALL trees split at once.
    One `segment_sum` builds the [nodes*features*bins*classes] histogram
    cell grid, a cumulative-sum scan turns it into left/right class
    counts per candidate threshold, and an argmin over the impurity
    surface picks each node's (feature, threshold) — fixed shapes
    throughout, `vmap` over trees, one jit for the whole fit;
  * bootstrap resampling and per-(tree, node) feature subsets are index
    arrays drawn up front (`featureSubsetStrategy` auto/all/sqrt/onethird);
  * prediction walks all trees in lockstep ([T, N] gathers per level) and
    majority-votes, MLlib's classification vote.

Nodes are always split to full depth; a node with no valid split (pure,
or empty under bootstrap) stores the sentinel threshold B-1 so every
sample routes left and the leaf majority is unchanged — the shape-static
equivalent of MLlib's early leaf cut-off.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.core.params import Params


@dataclasses.dataclass
class ForestParams(Params):
    """RandomForestAlgorithmParams parity."""

    num_classes: int = 0                  # 0 = infer from labels
    num_trees: int = 10
    feature_subset_strategy: str = "auto"   # auto|all|sqrt|onethird
    impurity: str = "gini"                  # gini|entropy
    max_depth: int = 4
    max_bins: int = 32
    seed: int = 0


def _subset_size(strategy: str, n_features: int) -> int:
    s = strategy.lower()
    if s == "auto" or s == "sqrt":
        # MLlib classification "auto" = sqrt
        return max(1, int(np.ceil(np.sqrt(n_features))))
    if s == "onethird":
        return max(1, int(np.ceil(n_features / 3)))
    if s == "all":
        return n_features
    raise ValueError(f"unknown featureSubsetStrategy {strategy!r}")


def _impurity_cost(left, right, kind: str):
    """Weighted impurity of a (left, right) class-count split.
    left/right: [..., C] counts. Returns [...] cost; +inf where a side
    is empty (invalid split, MLlib's minInstancesPerNode=1)."""
    nl = left.sum(-1)
    nr = right.sum(-1)
    n = nl + nr

    def node_impurity(counts, total):
        p = counts / jnp.maximum(total, 1.0)[..., None]
        if kind == "entropy":
            return -(jnp.where(p > 0, p * jnp.log(p), 0.0)).sum(-1)
        return 1.0 - (p * p).sum(-1)          # gini

    cost = (nl * node_impurity(left, nl) +
            nr * node_impurity(right, nr)) / jnp.maximum(n, 1.0)
    return jnp.where((nl == 0) | (nr == 0), jnp.inf, cost)


def _fit_kernel(bins, labels, boot_idx, feat_mask, n_classes: int,
                max_depth: int, max_bins: int, impurity: str):
    """Single-tree fit on quantized features; vmapped over trees.

    bins      [N, F] int32 quantile-bin codes
    labels    [N] int32 class codes
    boot_idx  [N] int32 bootstrap sample indices (this tree's bag)
    feat_mask [2^max_depth - 1, F] bool — allowed features per node
    Returns (feat [M], thr [M], leaf [2^max_depth] class ids) with
    M = 2^max_depth - 1 internal nodes in breadth-first order.
    """
    n, f = bins.shape
    b, c = max_bins, n_classes
    xb = bins[boot_idx]                       # [N, F] this tree's bag
    yb = labels[boot_idx]                     # [N]

    feat_out = jnp.zeros((2 ** max_depth - 1,), jnp.int32)
    thr_out = jnp.full((2 ** max_depth - 1,), b - 1, jnp.int32)
    node = jnp.zeros((n,), jnp.int32)         # relative id within level

    for d in range(max_depth):
        width = 2 ** d
        base = width - 1
        # histogram: cell = ((node*F + f)*B + bin) -> [width*F*B, C]
        cell = (node[:, None] * f + jnp.arange(f)[None, :]) * b + xb
        onehot = jax.nn.one_hot(yb, c, dtype=jnp.float32)
        hist = jax.ops.segment_sum(
            jnp.repeat(onehot, f, axis=0).reshape(n, f, c).reshape(-1, c),
            cell.reshape(-1), num_segments=width * f * b)
        hist = hist.reshape(width, f, b, c)
        # threshold t sends bin <= t left: left counts = cumsum over bins
        left = jnp.cumsum(hist, axis=2)        # [w, F, B, C]
        total = left[:, :, -1:, :]
        right = total - left
        cost = _impurity_cost(left, right, impurity)   # [w, F, B]
        # last bin (everything left) is the no-op sentinel; forbid it in
        # the argmin by +inf, and forbid disallowed features
        cost = cost.at[:, :, -1].set(jnp.inf)
        mask = feat_mask[base:base + width]            # [w, F]
        cost = jnp.where(mask[:, :, None], cost, jnp.inf)
        flat = cost.reshape(width, f * b)
        best = jnp.argmin(flat, axis=1)                # [w]
        best_cost = jnp.take_along_axis(flat, best[:, None], 1)[:, 0]
        bf = (best // b).astype(jnp.int32)
        bt = (best % b).astype(jnp.int32)
        # no valid split -> sentinel (feature 0, thr B-1: all left)
        ok = jnp.isfinite(best_cost)
        bf = jnp.where(ok, bf, 0)
        bt = jnp.where(ok, bt, b - 1)
        feat_out = jax.lax.dynamic_update_slice(feat_out, bf, (base,))
        thr_out = jax.lax.dynamic_update_slice(thr_out, bt, (base,))
        # route samples
        nf = bf[node]
        nt = bt[node]
        go_right = jnp.take_along_axis(xb, nf[:, None], 1)[:, 0] > nt
        node = node * 2 + go_right.astype(jnp.int32)

    # leaves: majority class of the final level's histogram
    width = 2 ** max_depth
    cell = node * c + yb
    leaf_hist = jax.ops.segment_sum(
        jnp.ones((n,), jnp.float32), cell, num_segments=width * c
    ).reshape(width, c)
    leaf = jnp.argmax(leaf_hist, axis=1).astype(jnp.int32)
    return feat_out, thr_out, leaf


@functools.partial(jax.jit, static_argnames=("n_classes", "max_depth",
                                             "max_bins", "impurity"))
def _fit_forest(bins, labels, boot_idx, feat_mask, n_classes, max_depth,
                max_bins, impurity):
    return jax.vmap(
        lambda bi, fm: _fit_kernel(bins, labels, bi, fm, n_classes,
                                   max_depth, max_bins, impurity)
    )(boot_idx, feat_mask)


def _sharded_fit_fn(mesh, c: int, depth: int, b: int, impurity: str):
    """Compiled tree-sharded fit fn, cached per (mesh, hyperparams) — a
    per-call jit(shard_map(...)) wrapper would re-trace every fold of a
    cross-validated eval (jit's cache keys on function identity)."""
    from predictionio_tpu.ops.fn_cache import mesh_cached_fn

    axis = mesh.axis_names[0]

    def build():
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        return jax.jit(shard_map(
            lambda xqd, cd, bi, fm: jax.vmap(
                lambda one_b, one_m: _fit_kernel(
                    xqd, cd, one_b, one_m, c, depth, b, impurity)
            )(bi, fm),
            mesh=mesh,
            in_specs=(P(), P(), P(axis, None), P(axis, None, None)),
            out_specs=(P(axis, None), P(axis, None), P(axis, None)),
            check_vma=False))

    return mesh_cached_fn("forest_fit", mesh, (axis, c, depth, b, impurity),
                          build)


@functools.partial(jax.jit, static_argnames=("max_depth", "n_classes"))
def _predict_kernel(feat, thr, leaf, qbins, max_depth, n_classes):
    """feat/thr [T, M], leaf [T, 2^D], qbins [N, F] -> votes argmax [N]."""
    t = feat.shape[0]
    nq = qbins.shape[0]
    node = jnp.zeros((t, nq), jnp.int32)
    for d in range(max_depth):
        base = 2 ** d - 1
        nf = jnp.take_along_axis(feat, base + node, axis=1)    # [T, N]
        nt = jnp.take_along_axis(thr, base + node, axis=1)
        xb = qbins.T[None, :, :]                                # [1, F, N]
        val = jnp.take_along_axis(
            jnp.broadcast_to(xb, (t,) + xb.shape[1:]), nf[:, None, :],
            axis=1)[:, 0, :]
        node = node * 2 + (val > nt).astype(jnp.int32)
    pred = jnp.take_along_axis(leaf, node, axis=1)              # [T, N]
    votes = jax.vmap(
        lambda col: jnp.bincount(col, length=n_classes),
        in_axes=1)(pred)                                        # [N, C]
    return jnp.argmax(votes, axis=1)


@dataclasses.dataclass
class ForestModel:
    """Picklable forest: bin thresholds + per-tree node arrays."""

    classes: np.ndarray          # [C] original labels (object/str)
    thresholds: np.ndarray       # [F, B-1] float32 quantile cut points
    feat: np.ndarray             # [T, 2^D - 1] int32
    thr: np.ndarray              # [T, 2^D - 1] int32 (bin index)
    leaf: np.ndarray             # [T, 2^D] int32 class codes
    max_depth: int

    def _binize(self, X: np.ndarray) -> np.ndarray:
        xq = np.empty(X.shape, np.int32)
        for j in range(X.shape[1]):
            xq[:, j] = np.searchsorted(self.thresholds[j], X[:, j],
                                       side="left")
        return xq

    def predict(self, X: np.ndarray) -> np.ndarray:
        """[N, F] -> [N] predicted labels (majority vote)."""
        X = np.asarray(X, np.float32)
        codes = _predict_kernel(
            jnp.asarray(self.feat), jnp.asarray(self.thr),
            jnp.asarray(self.leaf), jnp.asarray(self._binize(X)),
            self.max_depth, len(self.classes))
        return self.classes[np.asarray(codes)]


def train_forest(X: np.ndarray, y: Sequence, params: ForestParams,
                 mesh=None) -> ForestModel:
    """Fit a forest on dense [N, F] features with arbitrary labels.

    With a multi-device `mesh`, TREES shard over its first axis (the
    embarrassingly-parallel axis MLlib also exploits per-tree): each
    device grows its tree subset on replicated binned data, no cross-
    device traffic until the per-tree node arrays gather at the end.
    num_trees pads up to a shard-count multiple for the fit, then the
    padding is sliced off so the model is mesh-shape invariant."""
    X = np.asarray(X, np.float32)
    n, f = X.shape
    classes, codes = np.unique(np.asarray(y), return_inverse=True)
    c = int(params.num_classes) or len(classes)
    if c < len(classes):
        raise ValueError(f"numClasses={c} but labels have {len(classes)}")
    b = int(params.max_bins)

    # quantile binning (MLlib's findSplits): B-1 interior cut points
    qs = np.linspace(0, 1, b + 1)[1:-1]
    thresholds = np.quantile(X, qs, axis=0).T.astype(np.float32)  # [F, B-1]
    xq = np.empty((n, f), np.int32)
    for j in range(f):
        xq[:, j] = np.searchsorted(thresholds[j], X[:, j], side="left")

    t_req = int(params.num_trees)
    # trees shard over the FIRST mesh axis only (_sharded_fit_fn), so the
    # pad target is that axis's size, not the total device count
    n_dev = int(mesh.shape[mesh.axis_names[0]]) if mesh is not None else 1
    depth = int(params.max_depth)
    # RNG draws sized by the REQUESTED tree count so the stream (and hence
    # every kept tree) is identical on any mesh; padding to the device-
    # count multiple happens on the arrays afterwards and is sliced off
    # the model below
    rng = np.random.default_rng(params.seed)
    boot = rng.integers(0, n, size=(t_req, n)).astype(np.int32)
    m = _subset_size(params.feature_subset_strategy, f)
    n_nodes = 2 ** depth - 1
    if m >= f:
        mask = np.ones((t_req, n_nodes, f), bool)
    else:
        # per-(tree, node) random feature subset of size m
        scores = rng.random((t_req, n_nodes, f))
        kth = np.partition(scores, m - 1, axis=-1)[..., m - 1:m]
        mask = scores <= kth
    t = t_req + ((-t_req) % n_dev if n_dev > 1 else 0)
    if t > t_req:
        pad = t - t_req     # throwaway trees: re-fit copies of tree 0
        boot = np.concatenate([boot, np.repeat(boot[:1], pad, 0)])
        mask = np.concatenate([mask, np.repeat(mask[:1], pad, 0)])

    if n_dev > 1:
        fit = _sharded_fit_fn(mesh, c, depth, b, params.impurity)
        feat, thr, leaf = fit(
            jnp.asarray(xq), jnp.asarray(codes.astype(np.int32)),
            jnp.asarray(boot), jnp.asarray(mask))
    else:
        feat, thr, leaf = _fit_forest(
            jnp.asarray(xq), jnp.asarray(codes.astype(np.int32)),
            jnp.asarray(boot), jnp.asarray(mask), c, depth, b,
            params.impurity)
    # slice the padding back off: the trained model (and its votes) must
    # not depend on the mesh shape
    return ForestModel(
        classes=classes, thresholds=thresholds,
        feat=np.asarray(feat)[:t_req], thr=np.asarray(thr)[:t_req],
        leaf=np.asarray(leaf)[:t_req], max_depth=depth)
