"""Naive Bayes classifiers.

Two variants, replacing the reference's two NB paths:
  * CategoricalNaiveBayes — parity with e2's string-feature NB
    (e2/.../engine/CategoricalNaiveBayes.scala:23-172): per-position
    categorical features, log prior + per-feature log likelihoods, optional
    default-likelihood function for unseen values. Counting is vectorized
    (np.unique + bincount) instead of combineByKey.
  * MultinomialNB — the MLlib NaiveBayes analog used by the classification
    template (examples/scala-parallel-classification/add-algorithm/src/main/
    scala/NaiveBayesAlgorithm.scala:35-56): numeric count-vector features;
    prediction is one MXU matmul X @ logP^T + prior.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from predictionio_tpu.obs.tracing import span


# ---------------------------------------------------------------------------
# Categorical NB (e2 parity)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LabeledPoint:
    """e2 LabeledPoint: (label, string features per position)."""

    label: str
    features: Tuple[str, ...]


@dataclasses.dataclass
class CategoricalNaiveBayesModel:
    """priors/likelihoods structure parity (CategoricalNaiveBayes.scala:87)."""

    priors: Dict[str, float]                           # label -> log prior
    likelihoods: Dict[str, List[Dict[str, float]]]     # label -> per-position

    def log_score(self, point: LabeledPoint,
                  default_likelihood: Callable[[Sequence[float]], float]
                  = lambda ls: float("-inf")) -> Optional[float]:
        if point.label not in self.priors:
            return None
        return self._log_score(point.label, point.features,
                               default_likelihood)

    def _log_score(self, label: str, features: Sequence[str],
                   default_likelihood) -> float:
        ll = self.likelihoods[label]
        total = self.priors[label]
        for feature, position in zip(features, ll):
            total += position.get(
                feature, default_likelihood(list(position.values())))
        return total

    def predict(self, features: Sequence[str]) -> str:
        scored = [(label, self._log_score(label, features,
                                          lambda ls: float("-inf")))
                  for label in self.priors]
        return max(scored, key=lambda x: x[1])[0]


def train_categorical_nb(points: Sequence[LabeledPoint]
                         ) -> CategoricalNaiveBayesModel:
    """CategoricalNaiveBayes.train parity, vectorized."""
    if not points:
        raise ValueError("no training points")
    n_positions = len(points[0].features)
    labels = np.asarray([p.label for p in points], dtype=object)
    label_vocab, label_codes = np.unique(labels, return_inverse=True)
    label_counts = np.bincount(label_codes, minlength=len(label_vocab))
    total = float(len(points))

    priors = {str(lab): math.log(label_counts[i] / total)
              for i, lab in enumerate(label_vocab)}
    likelihoods: Dict[str, List[Dict[str, float]]] = {
        str(lab): [] for lab in label_vocab}

    for pos in range(n_positions):
        feats = np.asarray([p.features[pos] for p in points], dtype=object)
        feat_vocab, feat_codes = np.unique(feats, return_inverse=True)
        # joint counts [n_labels, n_feat_values] in one bincount
        joint = np.bincount(
            label_codes * len(feat_vocab) + feat_codes,
            minlength=len(label_vocab) * len(feat_vocab),
        ).reshape(len(label_vocab), len(feat_vocab))
        for li, lab in enumerate(label_vocab):
            position_map = {
                str(feat_vocab[fi]): math.log(joint[li, fi] / label_counts[li])
                for fi in range(len(feat_vocab)) if joint[li, fi] > 0}
            likelihoods[str(lab)].append(position_map)

    return CategoricalNaiveBayesModel(priors=priors, likelihoods=likelihoods)


# ---------------------------------------------------------------------------
# Multinomial NB (MLlib analog)
# ---------------------------------------------------------------------------

#: inputs below this element count train on host (BLAS one-hot gemm) —
#: the device (or sharded-device) count matmul can't repay its transfer
#: + dispatch below this size. Calibrated against a link that no longer
#: exists; not re-measured.
DEVICE_MIN_SIZE = 1_000_000

def _sharded_count_fn(mesh, axis: str, n_labels: int):
    """Compiled sharded count fn, cached per (mesh, n_labels) — jit's
    cache keys on function identity, so the wrapper must be reused."""
    from predictionio_tpu.ops.fn_cache import mesh_cached_fn

    def build():
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        def count_block(c, x):
            onehot = jax.nn.one_hot(c, n_labels, dtype=jnp.float32)
            return jax.lax.psum(onehot.T @ x.astype(jnp.float32), axis)

        return jax.jit(shard_map(
            count_block, mesh=mesh,
            in_specs=(P(axis), P(axis, None)),
            out_specs=P()))

    return mesh_cached_fn("nb_count", mesh, (axis, n_labels), build)


def _count_fn(n_labels: int):
    """Stable single-device count jit per label count (a per-call jit
    would recompile every train).
    Ledger-cached so the per-label-count programs show up bounded in
    ``pio_jax_compile_total{family=nb_count_host}``."""
    from predictionio_tpu.ops.fn_cache import shape_cached_fn

    def build():
        import jax
        import jax.numpy as jnp

        @jax.jit
        def count(codes, x):
            onehot = jax.nn.one_hot(codes, n_labels, dtype=jnp.float32)
            return onehot.T @ x.astype(jnp.float32)

        return count

    return shape_cached_fn("nb_count_host", n_labels, build)


def _compact_for_transfer(X: np.ndarray) -> np.ndarray:
    """Count matrices are usually small non-negative integers stored as
    float; ship them as uint8/uint16 (4x/2x fewer bytes over the
    host->device link — the usual bottleneck, SURVEY §7 'HBM bandwidth')
    and widen to f32 on device."""
    if X.dtype.kind in "ui":
        return X
    if X.dtype.kind != "f" or X.size == 0:
        return X
    xmax, xmin = X.max(), X.min()
    if xmin < 0 or xmax >= 65536 or np.any(np.mod(X, 1)):
        return X
    return X.astype(np.uint8 if xmax < 256 else np.uint16)


def _score_fn():
    """Stable scoring jit (a per-call wrapper would re-trace and
    re-compile every predict); one ledger entry under
    ``family=nb_score``."""
    from predictionio_tpu.ops.fn_cache import shape_cached_fn

    def build():
        import jax
        import jax.numpy as jnp

        @jax.jit
        def score(x, lp, pri):
            return x.astype(jnp.float32) @ lp.T + pri[None, :]

        return score

    return shape_cached_fn("nb_score", (), build)


#: device predict only pays off above this element count when the input
#: is NOT already device-resident. Calibrated against a link that no
#: longer exists; not re-measured.
PREDICT_DEVICE_MIN_SIZE = 50_000_000


@dataclasses.dataclass
class MultinomialNBModel:
    """label vocab + log priors [L] + log feature probs [L, F]."""

    label_vocab: np.ndarray
    log_prior: np.ndarray
    log_prob: np.ndarray

    def predict_scores(self, X: np.ndarray) -> np.ndarray:
        """[N, F] -> [N, L] joint log-likelihood (one matmul).

        Dispatch-aware routing (the serving-path rule, models/als.py
        _use_host): the matmul is tiny next to shipping X over the
        host->device link, so the device only wins when X is already
        resident there (train just ran on it) or very large. The cache
        keys on the CALLER's array object (atleast_2d happens inside the
        build), so train-then-predict on the same X reuses one upload."""
        from predictionio_tpu.ops import device_cache

        if not device_cache.is_resident([X], ("nb_x",)) \
                and X.size < PREDICT_DEVICE_MIN_SIZE:
            xs = np.atleast_2d(X)
            return xs.astype(np.float32, copy=False) @ self.log_prob.T \
                + self.log_prior[None, :]
        import jax

        xd = device_cache.resident(
            [X], ("nb_x",),
            lambda: jax.device_put(_compact_for_transfer(np.atleast_2d(X))))
        scores = np.asarray(jax.device_get(_score_fn()(
            xd, self.log_prob, self.log_prior)))
        # a resident copy from a sharded train carries device-count
        # padding rows; slice back to the caller's row count
        return scores[:np.atleast_2d(X).shape[0]]

    def predict(self, X: np.ndarray) -> np.ndarray:
        scores = self.predict_scores(np.atleast_2d(X))
        return self.label_vocab[np.argmax(scores, axis=1)]


def train_multinomial_nb(X: np.ndarray, labels: Sequence[str],
                         smoothing: float = 1.0, mesh=None
                         ) -> MultinomialNBModel:
    """MLlib NaiveBayes.train parity (lambda smoothing). Per-label feature
    counting runs as a one-hot [L,N]@[N,F] device matmul (MXU) when the
    input is big enough to pay for the transfer.

    With a multi-device `mesh`, documents shard over its first axis and
    each device contributes a partial [L, F] count combined by one psum —
    the collective analog of the reference's distributed `combineByKey`
    (e2/.../CategoricalNaiveBayes.scala:29, SURVEY §2.9 P1)."""
    from predictionio_tpu.ops import device_cache

    labels = np.asarray(labels, dtype=object)
    label_vocab, label_codes = np.unique(labels, return_inverse=True)
    n_labels = len(label_vocab)
    n_features = X.shape[1]
    n_dev = int(np.prod(mesh.devices.shape)) if mesh is not None else 1
    # device path: worth the transfer for big X, but the [N, L] one-hot it
    # materializes must stay bounded too (many-label inputs would OOM where
    # the host path needs only the [L, F] buffer)
    if mesh is not None and n_dev > 1 and X.size >= DEVICE_MIN_SIZE \
            and X.shape[0] * n_labels * 4 <= (1 << 28) * n_dev:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        axis = mesh.axis_names[0]
        shard = int(mesh.shape[axis])
        pad = (-len(label_codes)) % shard

        def _put_x_sharded():
            with span("nb_compact"):
                Xc = _compact_for_transfer(X)
                if pad:
                    Xc = np.concatenate(
                        [Xc, np.zeros((pad, n_features), Xc.dtype)])
            with span("nb_transfer"):
                xd = jax.device_put(Xc, NamedSharding(mesh, P(axis, None)))
                jax.block_until_ready(xd)
            return xd

        # only X's sharded placement is cached (labels change freely —
        # the tiny padded codes vector ships fresh on every call); the
        # hashable Mesh itself keys the layout (id(mesh) could alias
        # after GC — the fn_cache.py rule)
        xd = device_cache.resident(
            [X], ("nb_x_sharded", mesh, pad), _put_x_sharded)
        # alias under predict's key too: model.predict(X) must reuse this
        # resident copy instead of paying a second full upload (the score
        # matmul slices the padding rows back off)
        device_cache.resident([X], ("nb_x",), lambda: xd)
        codes = np.concatenate(
            [label_codes.astype(np.int32),
             np.full(pad, -1, np.int32)]         # one_hot(-1) == zero row
        ) if pad else label_codes.astype(np.int32)
        counts = np.asarray(jax.device_get(
            _sharded_count_fn(mesh, axis, n_labels)(codes, xd)
        )).astype(np.float64)
    elif X.size >= DEVICE_MIN_SIZE and X.shape[0] * n_labels * 4 <= 1 << 28:
        import jax

        def _put_x():
            with span("nb_compact"):
                Xc = _compact_for_transfer(X)
            with span("nb_transfer"):
                xd = jax.device_put(Xc)
                jax.block_until_ready(xd)
            return xd

        xd = device_cache.resident([X], ("nb_x",), _put_x)
        counts = np.asarray(jax.device_get(_count_fn(n_labels)(
            label_codes.astype(np.int32), xd))).astype(np.float64)
    elif X.dtype.kind == "f" and X.shape[0] * n_labels * 4 <= 1 << 28:
        # host BLAS one-hot count: one [L, N] @ [N, F] gemm — ~20x faster
        # than np.add.at's per-element scatter at spam-corpus sizes. Same
        # 256MB one-hot bound as the device branch: past it, fall through
        # to the O(1)-extra-memory scatter fold
        onehot = np.zeros((n_labels, X.shape[0]), np.float32)
        onehot[label_codes, np.arange(X.shape[0])] = 1.0
        counts = (onehot @ X).astype(np.float64)
    else:
        counts = np.zeros((n_labels, n_features), np.float64)
        np.add.at(counts, label_codes, X)
    label_counts = np.bincount(label_codes, minlength=n_labels)
    log_prior = np.log(label_counts / label_counts.sum())
    smoothed = counts + smoothing
    log_prob = np.log(smoothed / smoothed.sum(axis=1, keepdims=True))
    return MultinomialNBModel(
        label_vocab=label_vocab,
        log_prior=log_prior.astype(np.float32),
        log_prob=log_prob.astype(np.float32))
