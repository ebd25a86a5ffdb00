"""Recommended-user engine template (user-to-user similarity).

Rebuilds examples/scala-parallel-similarproduct/recommended-user: "follow"
events between users train an implicit-ALS user embedding; a query names
one or more users and gets back the users most similar to them.

Reference parity map:
  * DataSource   <- recommended-user/src/main/scala/DataSource.scala — users
    from `$set` aggregateProperties; user->user "follow" events
  * ALSAlgorithm <- ALSAlgorithm.scala — trainImplicit on (follower,
    followedUser, 1) triples; the model keeps the FOLLOWED-side factors
    (MLlib productFeatures) and scores candidates by summed cosine
    similarity against the query users' vectors, score > 0 only
  * Serving      <- Serving.scala — first prediction wins

TPU-native: the per-candidate cosine loop (ALSAlgorithm.scala predict, a
`.par` collection over every user) becomes one [n_users, K] @ [K] device
matvec over row-normalized factors.

Query: {"users": [...], "num": N, "whiteList"?, "blackList"?};
result: {"similarUserScores": [{"user": ..., "score": ...}]}.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Dict, List, Optional, Tuple

import numpy as np

from predictionio_tpu.core import Engine, EngineParams, FirstServing, Params, Preparator
from predictionio_tpu.core.base import Algorithm, DataSource
from predictionio_tpu.data.bimap import assign_indices, vocab_index
from predictionio_tpu.engines.common import resolved_als_solver
from predictionio_tpu.models.als import ALSData, ALSParams, train_als

logger = logging.getLogger("pio.engine.recommended_user")


# -- data types ---------------------------------------------------------------

@dataclasses.dataclass
class FollowEvent:
    user: str
    followed_user: str
    t: int


@dataclasses.dataclass
class FollowColumns:
    """Columnar user->user follow edges from the event scan."""

    users: np.ndarray           # object (follower ids)
    followed: np.ndarray        # object (followed ids)
    times: np.ndarray           # int64 epoch ms

    def __len__(self) -> int:
        return len(self.users)


@dataclasses.dataclass
class TrainingData:
    users: Dict[str, dict]
    follows: FollowColumns

    # row-object view kept for reference-API parity / inspection
    @property
    def follow_events(self) -> List[FollowEvent]:
        return [FollowEvent(u, f, int(t)) for u, f, t in
                zip(self.follows.users, self.follows.followed,
                    self.follows.times)]


PreparedData = TrainingData


@dataclasses.dataclass(frozen=True)
class Query:
    users: Tuple[str, ...]
    num: int
    white_list: Optional[Tuple[str, ...]] = None
    black_list: Optional[Tuple[str, ...]] = None

    def __post_init__(self):
        object.__setattr__(self, "users", tuple(self.users))
        for f in ("white_list", "black_list"):
            v = getattr(self, f)
            if v is not None:
                object.__setattr__(self, f, tuple(v))


@dataclasses.dataclass
class SimilarUserScore:
    user: str
    score: float


@dataclasses.dataclass
class PredictedResult:
    similar_user_scores: List[SimilarUserScore]

    def to_dict(self) -> dict:
        return {"similarUserScores": [{"user": s.user, "score": s.score}
                                      for s in self.similar_user_scores]}


# -- DASE ---------------------------------------------------------------------

@dataclasses.dataclass
class DataSourceParams(Params):
    app_name: str


class RecommendedUserDataSource(DataSource):
    """DataSource.scala parity: users from aggregated `$set`s plus
    user -> user "follow" events."""

    params_class = DataSourceParams

    def __init__(self, params: DataSourceParams):
        self.params = params

    def read_training(self, ctx) -> TrainingData:
        from predictionio_tpu.data.ingest import (
            aggregate_scan, decoding, event_columns, training_scan,
        )

        app = self.params.app_name
        users = {uid: dict(pm.fields) for uid, pm in
                 aggregate_scan(app, "user").items()}
        scan = training_scan(
            app, entity_type="user", event_names=["follow"],
            target_entity_type="user",
            columns=("entity_id", "target_entity_id", "event_time_ms"))
        with decoding(app, scan.table):
            u, f, t = event_columns(
                scan.table, "entity_id", "target_entity_id",
                "event_time_ms")
        return TrainingData(users=users,
                            follows=FollowColumns(u, f, t))


class RecommendedUserPreparator(Preparator):
    def prepare(self, ctx, td: TrainingData) -> PreparedData:
        return td


@dataclasses.dataclass
class ALSAlgorithmParams(Params):
    json_aliases = {"lambda": "reg"}

    rank: int = 10
    num_iterations: int = 20
    reg: float = 0.01
    alpha: float = 1.0
    seed: int = 3
    #: {"mode": "full"|"subspace", "block_size": N}; None defers
    #: to server.json "train" / PIO_ALS_SOLVER overrides
    solver: Optional[dict] = None


@dataclasses.dataclass
class RecommendedUserModel:
    """Followed-side factors + id map (ALSModel in the reference, holding
    similarUserFeatures / similarUserStringIntMap)."""

    user_vocab: np.ndarray           # followed users with factors, sorted
    V: np.ndarray                    # [n_users, K] row-normalized
    users: Dict[str, dict]           # $set metadata (User() in reference)

    def user_index(self, user_id: str) -> Optional[int]:
        return vocab_index(self.user_vocab, user_id)


class ALSAlgorithm(Algorithm):
    """ALSAlgorithm.scala parity: implicit ALS over the follow graph."""

    params_class = ALSAlgorithmParams

    def __init__(self, params: Optional[ALSAlgorithmParams] = None):
        self.params = params or ALSAlgorithmParams()

    def train(self, ctx, pd: PreparedData) -> RecommendedUserModel:
        from predictionio_tpu.data.bimap import batch_lookup
        from predictionio_tpu.data.ingest import pair_counts

        if not len(pd.follows):
            raise ValueError("follow events cannot be empty "
                             "(ALSAlgorithm.scala require parity)")
        if not pd.users:
            raise ValueError("users cannot be empty (use $set user events)")
        # reference drops events whose ids miss the BiMap built from the
        # $set user set (uindex == -1 filter) — one vectorized membership
        # test against the sorted known-user vocab
        known = np.unique(np.asarray(list(pd.users), dtype=object))
        valid = ((batch_lookup(known, pd.follows.users) >= 0)
                 & (batch_lookup(known, pd.follows.followed) >= 0))
        # each follow contributes confidence 1; repeats sum — MLlib
        # trainImplicit aggregates duplicate MLlibRating triples the same way
        followers, followed, values = pair_counts(
            pd.follows.users[valid], pd.follows.followed[valid])
        if not len(values):
            raise ValueError("no follow events with valid user ids "
                             "(mllibRatings require parity)")
        f_vocab, f_codes = assign_indices(followers)
        t_vocab, t_codes = assign_indices(followed)
        from predictionio_tpu.workflow.context import mesh_of
        mesh = mesh_of(ctx)
        n_shards = int(np.prod(mesh.devices.shape))
        data = ALSData.build(f_codes, t_codes, values,
                             len(f_vocab), len(t_vocab), n_shards)
        _solver, _block = resolved_als_solver(self.params, logger)
        _, V = train_als(mesh, data, ALSParams(
            rank=self.params.rank,
            num_iterations=self.params.num_iterations,
            reg=self.params.reg, alpha=self.params.alpha,
            implicit_prefs=True, seed=self.params.seed,
            solver=_solver, block_size=_block))
        norms = np.linalg.norm(V, axis=1, keepdims=True)
        V = V / np.where(norms == 0, 1.0, norms)
        return RecommendedUserModel(user_vocab=t_vocab, V=V, users=pd.users)

    def warmup_query(self, model: RecommendedUserModel) -> Optional[Query]:
        """Deploy warm-swap probe (deploy/warm.py shape ladder)."""
        if model is None or not len(model.user_vocab):
            return None
        return Query(users=(str(model.user_vocab[0]),), num=10)

    def predict(self, model: RecommendedUserModel,
                query: Query) -> PredictedResult:
        query_idx = {i for i in (model.user_index(u) for u in query.users)
                     if i is not None}
        if not query_idx:
            return PredictedResult(similar_user_scores=[])
        # summed cosine over ALL candidates: V is row-normalized, so the
        # reference's per-user cosine sum is one matvec V @ sum(q_vecs)
        qsum = model.V[sorted(query_idx)].sum(axis=0)
        scores = model.V @ qsum
        white = None
        if query.white_list is not None:
            white = {i for i in (model.user_index(u)
                                 for u in query.white_list) if i is not None}
        black = set()
        if query.black_list is not None:
            black = {i for i in (model.user_index(u)
                                 for u in query.black_list) if i is not None}
        order = np.argsort(-scores)
        out = []
        for idx in order:
            idx = int(idx)
            if scores[idx] <= 0:       # reference keeps score > 0 only
                break
            if idx in query_idx or idx in black:
                continue
            if white is not None and idx not in white:
                continue
            out.append(SimilarUserScore(user=str(model.user_vocab[idx]),
                                        score=float(scores[idx])))
            if len(out) >= query.num:
                break
        return PredictedResult(similar_user_scores=out)


class RecommendedUserServing(FirstServing):
    """Serving.scala parity — first prediction wins."""


# -- factory ------------------------------------------------------------------

def engine() -> Engine:
    """RecommendedUserEngine factory (Engine.scala parity)."""
    return Engine(
        data_source_classes=RecommendedUserDataSource,
        preparator_classes=RecommendedUserPreparator,
        algorithm_classes={"als": ALSAlgorithm},
        serving_classes=RecommendedUserServing,
    )


def default_engine_params(app_name: str, **algo_overrides) -> EngineParams:
    return EngineParams(
        data_source_params=DataSourceParams(app_name=app_name),
        algorithm_params_list=[("als", ALSAlgorithmParams(**algo_overrides))],
    )
