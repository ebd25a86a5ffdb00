"""Similar-product engine template (implicit ALS + cooccurrence, multi-algo).

Rebuilds examples/scala-parallel-similarproduct/multi-events-multi-algos (the
second judged config): users/items from `$set` aggregateProperties, view/like
events, three algorithms sharing one Query/PredictedResult shape:

  * ALSAlgorithm          <- ALSAlgorithm.scala:60-200 — implicit ALS on
    deduplicated view counts; predict = summed cosine similarity between the
    query items' factors and all item factors (vectorized to one MXU matmul)
  * CooccurrenceAlgorithm <- CooccurrenceAlgorithm.scala:44+ — top-N
    cooccurring items (models/cooccurrence.py)
  * LikeAlgorithm         <- LikeAlgorithm.scala — like/dislike events,
    latest event per (user, item) wins, like=+1 / dislike=-1 into implicit ALS

Query: {"items": [...], "num": N, "categories"?, "whiteList"?, "blackList"?};
result: {"itemScores": [{"item": ..., "score": ...}]}.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from predictionio_tpu.core import Engine, EngineParams, FirstServing, Params, Preparator
from predictionio_tpu.core.base import Algorithm, DataSource
from predictionio_tpu.data.bimap import assign_indices, vocab_index
from predictionio_tpu.engines.common import (
    InteractionColumns, Item, ItemScore, PredictedResult, categories_match,
    item_meta_join, resolved_als_solver,
)
from predictionio_tpu.models.als import ALSData, ALSParams, train_als
from predictionio_tpu.models.cooccurrence import CooccurrenceModel, train_cooccurrence

logger = logging.getLogger("pio.engine.similarproduct")


# -- data types ---------------------------------------------------------------

@dataclasses.dataclass
class ViewEvent:
    user: str
    item: str
    t: int


@dataclasses.dataclass
class LikeEvent:
    user: str
    item: str
    t: int
    like: bool


@dataclasses.dataclass
class TrainingData:
    users: Dict[str, dict]
    items: Dict[str, Item]
    views: InteractionColumns
    likes: InteractionColumns

    # row-object views kept for reference-API parity / inspection; the
    # algorithms consume the columns directly
    @property
    def view_events(self) -> List[ViewEvent]:
        return [ViewEvent(u, i, int(t)) for u, i, t in
                zip(self.views.users, self.views.items, self.views.times)]

    @property
    def like_events(self) -> List[LikeEvent]:
        return [LikeEvent(u, i, int(t), bool(l)) for u, i, t, l in
                zip(self.likes.users, self.likes.items, self.likes.times,
                    self.likes.likes)]


PreparedData = TrainingData


@dataclasses.dataclass(frozen=True)
class Query:
    items: Tuple[str, ...]
    num: int
    categories: Optional[Tuple[str, ...]] = None
    white_list: Optional[Tuple[str, ...]] = None
    black_list: Optional[Tuple[str, ...]] = None

    def __post_init__(self):
        object.__setattr__(self, "items", tuple(self.items))
        for f in ("categories", "white_list", "black_list"):
            v = getattr(self, f)
            if v is not None:
                object.__setattr__(self, f, tuple(v))


# -- DASE ---------------------------------------------------------------------

@dataclasses.dataclass
class DataSourceParams(Params):
    app_name: str


class SimilarProductDataSource(DataSource):
    """DataSource.scala parity: users/items from aggregated `$set`s, view
    and like events."""

    params_class = DataSourceParams

    def __init__(self, params: DataSourceParams):
        self.params = params

    def read_training(self, ctx) -> TrainingData:
        from predictionio_tpu.data.ingest import (
            aggregate_scan, decoding, event_columns, training_scan,
        )

        app = self.params.app_name
        # entity properties via the columnar $set/$unset/$delete fold
        users = {uid: dict(pm.fields) for uid, pm in
                 aggregate_scan(app, "user").items()}
        items = {iid: Item(categories=pm.get_opt("categories"))
                 for iid, pm in aggregate_scan(app, "item").items()}
        # ONE columnar scan for all three interaction kinds, split by mask
        scan = training_scan(
            app, entity_type="user",
            event_names=["view", "like", "dislike"],
            target_entity_type="item",
            columns=("event", "entity_id", "target_entity_id",
                     "event_time_ms"))
        with decoding(app, scan.table):
            events, u, i, t = event_columns(
                scan.table, "event", "entity_id", "target_entity_id",
                "event_time_ms")
        is_view = events == "view"
        return TrainingData(
            users=users, items=items,
            views=InteractionColumns(u[is_view], i[is_view], t[is_view]),
            likes=InteractionColumns(
                u[~is_view], i[~is_view], t[~is_view],
                likes=(events[~is_view] == "like")))


class SimilarProductPreparator(Preparator):
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
class SimilarityModel:
    """Item factors + metadata for cosine-similarity scoring."""

    item_vocab: np.ndarray
    V: np.ndarray                     # [n_items, K] row-normalized
    items: Dict[int, Item]

    def __getstate__(self):
        d = dict(self.__dict__)
        d.pop("_scorer_cache", None)  # quantized residency never persists
        return d

    def item_index(self, item_id: str) -> Optional[int]:
        return vocab_index(self.item_vocab, item_id)


def _candidate_ok(idx: int, items: Dict[int, Item],
                  query_idx: set, query: Query,
                  white: Optional[set], black: set) -> bool:
    """isCandidateItem parity (CooccurrenceAlgorithm.scala / ALSAlgorithm)."""
    if idx in query_idx:
        return False
    if white is not None and idx not in white:
        return False
    if idx in black:
        return False
    return categories_match(items.get(idx), query.categories)


def _score_and_filter(model: SimilarityModel, scores: np.ndarray,
                      query: Query, query_idx: set) -> PredictedResult:
    white = None
    if query.white_list is not None:
        white = {i for i in (model.item_index(x) for x in query.white_list)
                 if i is not None}
    black = set()
    if query.black_list is not None:
        black = {i for i in (model.item_index(x) for x in query.black_list)
                 if i is not None}
    order = np.argsort(-scores)
    out = []
    for idx in order:
        idx = int(idx)
        if scores[idx] <= 0:
            break
        if not _candidate_ok(idx, model.items, query_idx, query, white, black):
            continue
        out.append(ItemScore(item=str(model.item_vocab[idx]),
                             score=float(scores[idx])))
        if len(out) >= query.num:
            break
    return PredictedResult(item_scores=out)


class ALSAlgorithm(Algorithm):
    """Implicit ALS on view counts; cosine-similarity predict."""

    params_class = ALSAlgorithmParams

    def __init__(self, params: Optional[ALSAlgorithmParams] = None):
        self.params = params or ALSAlgorithmParams()

    def _ratings(self, pd: PreparedData):
        """Deduplicated view counts as (users, items, values) columns —
        the vectorized `counts[(u, i)] += 1` fold."""
        from predictionio_tpu.data.ingest import pair_counts

        return pair_counts(pd.views.users, pd.views.items)

    def train(self, ctx, pd: PreparedData) -> SimilarityModel:
        users, items, values = self._ratings(pd)
        if not len(values):
            raise ValueError("view/like events cannot be empty "
                             "(ALSAlgorithm.scala:66 require parity)")
        if not pd.items:
            raise ValueError("items cannot be empty (use $set item events)")
        user_vocab, user_codes = assign_indices(users)
        item_vocab, item_codes = assign_indices(items)
        from predictionio_tpu.workflow.context import mesh_of
        mesh = mesh_of(ctx)
        n_shards = int(np.prod(mesh.devices.shape))
        data = ALSData.build(user_codes, item_codes, values,
                             len(user_vocab), len(item_vocab), n_shards)
        _solver, _block = resolved_als_solver(self.params, logger)
        _, V = train_als(mesh, data, ALSParams(
            rank=self.params.rank, num_iterations=self.params.num_iterations,
            reg=self.params.reg, alpha=self.params.alpha,
            implicit_prefs=True, seed=self.params.seed,
            solver=_solver, block_size=_block))
        norms = np.linalg.norm(V, axis=1, keepdims=True)
        V = V / np.where(norms == 0, 1.0, norms)
        return SimilarityModel(item_vocab=item_vocab, V=V,
                               items=item_meta_join(item_vocab, pd.items))

    def warmup_query(self, model: SimilarityModel) -> Optional[Query]:
        """Deploy warm-swap probe: any catalog item drives the batched
        cosine scorer through the bucket ladder (deploy/warm.py)."""
        if model is None or not len(model.item_vocab):
            return None
        return Query(items=(str(model.item_vocab[0]),), num=10)

    def predict(self, model: SimilarityModel, query: Query) -> PredictedResult:
        query_idx = {i for i in (model.item_index(x) for x in query.items)
                     if i is not None}
        if not query_idx:
            return PredictedResult(item_scores=[])
        # summed cosine: V is row-normalized so scores = V @ sum(q_vecs)
        qsum = model.V[sorted(query_idx)].sum(axis=0)
        scores = model.V @ qsum
        return _score_and_filter(model, scores, query, query_idx)

    def batch_predict(self, model: SimilarityModel, queries):
        """Vectorized batch scorer (the query-server micro-batch path):
        B summed-cosine matvecs collapse into one [B, K] @ [K, N]
        matmul; per-query candidate filtering stays on host. The server
        hands this a bucketed, padded batch (ops/bucketing), so B is
        already shape-stable.

        Under a non-exact scorer mode (ops/scoring) the matmul +
        top-k rides the fused streaming kernel instead of materializing
        [B, N] host scores — eligible whenever no query carries the
        unbounded filters (categories / whiteList), whose rejection
        count a top-k fetch cannot bound; those queries keep the exact
        full-score path."""
        idx_sets = []
        for _, q in queries:
            idx_sets.append({i for i in (model.item_index(x)
                                         for x in q.items) if i is not None})
        rows = [b for b, qi in enumerate(idx_sets) if qi]
        out = [(i, PredictedResult(item_scores=[])) for i, _ in queries]
        if not rows:
            return out
        qsums = np.stack([model.V[sorted(idx_sets[b])].sum(axis=0)
                          for b in rows])
        fused = self._fused_batch(model, queries, rows, idx_sets, qsums)
        if fused is not None:
            for b, res in zip(rows, fused):
                out[b] = (queries[b][0], res)
            return out
        scores = qsums @ model.V.T                       # [B, N] host BLAS
        for r, b in enumerate(rows):
            i, q = queries[b]
            out[b] = (i, _score_and_filter(model, scores[r], q,
                                           idx_sets[b]))
        return out

    def _fused_batch(self, model: SimilarityModel, queries, rows,
                     idx_sets, qsums):
        """Score `rows` through the fused top-k kernel, or None when the
        batch is ineligible (exact mode, parity-demoted scorer, or a
        query whose filters need full scores). Query-item and blacklist
        exclusions are BOUNDED (at most len(items)+len(blackList) of the
        top hits can be rejected), so fetching top-(num + bound) and
        filtering on host reproduces `_score_and_filter` exactly —
        including its stop-at-nonpositive-score rule."""
        from predictionio_tpu.ops import scoring

        if scoring.holder_scorer_config(model).mode == "exact":
            return None
        extra = 0
        want_max = 0
        for b in rows:
            q = queries[b][1]
            if q.categories is not None or q.white_list is not None:
                return None
            extra = max(extra,
                        len(idx_sets[b]) + len(q.black_list or ()))
            want_max = max(want_max, q.num)
        scorer = scoring.scorer_for(model, model.V)
        if scorer is None or not scorer.active:
            return None
        n_items = len(model.item_vocab)
        k = min(want_max + extra, n_items)
        scores, idx = scorer.topk(qsums, k)
        results = []
        for r, b in enumerate(rows):
            q = queries[b][1]
            black = {i for i in (model.item_index(x)
                                 for x in (q.black_list or ()))
                     if i is not None}
            picked = []
            for t in range(idx.shape[1]):
                s = float(scores[r, t])
                if not np.isfinite(s) or s <= 0:
                    break
                i = int(idx[r, t])
                # the ONE candidate-rule definition `_score_and_filter`
                # uses — the fused and exact lanes cannot drift
                if not _candidate_ok(i, model.items, idx_sets[b], q,
                                     None, black):
                    continue
                picked.append(ItemScore(item=str(model.item_vocab[i]),
                                        score=s))
                if len(picked) >= q.num:
                    break
            results.append(PredictedResult(item_scores=picked))
        return results


class LikeAlgorithm(ALSAlgorithm):
    """LikeAlgorithm.scala parity: latest like/dislike per (user, item),
    like=+1, dislike=-1, into implicit ALS."""

    def _ratings(self, pd: PreparedData):
        from predictionio_tpu.data.ingest import latest_per_pair

        values = np.where(pd.likes.likes, 1.0, -1.0).astype(np.float32)
        return latest_per_pair(pd.likes.users, pd.likes.items,
                               pd.likes.times, values)


@dataclasses.dataclass
class CooccurrenceAlgorithmParams(Params):
    n: int = 20


@dataclasses.dataclass
class CooccurrenceEngineModel:
    model: CooccurrenceModel
    items: Dict[int, Item]


class CooccurrenceAlgorithm(Algorithm):
    params_class = CooccurrenceAlgorithmParams

    def __init__(self, params: Optional[CooccurrenceAlgorithmParams] = None):
        self.params = params or CooccurrenceAlgorithmParams()

    def train(self, ctx, pd: PreparedData) -> CooccurrenceEngineModel:
        if not len(pd.views):
            raise ValueError("view events cannot be empty")
        from predictionio_tpu.data.ingest import intern_pairs

        user_vocab, user_codes, item_vocab, item_codes = intern_pairs(
            pd.views.users, pd.views.items)
        from predictionio_tpu.workflow.context import mesh_of

        top = train_cooccurrence(user_codes, item_codes,
                                 len(user_vocab), len(item_vocab),
                                 self.params.n, mesh=mesh_of(ctx))
        model = CooccurrenceModel(item_vocab=item_vocab,
                                  top_cooccurrences=top)
        return CooccurrenceEngineModel(
            model=model, items=item_meta_join(item_vocab, pd.items))

    def warmup_query(self, m: CooccurrenceEngineModel) -> Optional[Query]:
        if m is None or not len(m.model.item_vocab):
            return None
        return Query(items=(str(m.model.item_vocab[0]),), num=10)

    def predict(self, m: CooccurrenceEngineModel, query: Query
                ) -> PredictedResult:
        similar = m.model.similar(
            list(query.items), num=query.num,
            white_list=(list(query.white_list)
                        if query.white_list is not None else None),
            black_list=(list(query.black_list)
                        if query.black_list is not None else None),
            candidate_filter=lambda idx: categories_match(
                m.items.get(idx), query.categories))
        return PredictedResult(item_scores=[
            ItemScore(item=i, score=c) for i, c in similar])

    def batch_predict(self, m: CooccurrenceEngineModel, queries):
        """Cooccurrence scoring is host-side top-list merging (microseconds
        per query) — there is nothing to vectorize, but the override opts
        the whole multi-algo engine into the query server's micro-batched
        path, where the expensive sibling (ALSAlgorithm's batched matmul)
        pays for the coalescing."""
        return [(i, self.predict(m, q)) for i, q in queries]


class SimilarProductServing(FirstServing):
    pass


def engine() -> Engine:
    """Engine.scala factory parity (multi-algo engine)."""
    return Engine(
        data_source_classes=SimilarProductDataSource,
        preparator_classes=SimilarProductPreparator,
        algorithm_classes={"als": ALSAlgorithm,
                           "cooccurrence": CooccurrenceAlgorithm,
                           "likealgo": LikeAlgorithm},
        serving_classes=SimilarProductServing,
    )


def default_engine_params(app_name: str,
                          algorithms: Sequence[str] = ("als",)) -> EngineParams:
    defaults = {"als": ALSAlgorithmParams(),
                "cooccurrence": CooccurrenceAlgorithmParams(),
                "likealgo": ALSAlgorithmParams()}
    return EngineParams(
        data_source_params=DataSourceParams(app_name=app_name),
        algorithm_params_list=[(a, defaults[a]) for a in algorithms],
    )
