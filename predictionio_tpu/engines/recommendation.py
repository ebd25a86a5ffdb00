"""Recommendation engine template (ALS).

Rebuilds examples/scala-parallel-recommendation/customize-serving (the first
judged config): rate/buy events -> Rating tuples -> blockwise ALS on the mesh
-> top-N item scores per user, with k-fold RMSE/Precision@K evaluation.

Reference parity map:
  * DataSource   <- src/main/scala/DataSource.scala:39-120 (reads "rate" and
    "buy" events; buy = implicit rating 4.0; readEval k-fold split)
  * ALSAlgorithm <- ALSAlgorithm.scala:39-155 (train:51 builds BiMaps + runs
    MLlib ALS; here ALSData + train_als on the workflow mesh)
  * ALSModel     <- ALSModel.scala:33-80 (factor matrices + id maps)
  * Serving      <- Serving.scala:29-43 (first serving)
  * Evaluation   <- Evaluation.scala:32-105 (PrecisionAtK via MetricEvaluator)

Wire format parity (quickstart): query {"user": "1", "num": 4} ->
{"itemScores": [{"item": "22", "score": 4.07}, ...]}.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import List, Optional, Sequence, Tuple

import numpy as np

from predictionio_tpu.core import (
    AverageMetric, Engine, EngineParams, FirstServing, OptionAverageMetric,
    Params, Preparator,
)
from predictionio_tpu.core.base import Algorithm, DataSource
from predictionio_tpu.data.bimap import assign_indices
from predictionio_tpu.data.eventstore import EventStoreClient
from predictionio_tpu.engines.common import resolved_als_solver
from predictionio_tpu.models.als import ALSData, ALSModel, ALSParams, train_als
from predictionio_tpu.obs.tracing import span
from predictionio_tpu.obs.train_stats import als_entities

logger = logging.getLogger("pio.engine.recommendation")


# -- data types ---------------------------------------------------------------

@dataclasses.dataclass
class Rating:
    user: str
    item: str
    rating: float


@dataclasses.dataclass
class RatingColumns:
    """Columnar view of the rating set — the RDD[Rating] analog the way a
    TPU pipeline wants it: three parallel arrays straight from the event
    store's columnar scan, no per-event Python objects."""

    users: np.ndarray    # object (string ids)
    items: np.ndarray    # object
    values: np.ndarray   # float32

    def __len__(self) -> int:
        return len(self.values)


@dataclasses.dataclass
class TrainingData:
    """Holds the rating set as rows (`ratings`, reference-API parity) or
    columns (`columns`, the training fast path) — whichever the reader
    produced; `as_columns()` converts on demand."""

    ratings: Optional[List[Rating]] = None
    columns: Optional[RatingColumns] = None

    def as_columns(self) -> RatingColumns:
        if self.columns is not None:
            return self.columns
        rs = self.ratings or []
        return RatingColumns(
            users=np.asarray([r.user for r in rs], dtype=object),
            items=np.asarray([r.item for r in rs], dtype=object),
            values=np.asarray([r.rating for r in rs], dtype=np.float32))

    def __len__(self) -> int:
        return (len(self.columns) if self.columns is not None
                else len(self.ratings or ()))


@dataclasses.dataclass
class PreparedData:
    ratings: Optional[List[Rating]] = None
    columns: Optional[RatingColumns] = None

    as_columns = TrainingData.as_columns
    __len__ = TrainingData.__len__


@dataclasses.dataclass(frozen=True)
class Query:
    """Quickstart query plus the blacklist-items variant's filters
    (examples/scala-parallel-recommendation/blacklist-items Query:
    user, num, blackList — whiteList is the natural dual, wired to the
    same model mask). JSON keys: "blackList" / "whiteList"."""

    user: str
    num: int
    black_list: Optional[Tuple[str, ...]] = None
    white_list: Optional[Tuple[str, ...]] = None


@dataclasses.dataclass
class ItemScore:
    item: str
    score: float


@dataclasses.dataclass
class PredictedResult:
    item_scores: List[ItemScore]

    def to_dict(self) -> dict:
        return {"itemScores": [{"item": s.item, "score": s.score}
                               for s in self.item_scores]}


@dataclasses.dataclass
class ActualResult:
    ratings: List[Rating]


# -- DASE components ----------------------------------------------------------

@dataclasses.dataclass
class DataSourceParams(Params):
    """Default = the customize-serving variant (rate + buy). The
    train-with-view-event variant is a config, not a fork: set
    eventNames=["view"] (+ implicitPrefs on the algorithm) and each view
    contributes eventWeights["view"] to the (user, item) preference —
    examples/scala-parallel-recommendation/train-with-view-event/
    DataSource.scala reads "view" events into implicit 1.0 ratings."""

    app_name: str
    eval_params: Optional[dict] = None  # {"kFold": 5, "queryNum": 10}
    #: which events become ratings; None = ["rate", "buy"]
    event_names: Optional[List[str]] = None
    #: rating assigned per non-"rate" event (the "rate" event always
    #: reads its rating property); None = {"buy": 4.0, "view": 1.0}
    event_weights: Optional[dict] = None


class RecommendationDataSource(DataSource):
    """DataSource.scala:39 — rate events keep their rating property; buy
    events become implicit rating 4.0 (:61-73); view events (variant)
    weight 1.0 each."""

    params_class = DataSourceParams
    DEFAULT_WEIGHTS = {"buy": 4.0, "view": 1.0}

    def __init__(self, params: DataSourceParams):
        self.params = params

    def _read_ratings(self) -> List[Rating]:
        c = self._read_columns()
        return [Rating(user=u, item=i, rating=float(v))
                for u, i, v in zip(c.users, c.items, c.values)]

    def _read_columns(self) -> RatingColumns:
        """Columnar training read (the shared ingest pipeline -> arrays),
        the JDBCPEvents-into-RDD analog without per-event objects.

        On a multi-process runtime this read is PARTITIONED exactly like
        the reference's per-executor JdbcRDD slices
        (JDBCPEvents.scala:89-101): `training_scan(sharded=True)` makes
        every process read only its shard of one collectively-agreed
        snapshot, and the downstream algorithm re-keys rows to their
        owners over the interconnect (models/als.build_distributed) — no
        process materializes the full event set."""
        from predictionio_tpu.data.columnar import property_column
        from predictionio_tpu.data.ingest import (
            decoding, event_columns, training_scan,
        )

        names = self.params.event_names or ["rate", "buy"]
        weights = {**self.DEFAULT_WEIGHTS, **(self.params.event_weights or {})}
        import jax

        scan = training_scan(
            self.params.app_name,
            sharded=True,
            entity_type="user",
            event_names=names,
            target_entity_type="item",
            ordered=False,     # rating math is permutation-invariant
            columns=("event", "entity_id", "target_entity_id",
                     "properties"))
        table = scan.table
        with decoding(self.params.app_name, table):
            events, users, items = event_columns(
                table, "event", "entity_id", "target_entity_id")
            is_rate = events == "rate"
            values = np.empty(len(events), np.float32)
            for name in set(events.tolist()):
                if name != "rate":
                    values[events == name] = float(weights.get(name, 1.0))
            if is_rate.any():
                import pyarrow as pa

                # parse ONLY the rate rows' properties (a mostly-implicit
                # event log would otherwise json-parse millions of rows whose
                # value the mask immediately discards)
                values[is_rate] = property_column(
                    table.filter(pa.array(is_rate)), "rating")
            bad = bool(np.isnan(values[is_rate]).any())
            if jax.process_count() > 1:
                # data errors live in ONE process's shard; the raise must be
                # COLLECTIVE or the erroring process dies while its peers
                # block forever in the training collectives downstream
                from predictionio_tpu.parallel.shuffle import allgather_object

                bad = any(allgather_object(bad))
            if bad:
                raise ValueError(
                    "rate event without a rating property "
                    "(DataSource.scala:66 MatchError parity)")
            # replicated fallback (backend couldn't partition): keep a
            # disjoint strided slice so the distributed build's
            # exchange-by-owner sees each rating exactly once
            users, items, values = scan.local_slice((users, items, values))
        return RatingColumns(users=users, items=items, values=values)

    def read_training(self, ctx) -> TrainingData:
        return TrainingData(columns=self._read_columns())

    def read_eval(self, ctx):
        """K-fold split via the shared helper (DataSource.scala:87-120 /
        e2 CommonHelperFunctions.splitData, core/cross_validation.py)."""
        from predictionio_tpu.core.cross_validation import k_fold

        ep = self.params.eval_params or {}
        k = int(ep.get("kFold", 3))
        ratings = self._read_ratings()
        folds = []
        for fold, (train, test) in enumerate(k_fold(ratings, k)):
            qa = [(Query(user=r.user, num=int(ep.get("queryNum", 10))),
                   ActualResult(ratings=[r]))
                  for r in test]
            folds.append((TrainingData(ratings=train), {"fold": fold}, qa))
        return folds

    def read_eval_grid(self, ctx):
        """ONE read for the whole device-batched sweep: the full rating
        columns plus fold count — the vectorized evaluator derives fold
        membership as index-mod-k mask columns (the same assignment
        `read_eval` uses) instead of materializing K data subsets."""
        from predictionio_tpu.core.evaluation import EvalGrid

        ep = self.params.eval_params or {}
        return EvalGrid(data=self._read_columns(),
                        k_fold=int(ep.get("kFold", 3)),
                        query_num=int(ep.get("queryNum", 10)))


class RecommendationPreparator(Preparator):
    """Template passthrough preparator (Preparator.scala parity)."""

    def prepare(self, ctx, td: TrainingData) -> PreparedData:
        return PreparedData(ratings=td.ratings, columns=td.columns)


@dataclasses.dataclass
class AlgorithmParams(Params):
    """ALSAlgorithm.scala params: rank, numIterations, lambda, seed."""

    json_aliases = {"lambda": "reg"}

    rank: int = 10
    num_iterations: int = 10
    reg: float = 0.01
    seed: int = 3
    implicit_prefs: bool = False
    alpha: float = 1.0
    #: training-solver selection: {"mode": "full"|"subspace",
    #: "block_size": N} — None defers to server.json "train" /
    #: PIO_ALS_SOLVER (utils/server_config.als_solver_config)
    solver: Optional[dict] = None


class ALSAlgorithm(Algorithm):
    """ALSAlgorithm.scala:39 — id assignment + ALS training on the mesh."""

    params_class = AlgorithmParams

    def __init__(self, params: Optional[AlgorithmParams] = None):
        self.params = params or AlgorithmParams()

    def train(self, ctx, pd: PreparedData) -> ALSModel:
        import jax

        n_local = len(pd)
        if jax.process_count() > 1:
            # the emptiness that matters is GLOBAL: a process whose
            # storage shard is legitimately empty must still join the
            # collectives below, not raise while its peers block
            from predictionio_tpu.parallel.shuffle import allgather_object

            n_local = sum(allgather_object(n_local))
        if not n_local:
            raise ValueError(
                "No ratings found. Check the appName or import data first "
                "(ALSAlgorithm.scala:55 empty-check parity).")

        cols = pd.as_columns()
        users, items, values = cols.users, cols.items, cols.values
        from predictionio_tpu.workflow.context import mesh_of
        mesh = mesh_of(ctx)
        if jax.process_count() > 1:
            # partitioned pipeline (P2+P4): `users`/`items` hold only this
            # process's storage shard; ids come from a collective vocab
            # union and rows reach their segment owners via one
            # all_to_all inside build_distributed
            from predictionio_tpu.models.als import build_distributed
            from predictionio_tpu.parallel.shuffle import global_vocab

            with span("train_id_assign"):
                user_vocab = global_vocab(np.asarray(users))
                item_vocab = global_vocab(np.asarray(items))
                user_codes = np.searchsorted(
                    user_vocab, users).astype(np.int32)
                item_codes = np.searchsorted(
                    item_vocab, items).astype(np.int32)
            data = build_distributed(mesh, user_codes, item_codes, values,
                                     len(user_vocab), len(item_vocab))
        else:
            with span("train_id_assign"):
                user_vocab, user_codes = assign_indices(users)
                item_vocab, item_codes = assign_indices(items)
            n_shards = int(np.prod(mesh.devices.shape))
            data = ALSData.build(user_codes, item_codes, values,
                                 len(user_vocab), len(item_vocab), n_shards)
        entities = als_entities()
        entities.set(len(user_vocab), side="user")
        entities.set(len(item_vocab), side="item")
        solver, block_size = resolved_als_solver(self.params, logger)
        als_params = ALSParams(
            rank=self.params.rank,
            num_iterations=self.params.num_iterations,
            reg=self.params.reg,
            seed=self.params.seed,
            implicit_prefs=self.params.implicit_prefs,
            alpha=self.params.alpha,
            solver=solver, block_size=block_size)
        from predictionio_tpu.workflow.checkpoint import checkpointer_of

        U, V = train_als(mesh, data, als_params,
                         checkpointer=checkpointer_of(ctx))
        return ALSModel(user_vocab=user_vocab, item_vocab=item_vocab, U=U, V=V)

    def predict(self, model: ALSModel, query: Query) -> PredictedResult:
        recs = model.recommend(
            query.user, query.num,
            exclude_items=tuple(query.black_list or ()),
            allow_items=(tuple(query.white_list)
                         if query.white_list is not None else None))
        return PredictedResult(
            item_scores=[ItemScore(item=i, score=s) for i, s in recs])

    def warmup_query(self, model: ALSModel) -> Optional[Query]:
        """Deploy warm-swap probe: any known user exercises the full
        bucketed top-k scorer family (deploy/warm.py shape ladder)."""
        if model is None or not len(model.user_vocab):
            return None
        return Query(user=str(model.user_vocab[0]), num=10)

    # -- online fold-in (deploy/foldin.py) -----------------------------------
    def foldin_spec(self, model: ALSModel, engine_params):
        """Fold-in contract: the SAME event→rating mapping the training
        read uses (rate keeps its rating property; buy/view weigh per
        DataSourceParams), each event one rating row, and BOTH sides
        fold — a fresh item's row is solved from its raters against the
        updated user factors."""
        from predictionio_tpu.deploy.foldin import FoldinSpec

        ds = getattr(engine_params, "data_source_params", None)
        app_name = getattr(ds, "app_name", None)
        if model is None or not app_name:
            return None
        names = tuple(getattr(ds, "event_names", None) or ["rate", "buy"])
        weights = {**RecommendationDataSource.DEFAULT_WEIGHTS,
                   **(getattr(ds, "event_weights", None) or {})}
        return FoldinSpec(
            app_name=app_name,
            als_params=ALSParams(
                rank=self.params.rank, reg=self.params.reg,
                alpha=self.params.alpha,
                implicit_prefs=self.params.implicit_prefs,
                seed=self.params.seed),
            event_names=names, event_weights=weights,
            rate_event="rate" if "rate" in names else None,
            aggregate="rows", fold_items=True)

    def foldin_factors(self, model: ALSModel):
        from predictionio_tpu.deploy.foldin import FoldinFactors

        return FoldinFactors(user_vocab=model.user_vocab,
                             item_vocab=model.item_vocab,
                             U=model.U, V=model.V,
                             V_device=model.V_device)

    def foldin_apply(self, model: ALSModel, spec, user_rows, item_rows,
                     counts) -> ALSModel:
        from predictionio_tpu.deploy.foldin import upsert_factor_rows

        user_vocab, U = upsert_factor_rows(model.user_vocab, model.U,
                                           user_rows)
        item_vocab, V = upsert_factor_rows(model.item_vocab, model.V,
                                           item_rows)
        new = ALSModel(user_vocab=user_vocab, item_vocab=item_vocab,
                       U=U, V=V)
        # carry the resident device copy of V across the drift: the
        # V_device cache is per-instance but keyed on V's identity, so a
        # user-only fold (V unchanged) keeps serving the already-
        # uploaded array instead of re-uploading the whole catalog every
        # apply tick; an item fold changes V and re-uploads as it must
        resident = getattr(model, "_resident", None)
        if resident is not None:
            new._resident = resident
        # same discipline for the quantized scorer residency
        # (ops/scoring): keyed on V identity, so a user-only fold keeps
        # the quantized copy while an item fold REQUANTIZES the updated
        # rows on the next scored batch — which is the fold-in
        # controller's pre-swap warm drive, keeping the rebuild off the
        # serving path
        scorer_cache = getattr(model, "_scorer_cache", None)
        if scorer_cache is not None:
            new._scorer_cache = scorer_cache
        return new

    #: device metric kinds `sweep_eval` can compute
    SWEEP_KINDS = ("precision_at_k", "topn_mse", "zero")

    def sweep_eval(self, ctx, grid, algo_params_list, metric,
                   other_metrics=(), registry=None):
        """Device-batched k-fold x hyperparameter sweep (the vectorized
        `pio eval` path): every (candidate, fold) unit trains in one
        vmapped program per distinct rank over a single shared
        fold-masked data layout, and metrics are computed on device in
        batch (models/als_sweep). Returns the evaluator's sweep contract
        ({scores, details, info}) or None to decline.
        """
        import jax

        if jax.process_count() > 1:
            # multi-process reads are sharded per process; the sweep
            # builds from ONE process's view, so fall back to the
            # distributed-aware sequential path
            return None
        from predictionio_tpu.core.evaluation import sweep_kind_of

        metrics = [metric, *other_metrics]
        kinds = [sweep_kind_of(m) for m in metrics]
        if any(k not in self.SWEEP_KINDS for k in kinds):
            return None
        prec_specs = {(m.k, m.rating_threshold)
                      for m, k in zip(metrics, kinds)
                      if k == "precision_at_k"}
        if len(prec_specs) > 1:       # one rank pass per sweep
            return None

        from predictionio_tpu.core.cross_validation import fold_assignments
        from predictionio_tpu.models.als_sweep import (
            build_sweep_data, run_sweep,
        )

        cols = grid.data
        fold_of = fold_assignments(grid.k_fold, len(cols))
        with span("eval_build", registry):
            user_vocab, user_codes = assign_indices(cols.users)
            item_vocab, item_codes = assign_indices(cols.items)
            data = build_sweep_data(
                user_codes, item_codes, cols.values, fold_of,
                len(user_vocab), len(item_vocab))
        from predictionio_tpu.utils.server_config import (
            ServerConfig, als_solver_config,
        )

        # resolve the host-level train section ONCE, not per candidate —
        # als_solver_config(config=None) re-reads server.json each call
        train_cfg = ServerConfig.load().train

        def with_solver(p):
            solver, block_size = als_solver_config(
                getattr(p, "solver", None), config=train_cfg)
            return ALSParams(
                rank=p.rank, num_iterations=p.num_iterations, reg=p.reg,
                seed=p.seed, implicit_prefs=p.implicit_prefs, alpha=p.alpha,
                solver=solver, block_size=block_size)

        candidates = [with_solver(p) for p in algo_params_list]
        needs_rank = any(k in ("precision_at_k", "topn_mse") for k in kinds)
        if prec_specs:
            pk, threshold = next(iter(prec_specs))
        else:
            pk, threshold = grid.query_num, 2.0
        rank_spec = ((grid.query_num, pk, threshold)
                     if needs_rank else None)
        result = run_sweep(data, candidates, rank_metrics=rank_spec,
                           registry=registry)

        def score_of(m, c):
            kind = sweep_kind_of(m)
            if kind == "precision_at_k":
                return c.precision
            if kind == "topn_mse":
                return c.topn_mse
            return 0.0

        scores = [(score_of(metric, c),
                   [score_of(m, c) for m in other_metrics])
                  for c in result.candidates]
        details = [c.to_json_dict() for c in result.candidates]
        info = {"mode": result.mode, "compileGroups": result.n_groups,
                "batchSizes": result.batch_sizes, "kFold": grid.k_fold}
        return {"scores": scores, "details": details, "info": info}

    def batch_predict(self, model: ALSModel, queries):
        """Vectorized: one device matmul for the whole batch — the eval /
        micro-batch fast path (vs CreateServer.scala:508 serial loop)."""
        reqs = [(q.user, q.num, tuple(q.black_list or ()),
                 tuple(q.white_list) if q.white_list is not None else None)
                for _, q in queries]
        recs = model.recommend_batch(reqs)
        return [
            (i, PredictedResult(item_scores=[
                ItemScore(item=it, score=s) for it, s in r]))
            for (i, _), r in zip(queries, recs)]

    def batch_predict_columnar(self, model: ALSModel, queries):
        """Offline-throughput lane (workflow/batch_predict.py): same
        scores as `batch_predict`, returned as the JSON-ready wire dicts
        directly. A 1024-row chunk otherwise materializes ~1024 * num
        ItemScore dataclasses purely to be flattened back into dicts one
        line later — at batch-scoring rates that object churn costs more
        than the matmul. The contract: byte-identical serialized output
        to `to_dict(batch_predict(...))` (asserted by the batchpredict
        parity tests)."""
        reqs = [(q.user, q.num, tuple(q.black_list or ()),
                 tuple(q.white_list) if q.white_list is not None else None)
                for _, q in queries]
        recs = model.recommend_batch(reqs)
        return [
            (i, {"itemScores": [{"item": it, "score": s} for it, s in r]})
            for (i, _), r in zip(queries, recs)]

    def batch_predict_arrow(self, model: ALSModel, queries):
        """Fully columnar offline lane (workflow/batch_predict.py): the
        same scores as `batch_predict`, assembled as ONE arrow column of
        `columnar_wire_type()` without materializing a single per-item
        Python object — model top-k lands in flat numpy arrays
        (`recommend_batch_arrays`) that feed `ListArray.from_arrays`
        directly. Returns the column parallel to `queries` (pad rows
        included; the caller slices them off). Value-identical to the
        dict lanes — asserted by the batchpredict parity tests."""
        import pyarrow as pa

        reqs = [(q.user, q.num, tuple(q.black_list or ()),
                 tuple(q.white_list) if q.white_list is not None else None)
                for _, q in queries]
        items, scores, counts = model.recommend_batch_arrays(reqs)
        offsets = np.zeros(len(reqs) + 1, dtype=np.int32)
        np.cumsum(counts, out=offsets[1:])
        struct = pa.StructArray.from_arrays(
            [pa.array(items, type=pa.string()),
             pa.array(scores, type=pa.float64())], ["item", "score"])
        lists = pa.ListArray.from_arrays(pa.array(offsets), struct)
        return pa.StructArray.from_arrays([lists], ["itemScores"])

    def columnar_wire_type(self):
        """Arrow type of the wire dicts above — lets batchpredict's
        parquet writer store predictions as a STRUCTURED column
        (list<struct<item,score>> under one struct) instead of JSON
        strings: downstream reads real columns, and writing skips the
        per-row json.dumps entirely."""
        import pyarrow as pa

        return pa.struct([("itemScores", pa.list_(pa.struct([
            ("item", pa.string()), ("score", pa.float64())])))])


class RecommendationServing(FirstServing):
    """Serving.scala:29 — first prediction wins."""


# -- metrics ------------------------------------------------------------------

class PrecisionAtK(OptionAverageMetric):
    """Evaluation.scala:32-105 — fraction of top-k that are 'positive'
    (actual rating >= threshold); None when the actual is not rateable."""

    sweep_kind = "precision_at_k"

    def __init__(self, k: int = 10, rating_threshold: float = 2.0):
        self.k = k
        self.rating_threshold = rating_threshold

    def header(self) -> str:
        return f"Precision@{self.k} (threshold={self.rating_threshold})"

    def calculate_point(self, eval_info, query: Query,
                        prediction: PredictedResult, actual: ActualResult):
        positives = {r.item for r in actual.ratings
                     if r.rating >= self.rating_threshold}
        if not positives:
            return None
        top = [s.item for s in prediction.item_scores[:self.k]]
        if not top:
            return 0.0
        return len(positives & set(top)) / min(self.k, len(top))


class RMSEMetric(AverageMetric):
    """Held-out squared error of the predicted rating for (user, item)."""

    smaller_is_better = True
    sweep_kind = "topn_mse"

    def header(self) -> str:
        return "MSE (sqrt for RMSE)"

    def calculate_point(self, eval_info, query, prediction, actual):
        # prediction carries item scores; use the actual pair's score if
        # present else 0 (cold item)
        by_item = {s.item: s.score for s in prediction.item_scores}
        errs = []
        for r in actual.ratings:
            errs.append((by_item.get(r.item, 0.0) - r.rating) ** 2)
        return float(np.mean(errs)) if errs else 0.0


# -- factory ------------------------------------------------------------------

def engine() -> Engine:
    """EngineFactory (Engine.scala:41-49 template parity)."""
    return Engine(
        data_source_classes=RecommendationDataSource,
        preparator_classes=RecommendationPreparator,
        algorithm_classes={"als": ALSAlgorithm},
        serving_classes=RecommendationServing,
    )


def default_engine_params(app_name: str, **algo_overrides) -> EngineParams:
    return EngineParams(
        data_source_params=DataSourceParams(app_name=app_name),
        algorithm_params_list=[("als", AlgorithmParams(**algo_overrides))],
    )
