"""Session-based recommendation engine: next-item prediction over each
user's time-ordered event stream with a causal transformer
(models/seqrec.py).

The reference's nearest analog is the MarkovChain e2 component
(e2/.../engine/MarkovChain.scala:25-87) — a first-order transition matrix.
This engine family is its long-context successor on the same DASE surface:
DataSource reads view/buy events and groups them into per-user sessions;
the algorithm trains the transformer on the mesh (dp x tp sharding);
queries carry the visitor's recent items and get the top-N likely next
items back.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

from predictionio_tpu.core.base import (
    Algorithm, DataSource, FirstServing, Preparator,
)
from predictionio_tpu.core.engine import Engine
from predictionio_tpu.core.params import EngineParams, Params
from predictionio_tpu.models.seqrec import (
    SeqRecModel, SeqRecParams, train_seqrec,
)


@dataclasses.dataclass
class TrainingData:
    sessions: List[List[str]]        # per-user time-ordered item ids

    def sanity_check(self):
        if not self.sessions:
            raise ValueError(
                "No sessions found. Check the appName or import data first.")


PreparedData = TrainingData


@dataclasses.dataclass
class Query:
    items: List[str]                 # visitor's recent items, oldest first
    num: int = 10


@dataclasses.dataclass
class ItemScore:
    item: str
    score: float


@dataclasses.dataclass
class PredictedResult:
    item_scores: List[ItemScore]

    def to_dict(self):
        """Reference wire shape: {"itemScores": [{"item","score"}...]}."""
        return {"itemScores": [{"item": s.item, "score": s.score}
                               for s in self.item_scores]}


@dataclasses.dataclass
class ActualResult:
    item: str                        # the item actually chosen next


@dataclasses.dataclass
class DataSourceParams(Params):
    app_name: str
    event_names: Sequence[str] = ("view", "buy")
    eval_params: Optional[dict] = None


class SessionDataSource(DataSource):
    """Groups user->item events into per-user sessions ordered by
    eventTime (the sequence analog of DataSource.scala:39's event read).

    Multi-process note: this read is deliberately UNSHARDED — sessions
    must stay whole, and range/fragment shards (`find_columnar(shard=)`)
    would split a user's events across processes. Every host reads the
    full session set (they are small next to the model) and the train
    step shards the BATCH over the mesh's "data" axis; a partitioned
    session loader would need an exchange keyed by user (the
    parallel/shuffle.exchange_rows pattern ALS uses for segments) plus
    per-process batch assembly, which the replicated design makes
    unnecessary at current scales."""

    params_class = DataSourceParams

    def __init__(self, params: DataSourceParams):
        self.params = params

    def _read_sessions(self) -> List[List[str]]:
        from predictionio_tpu.data.ingest import (
            decoding, event_columns, sessions_by_entity, training_scan,
        )

        scan = training_scan(
            self.params.app_name,
            entity_type="user",
            event_names=list(self.params.event_names),
            target_entity_type="item",
            columns=("entity_id", "target_entity_id", "event_time_ms"))
        with decoding(self.params.app_name, scan.table):
            users, items, times = event_columns(
                scan.table, "entity_id", "target_entity_id",
                "event_time_ms")
        return sessions_by_entity(users, items, times)

    def read_training(self, ctx) -> TrainingData:
        return TrainingData(sessions=self._read_sessions())

    def read_eval(self, ctx):
        """Leave-one-out per session, k-fold over users (the SASRec eval
        protocol mapped onto readEval's fold contract)."""
        from predictionio_tpu.core.cross_validation import split_data

        ep = self.params.eval_params or {}
        k = int(ep.get("kFold", 3))
        sessions = [s for s in self._read_sessions() if len(s) >= 3]
        folds = []
        for fold, (_train_idx, test_idx) in enumerate(
                split_data(k, len(sessions))):
            held_out = set(test_idx.tolist())
            train, qa = [], []
            for i, s in enumerate(sessions):
                if i in held_out:
                    qa.append((Query(items=s[:-1],
                                     num=int(ep.get("queryNum", 10))),
                               ActualResult(item=s[-1])))
                    train.append(s[:-1])
                else:
                    train.append(s)
            folds.append((TrainingData(sessions=train), {"fold": fold}, qa))
        return folds


class SessionPreparator(Preparator):
    def prepare(self, ctx, td: TrainingData) -> PreparedData:
        return TrainingData(
            sessions=[s for s in td.sessions if len(s) >= 2])


@dataclasses.dataclass
class AlgorithmParams(SeqRecParams):
    pass


class SeqRecAlgorithm(Algorithm):
    """Transformer next-item model trained on the workflow mesh."""

    params_class = AlgorithmParams

    def __init__(self, params: Optional[AlgorithmParams] = None):
        self.params = params or AlgorithmParams()

    def train(self, ctx, pd: PreparedData) -> SeqRecModel:
        from predictionio_tpu.workflow.checkpoint import checkpointer_of
        from predictionio_tpu.workflow.context import mesh_of

        return train_seqrec(mesh_of(ctx), pd.sessions, self.params,
                            checkpointer=checkpointer_of(ctx))

    def predict(self, model: SeqRecModel, query: Query) -> PredictedResult:
        recs = model.recommend_next(query.items, query.num)
        return PredictedResult(
            item_scores=[ItemScore(item=i, score=s) for i, s in recs])


class SessionServing(FirstServing):
    pass


def engine() -> Engine:
    return Engine(
        data_source_classes=SessionDataSource,
        preparator_classes=SessionPreparator,
        algorithm_classes={"seqrec": SeqRecAlgorithm},
        serving_classes=SessionServing,
    )


def default_engine_params(app_name: str, **algo_overrides) -> EngineParams:
    return EngineParams(
        data_source_params=DataSourceParams(app_name=app_name),
        algorithm_params_list=[("seqrec", AlgorithmParams(**algo_overrides))],
    )
