"""The Engine: component registries + train / eval / prepare-deploy drivers.

Parity with the reference Engine (core/.../controller/Engine.scala:82-818):
  * registries of named D/P/A/S classes with params-from-JSON      (:82-155)
  * train: instantiate -> read -> sanity -> prepare -> per-algo train (:623-726)
  * prepare_deploy: restore/retrain models for serving              (:198-282)
  * eval: k-fold x algorithms matrix with supplement/serve          (:728-818)

The reference's makeSerializableModels/Kryo machinery disappears: every model
is picklable by construction (pytrees of numpy arrays after device_get).
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

from predictionio_tpu.core import params as params_mod
from predictionio_tpu.core.base import (
    Algorithm, DataSource, PersistentModel, PersistentModelManifest, Preparator,
    SanityCheck, Serving, instantiate, load_class, params_class_of,
)
from predictionio_tpu.core.params import EngineParams, engine_params_from_json
from predictionio_tpu.obs.tracing import span

logger = logging.getLogger("pio.engine")

ClassMap = Union[type, Dict[str, type]]


def algo_model_id(instance_id: str, index: int, name: str) -> str:
    """Per-algorithm persistence key (Engine.scala:244 `id-ax-algoName`)."""
    return f"{instance_id}-ax{index}-{name}" if name else f"{instance_id}-ax{index}"


def _as_map(classes: ClassMap) -> Dict[str, type]:
    if isinstance(classes, dict):
        return dict(classes)
    return {"": classes}


def _pick(classes: Dict[str, type], name: str, what: str) -> type:
    if name in classes:
        return classes[name]
    if name == "" and len(classes) == 1:
        return next(iter(classes.values()))
    raise KeyError(f"unknown {what} name {name!r}; known: {sorted(classes)}")


def _sanity(obj: Any, what: str, skip: bool) -> None:
    """Engine.scala:650-706 — run SanityCheck when implemented."""
    if skip:
        return
    if isinstance(obj, SanityCheck):
        logger.debug("%s: running sanity check on %s", what, type(obj).__name__)
        obj.sanity_check()


@dataclasses.dataclass
class TrainResult:
    """Per-algorithm trained models plus the instantiated components."""

    models: List[Any]
    algorithms: List[Algorithm]
    serving: Serving
    engine_params: EngineParams


class Engine:
    """Engine.scala:82 — holds name->class maps for the DASE components."""

    def __init__(self,
                 data_source_classes: ClassMap,
                 preparator_classes: ClassMap,
                 algorithm_classes: ClassMap,
                 serving_classes: ClassMap):
        self.data_source_classes = _as_map(data_source_classes)
        self.preparator_classes = _as_map(preparator_classes)
        self.algorithm_classes = _as_map(algorithm_classes)
        self.serving_classes = _as_map(serving_classes)

    # -- component instantiation -------------------------------------------
    def _data_source(self, ep: EngineParams) -> DataSource:
        cls = _pick(self.data_source_classes, ep.data_source_name, "data source")
        return instantiate(cls, ep.data_source_params)

    def _preparator(self, ep: EngineParams) -> Preparator:
        cls = _pick(self.preparator_classes, ep.preparator_name, "preparator")
        return instantiate(cls, ep.preparator_params)

    def _algorithms(self, ep: EngineParams) -> List[Tuple[str, Algorithm]]:
        if not ep.algorithm_params_list:
            raise ValueError("EngineParams.algorithm_params_list must not be empty")
        out = []
        for name, algo_params in ep.algorithm_params_list:
            cls = _pick(self.algorithm_classes, name, "algorithm")
            out.append((name, instantiate(cls, algo_params)))
        return out

    def _serving(self, ep: EngineParams) -> Serving:
        cls = _pick(self.serving_classes, ep.serving_name, "serving")
        return instantiate(cls, ep.serving_params)

    # -- params parsing ------------------------------------------------------
    def engine_params_from_json(self, data: dict) -> EngineParams:
        """jValueToEngineParams parity, resolving params classes per component."""
        algo_params_classes = {
            name: params_class_of(cls)
            for name, cls in self.algorithm_classes.items()}
        # entries omitting "name" select the single algorithm (like _pick)
        if "" not in algo_params_classes and len(self.algorithm_classes) == 1:
            algo_params_classes[""] = params_class_of(
                next(iter(self.algorithm_classes.values())))
        ds_name = (data.get("datasource") or {}).get("name", "")
        prep_name = (data.get("preparator") or {}).get("name", "")
        serving_name = (data.get("serving") or {}).get("name", "")
        return engine_params_from_json(
            data,
            data_source_params_class=params_class_of(
                _pick(self.data_source_classes, ds_name, "data source")),
            preparator_params_class=params_class_of(
                _pick(self.preparator_classes, prep_name, "preparator")),
            algorithm_params_classes=algo_params_classes,
            serving_params_class=params_class_of(
                _pick(self.serving_classes, serving_name, "serving")),
        )

    # -- train (object Engine.train, Engine.scala:623) -----------------------
    def train(self, ctx, engine_params: EngineParams,
              skip_sanity_check: bool = False,
              stop_after_read: bool = False,
              stop_after_prepare: bool = False) -> TrainResult:
        data_source = self._data_source(engine_params)
        with span("train_read"):
            td = data_source.read_training(ctx)
        _sanity(td, "training data", skip_sanity_check)
        if stop_after_read:
            raise StopAfterReadInterruption(td)

        preparator = self._preparator(engine_params)
        with span("train_prepare"):
            pd = preparator.prepare(ctx, td)
        _sanity(pd, "prepared data", skip_sanity_check)
        if stop_after_prepare:
            raise StopAfterPrepareInterruption(pd)

        named_algos = self._algorithms(engine_params)
        models = []
        shared_ckpt = getattr(ctx, "checkpointer", None)
        for i, (name, algo) in enumerate(named_algos):
            logger.info("training algorithm %s (%s)",
                        name or "<default>", type(algo).__name__)
            if shared_ckpt is not None:
                # per-algorithm namespace: algorithm i must never resume
                # from algorithm j's snapshots
                ctx.checkpointer = shared_ckpt.scoped(
                    f"algo_{i}_{name or type(algo).__name__}")
            try:
                with span("train_algorithm"):
                    model = algo.train(ctx, pd)
            finally:
                if shared_ckpt is not None:
                    ctx.checkpointer = shared_ckpt
            _sanity(model, f"model of {name or type(algo).__name__}",
                    skip_sanity_check)
            models.append(model)
        return TrainResult(
            models=models,
            algorithms=[a for _, a in named_algos],
            serving=self._serving(engine_params),
            engine_params=engine_params)

    # -- model persistence (Engine.makeSerializableModels / prepareDeploy) ---
    def persist_models(self, ctx, model_id: str,
                       train_result: TrainResult) -> List[Any]:
        """Per-algo persistable representation (Engine.scala:284-311):
        model | PersistentModelManifest | None(retrain-at-deploy).

        Each algorithm gets a distinct id `<instance>-ax<i>-<name>` so
        multiple PersistentModel algorithms never collide
        (Engine.scala:244 keys custom-persisted models the same way).
        """
        out = []
        for i, ((name, algo_params), algo, model) in enumerate(zip(
                train_result.engine_params.algorithm_params_list,
                train_result.algorithms, train_result.models)):
            out.append(algo.make_persistent_model(
                ctx, algo_model_id(model_id, i, name), algo_params, model))
        return out

    def prepare_deploy(self, ctx, engine_params: EngineParams,
                       model_id: str, persisted: Sequence[Any]) -> TrainResult:
        """Engine.prepareDeploy:198 — restore each algorithm's model:
          * PersistentModelManifest -> class loader (:241-250)
          * None -> retrain from the event store (:210-228)
          * otherwise the checkpointed model itself
        """
        named_algos = self._algorithms(engine_params)
        # retrain ONLY the slots persisted as None (Engine.scala:211-227
        # reads+prepares once and calls trainBase only for the Unit slots)
        prepared = None
        if any(m is None for m in persisted):
            logger.info("some models are not persisted; retraining for deploy")
            data_source = self._data_source(engine_params)
            td = data_source.read_training(ctx)
            preparator = self._preparator(engine_params)
            prepared = preparator.prepare(ctx, td)
        models = []
        for i, ((name, algo_params), (_, algo), m) in enumerate(zip(
                engine_params.algorithm_params_list, named_algos, persisted)):
            if isinstance(m, PersistentModelManifest):
                cls = load_class(m.class_path)
                models.append(cls.load(
                    algo_model_id(model_id, i, name), algo_params, ctx))
            elif m is None:
                models.append(algo.train(ctx, prepared))
            else:
                models.append(m)
        return TrainResult(
            models=models,
            algorithms=[a for _, a in named_algos],
            serving=self._serving(engine_params),
            engine_params=engine_params)

    # -- eval (object Engine.eval, Engine.scala:728) -------------------------
    def eval(self, ctx, engine_params: EngineParams,
             skip_sanity_check: bool = True):
        """Returns [(EvalInfo, [(Q, P, A)])] per fold: train on each fold's
        training data, predict its queries through supplement/serve."""
        data_source = self._data_source(engine_params)
        eval_data = data_source.read_eval(ctx)
        preparator = self._preparator(engine_params)
        named_algos = self._algorithms(engine_params)
        serving = self._serving(engine_params)

        results = []
        for fold_idx, (td, eval_info, qa_pairs) in enumerate(eval_data):
            _sanity(td, f"fold {fold_idx} training data", skip_sanity_check)
            pd = preparator.prepare(ctx, td)
            models = [algo.train(ctx, pd) for _, algo in named_algos]
            qpa = evaluate_fold(named_algos, models, serving, qa_pairs)
            results.append((eval_info, qpa))
        return results

    def batch_eval(self, ctx, engine_params_list: Sequence[EngineParams]):
        """BaseEngine.batchEval:82 — default: eval per params."""
        return [(ep, self.eval(ctx, ep)) for ep in engine_params_list]


def evaluate_fold(named_algos, models, serving, qa_pairs):
    """The per-fold predict pipeline (Engine.scala:767-812): supplement each
    query, batch-predict per algorithm, align per query, serve.

    The reference aligns per-query predictions with zipWithUniqueId +
    union/groupByKey over RDDs (:777-794); here queries are indexed directly.
    """
    supplemented = [(i, serving.supplement(q))
                    for i, (q, _a) in enumerate(qa_pairs)]
    per_algo: List[Dict[int, Any]] = []
    for (name, algo), model in zip(named_algos, models):
        preds = dict(algo.batch_predict(model, supplemented))
        per_algo.append(preds)
    out = []
    for i, (q, a) in enumerate(qa_pairs):
        predictions = [preds[i] for preds in per_algo]
        out.append((q, serving.serve(q, predictions), a))
    return out


class StopAfterReadInterruption(Exception):
    """WorkflowParams.stopAfterRead debug stop (CreateWorkflow.scala parity)."""

    def __init__(self, training_data):
        super().__init__("stopped after read")
        self.training_data = training_data


class StopAfterPrepareInterruption(Exception):
    def __init__(self, prepared_data):
        super().__init__("stopped after prepare")
        self.prepared_data = prepared_data


class EngineFactory:
    """EngineFactory.scala:31 — a callable returning an Engine; referenced by
    dotted path in engine.json ("engineFactory")."""

    @classmethod
    def apply(cls) -> Engine:
        raise NotImplementedError

    def __call__(self) -> Engine:
        return self.apply()
