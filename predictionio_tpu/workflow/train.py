"""The train workflow: run an engine's pipeline, checkpoint, record metadata.

Parity with CoreWorkflow.runTrain (core/.../workflow/CoreWorkflow.scala:45-102)
and the CreateWorkflow entry (CreateWorkflow.scala:136-281): an EngineInstance
row is inserted with status INIT, the engine trains on the workflow context's
mesh, models are serialized into the Models store keyed by the instance id,
and the instance is marked COMPLETED. Failed runs leave the instance INIT so
it can never be deployed (SURVEY.md section 5 failure semantics).
"""

from __future__ import annotations

import datetime as _dt
import json
import logging
import time
from typing import Optional, Tuple

from predictionio_tpu.core.engine import Engine
from predictionio_tpu.core.params import EngineParams, params_to_json
from predictionio_tpu.data.event import UTC
from predictionio_tpu.storage.base import EngineInstance
from predictionio_tpu.storage.registry import Storage
from predictionio_tpu.workflow.context import WorkflowContext, WorkflowParams
from predictionio_tpu.workflow.instrument import (
    PersistRecord, digest_parts, observe_persist, pickling_parts,
    process_faults, unwritten_bytes, workflow_run_metrics,
)
from predictionio_tpu.workflow.serialization import (
    CollectorPauses, DigestingWriter, dump_models,
)

logger = logging.getLogger("pio.workflow")


def run_train(engine: Engine,
              engine_params: EngineParams,
              engine_factory: str = "",
              engine_variant: str = "default",
              workflow_params: Optional[WorkflowParams] = None,
              ctx: Optional[WorkflowContext] = None) -> EngineInstance:
    """Returns the COMPLETED EngineInstance (raises on failure)."""
    # the whole run is one trace, opened before anything is read or
    # written: a parent pipeline (or a multi-process launcher) hands its
    # context via PIO_TRACE_CONTEXT so this train's record joins the
    # parent's trace id in the flight recorder
    from predictionio_tpu.deploy.releases import record_release
    from predictionio_tpu.obs.trace_context import record_event
    from predictionio_tpu.obs.tracing import adopt, span

    wp = workflow_params or WorkflowParams()
    model_digest, model_size = "", 0
    attrs = {"variant": engine_variant}
    with adopt("train", attrs=attrs):
        # the workflow context, the instance's row (four parameter sets as
        # JSON) and its insert, a metadata-store commit
        with span("train_begin"):
            ctx = ctx or WorkflowContext.create(
                mode="Training", batch=wp.batch, workflow_params=wp)
            instances = Storage.get_meta_data_engine_instances()
            instance = EngineInstance(
                status="INIT",
                start_time=_dt.datetime.now(tz=UTC),
                engine_id=engine_factory or type(engine).__name__,
                engine_version="1",
                engine_variant=engine_variant,
                engine_factory=engine_factory,
                batch=wp.batch,
                runtime_conf={k: str(v) for k, v in wp.runtime_conf.items()},
                data_source_params=json.dumps(
                    params_to_json(engine_params.data_source_params),
                    sort_keys=True),
                preparator_params=json.dumps(
                    params_to_json(engine_params.preparator_params),
                    sort_keys=True),
                algorithms_params=json.dumps(
                    [{"name": n, "params": params_to_json(p)}
                     for n, p in engine_params.algorithm_params_list],
                    sort_keys=True),
                serving_params=json.dumps(
                    params_to_json(engine_params.serving_params),
                    sort_keys=True),
            )
            instance_id = instances.insert(instance)
            # insert returns the generated id; don't rely on the backend
            # mutating the record in place
            instance.id = instance_id
            attrs["instance"] = instance_id   # the record is written on exit
            logger.info("EngineInstance %s created (INIT)", instance_id)

        with workflow_run_metrics("train", "pio_train"):
            # CoreWorkflow.runTrain:45 — train, persist, mark COMPLETED
            result = engine.train(
                ctx, engine_params,
                skip_sanity_check=wp.skip_sanity_check,
                stop_after_read=wp.stop_after_read,
                stop_after_prepare=wp.stop_after_prepare)

            with span("train_persist"):
                if wp.save_model:
                    with span("persist_models"):
                        persisted = engine.persist_models(ctx, instance_id,
                                                          result)
                    model_digest, model_size = _persist(instance_id,
                                                        persisted)
                    logger.info("models saved (%d bytes) for instance %s",
                                model_size, instance_id)

                instance.status = "COMPLETED"
                instance.end_time = _dt.datetime.now(tz=UTC)
                with span("persist_instance_update"):
                    instances.update(instance)
        record_event("train_completed", {
            "instance": instance_id, "variant": engine_variant})

        # register the completed instance as the variant's next release
        # (deploy/ subsystem: `pio releases` listing, warm deploys,
        # rollback lineage). Best-effort by contract — the train already
        # succeeded.
        with span("train_release"):
            record_release(
                instance,
                train_seconds=(instance.end_time - instance.start_time
                               ).total_seconds(),
                model_digest=model_digest, model_size_bytes=model_size)
    if getattr(ctx, "checkpointer", None) is not None:
        # resume is for crashed/preempted runs only: a completed run clears
        # its snapshots so the next train never resumes from stale factors
        ctx.checkpointer.clear()
    logger.info("training completed: instance %s", instance_id)
    return instance


def _persist(instance_id: str, models) -> Tuple[str, int]:
    """Write the release in one pass: the pickler writes into the model
    store's writable (its own temporary file on a file store, a buffer
    for the row insert elsewhere) through a writer that takes the sha256
    of the same bytes beside the write, taking each device array's host
    copy as it reaches it. Returns (digest, size) of exactly what the
    store now holds; the blob is visible only once this returns.

    Its parts are spans of their own, so that each train shows them
    (`persist_dump`: the pickler and the write; `persist_close`: the hash
    thread's join and, where the store handed out its own file, that
    file's close, the flush to the mount; `persist_commit`, in the
    store: the rename or the row's insert).

    `persist_dump` is accounted from inside, by the thread that spends
    the seconds (`instrument.pickling_parts`, `digest_parts`): eight
    sums a persist, each one sample of the span histogram and one row of
    the train's record (`tracing.timed_stage`), the pickling thread's
    under `persist_dump`, the digest thread's under `train_persist` once
    the join has made them final. None is a `span()`: the device is idle
    from one end of `persist_dump` to the other, and an annotation a
    buffer, or any on the digest thread, would cut that one gap of a
    capture into hundreds."""
    from predictionio_tpu.obs.tracing import span, timed_stage

    store = Storage.get_model_data_models()
    pauses = CollectorPauses()
    with store.open_write(instance_id) as f:
        with DigestingWriter(f, pauses) as out:
            # the machine, read at each end of the span and outside it
            faults, dirty = process_faults(), unwritten_bytes()
            with span("persist_dump"):
                t0 = time.perf_counter()
                with pauses:
                    fetched = dump_models(models, out, pauses)
                dump_seconds = time.perf_counter() - t0
                parts = pickling_parts(dump_seconds, fetched.wait_seconds,
                                       out, pauses.seconds)
                for name, seconds in parts.items():
                    timed_stage(name, seconds)
            faulted = major = None
            if faults is not None:
                faulted, major = (b - a for a, b in
                                  zip(faults, process_faults()))
            with span("persist_close"):
                out.close()
                if store.streams_writes:
                    # the store closes it again on its way out: a no-op
                    f.close()
    # the digest thread's sums are final since the join
    for name, seconds in digest_parts(out).items():
        timed_stage(name, seconds)
        parts[name] = seconds
    record = PersistRecord(
        size=out.size, streamed=store.streams_writes,
        device_bytes=fetched.device_bytes, dump_seconds=dump_seconds,
        parts=parts, slowest_write_bytes=out.slowest_write_bytes,
        slowest_write_offset=out.slowest_write_offset,
        digest_life_seconds=out.life_seconds,
        faulted_bytes=faulted, major_faults=major, dirty_bytes=dirty)
    observe_persist(record)
    logger.info("%s", record.line())
    return out.hexdigest(), out.size


def load_for_deploy(engine: Engine, instance: EngineInstance,
                    ctx: Optional[WorkflowContext] = None):
    """Restore a TrainResult for serving from a COMPLETED instance
    (CreateServer.scala:204-206 + Engine.prepareDeploy:198)."""
    from predictionio_tpu.workflow.serialization import deserialize_models

    ctx = ctx or WorkflowContext.create(mode="Serving", batch=instance.batch)
    engine_params = engine_params_of_instance(engine, instance)
    model = Storage.get_model_data_models().get(instance.id)
    persisted = deserialize_models(model.models) if model else \
        [None] * len(engine_params.algorithm_params_list)
    return engine.prepare_deploy(ctx, engine_params, instance.id, persisted), ctx


def engine_params_of_instance(engine: Engine,
                              instance: EngineInstance) -> EngineParams:
    """EngineInstance params JSON -> EngineParams
    (Engine.engineInstanceToEngineParams:420 parity)."""
    data = {
        "datasource": {"params": json.loads(instance.data_source_params or "{}")},
        "preparator": {"params": json.loads(instance.preparator_params or "{}")},
        "algorithms": json.loads(instance.algorithms_params or "[]"),
        "serving": {"params": json.loads(instance.serving_params or "{}")},
    }
    return engine.engine_params_from_json(data)
