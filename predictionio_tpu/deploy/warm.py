"""Warm swap: pre-compile a release's serving shapes before cutover.

XLA compiles one executable per distinct input shape, and a factorization
model's compiles are exactly the kind too expensive to pay on the serving
path (ALX, arXiv:2112.02194). A cold ``/reload`` therefore stalls the
first post-swap batches behind fresh compiles — at every shape in the
``ops/bucketing`` ladder. The warm path instead:

  1. **load** — deserialize the release into a :class:`ServingUnit` on a
     background thread (the incumbent keeps serving).
  2. **warmup** — drive the unit's full batch-predict path (pad rules and
     all) once per reachable bucket shape, so every jitted scorer family
     registers its executables pre-cutover, and the ``_vectorized``
     capability flag is computed fresh for the unit.
  3. **verify** — one real scoring must succeed before the unit may take
     traffic.
  4. **swap** — the server replaces its active unit in ONE reference
     assignment; in-flight batches keep the unit they were routed to, so
     no request ever observes a half-swapped (result, vectorized) pair.

Each phase is timed into ``pio_deploy_phase_duration_seconds{phase=...}``
and traced as a ``deploy_*`` span.
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Any, Callable, List, Optional, Sequence

from predictionio_tpu.obs.jax_stats import compile_counter
from predictionio_tpu.obs.registry import MetricsRegistry, default_registry
from predictionio_tpu.ops.bucketing import bucket_size
from predictionio_tpu.storage.base import EngineInstance, Release

logger = logging.getLogger("pio.deploy")


class FoldinSwapRaced(Exception):
    """A fold-in drift lost the cutover race: the serving unit changed
    (reload/deploy/rollback/canary) between the solve's snapshot and the
    swap. The apply requeues its deltas and the next tick folds them
    onto whatever is live — never silently reverting a real deploy."""


class DeployError(Exception):
    """A release failed to become servable (load/warmup/verify)."""


@dataclasses.dataclass
class ServingUnit:
    """One resident, servable release: everything a query needs bundled
    into a single object so a swap is one atomic reference assignment.

    ``vectorized`` is computed once per unit (the per-request walk the
    query server used to cache separately — keeping it inside the unit is
    what makes a half-swapped (result, _vectorized) pair unrepresentable).
    ``batcher`` is attached by the query server when the unit goes live.
    """

    instance: EngineInstance
    result: Any                        # core.engine.TrainResult
    ctx: Any
    vectorized: bool
    release: Optional[Release] = None
    batcher: Any = None
    #: the pre-fold-in BASE unit when this unit is an online fold-in
    #: drift of it (deploy/foldin.py): kept resident so rollback
    #: restores pre-fold-in answers instantly, however many applies
    #: have stacked since the real deploy
    foldin_of: Optional["ServingUnit"] = None
    #: factor rows folded into this unit since its base was deployed
    foldin_rows: int = 0

    @property
    def release_version(self) -> int:
        return self.release.version if self.release else 0


def _compute_vectorized(result) -> bool:
    """Micro-batching pays only when EVERY algorithm overrides
    batch_predict (same rule as the query server has always applied)."""
    from predictionio_tpu.core.base import Algorithm

    return bool(result.algorithms) and all(
        type(a).batch_predict is not Algorithm.batch_predict
        for a in result.algorithms)


def build_unit(engine, instance: EngineInstance,
               release: Optional[Release] = None,
               ctx: Optional[Any] = None) -> ServingUnit:
    """Deserialize a COMPLETED instance into a ServingUnit (the load
    phase — runs on a background thread, off the serving loop)."""
    from predictionio_tpu.workflow.train import load_for_deploy

    result, ctx = load_for_deploy(engine, instance, ctx=ctx)
    return ServingUnit(instance=instance, result=result, ctx=ctx,
                       vectorized=_compute_vectorized(result),
                       release=release)


def resolve_warmup_query(result, explicit: Optional[Any] = None):
    """The query the shape ladder drives: an explicit one (operator-
    provided or the last query served) wins; otherwise the first
    algorithm that can synthesize one from its model
    (``Algorithm.warmup_query``) supplies it."""
    if explicit is not None:
        return explicit
    for algo, model in zip(result.algorithms, result.models):
        try:
            q = algo.warmup_query(model)
        except Exception:
            logger.exception("warmup_query failed on %s", type(algo).__name__)
            continue
        if q is not None:
            return q
    return None


@dataclasses.dataclass
class WarmupReport:
    """What the warmup pass actually exercised (surfaced by
    /deploy/status.json and asserted by the swap tests)."""

    buckets: List[int] = dataclasses.field(default_factory=list)
    queries: int = 0
    compile_delta: int = 0          # executables built DURING warmup
    seconds: float = 0.0
    skipped: Optional[str] = None   # reason when nothing could be warmed

    def to_dict(self) -> dict:
        return {"buckets": self.buckets, "queries": self.queries,
                "compileDelta": self.compile_delta,
                "seconds": round(self.seconds, 6), "skipped": self.skipped}


def _total_compiles() -> float:
    c = compile_counter(default_registry())
    return sum(v for _labels, v in c.samples())


def warmup_ladder(max_batch: int) -> List[int]:
    """The distinct bucketed batch sizes a batcher capped at `max_batch`
    can ever hand a scorer — each must be compiled before cutover."""
    out, b = [], 1
    while b < max_batch:
        out.append(b)
        b <<= 1
    out.append(bucket_size(max_batch, max_batch))
    return sorted(set(out))


def warmup_unit(unit: ServingUnit,
                predict_batch: Callable[[Sequence[Any]], List[Any]],
                max_batch: int,
                query: Optional[Any] = None) -> WarmupReport:
    """Drive `predict_batch` (the unit's full serving batch path — pad
    rules, supplement, serve) once per reachable bucket shape.

    Results are discarded; what matters is the side effect: every jitted
    scorer family compiles its per-bucket executables NOW, on the warmup
    thread, instead of under the first post-cutover traffic. Per-query
    failures inside a rung are tolerated (the verify phase is the
    health gate); a rung that fails wholesale aborts with DeployError.
    """
    report = WarmupReport()
    t0 = time.perf_counter()
    q = resolve_warmup_query(unit.result, query)
    if q is None:
        report.skipped = "no_warmup_query"
        report.seconds = time.perf_counter() - t0
        return report
    if not unit.vectorized:
        # the per-request path has no shape ladder to pre-compile; one
        # scoring still smoke-tests deserialization + imports
        report.skipped = "not_vectorized"
    compiles_before = _total_compiles()
    for b in ([1] if report.skipped else warmup_ladder(max_batch)):
        try:
            out = predict_batch([q] * b)
        except Exception as e:
            raise DeployError(f"warmup failed at batch size {b}: {e!r}") from e
        report.buckets.append(b)
        report.queries += b
        if out and all(isinstance(r, Exception) for r in out):
            raise DeployError(
                f"warmup batch of {b} failed wholesale: {out[0]!r}")
    report.compile_delta = int(_total_compiles() - compiles_before)
    report.seconds = time.perf_counter() - t0
    return report


def verify_unit(unit: ServingUnit,
                predict_batch: Callable[[Sequence[Any]], List[Any]],
                query: Optional[Any] = None) -> None:
    """Health gate: one real scoring through the unit's serving path must
    produce a non-error result before the unit may take traffic."""
    q = resolve_warmup_query(unit.result, query)
    if q is None:
        logger.warning("verify skipped: no warmup query for instance %s",
                       unit.instance.id)
        return
    out = predict_batch([q])
    if not out or isinstance(out[0], Exception):
        err = out[0] if out else RuntimeError("empty result")
        raise DeployError(f"verify query failed: {err!r}")


# ---------------------------------------------------------------------------
# pio_deploy_* metric handles
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class DeployMetrics:
    phase_hist: Any       # pio_deploy_phase_duration_seconds{phase}
    swap_total: Any       # pio_deploy_swap_total{mode, outcome}
    rollback_total: Any   # pio_deploy_rollback_total{reason}
    promote_total: Any    # pio_deploy_promote_total{reason}
    requests_total: Any   # pio_deploy_requests_total{role}
    canary_fraction: Any  # pio_deploy_canary_fraction gauge
    canary_splitter_acc: Any  # pio_deploy_canary_splitter_acc gauge
    active_version: Any   # pio_deploy_active_release_version gauge
    warmup_shapes: Any    # pio_deploy_warmup_shapes_total counter


def deploy_metrics(registry: Optional[MetricsRegistry] = None
                   ) -> DeployMetrics:
    """Get-or-create the deploy metric family on `registry` (idempotent;
    OBSERVABILITY.md documents each)."""
    reg = registry or default_registry()
    return DeployMetrics(
        phase_hist=reg.histogram(
            "pio_deploy_phase_duration_seconds",
            "Wall time of each deploy phase (load/warmup/verify/swap/drain)",
            labelnames=("phase",)),
        swap_total=reg.counter(
            "pio_deploy_swap_total",
            "Release cutovers by mode (warm/cold) and outcome",
            labelnames=("mode", "outcome")),
        rollback_total=reg.counter(
            "pio_deploy_rollback_total",
            "Rollbacks by trigger (slo_latency/slo_errors/operator)",
            labelnames=("reason",)),
        promote_total=reg.counter(
            "pio_deploy_promote_total",
            "Canary promotions by trigger (healthy/operator)",
            labelnames=("reason",)),
        requests_total=reg.counter(
            "pio_deploy_requests_total",
            "Queries routed per serving role during a staged rollout",
            labelnames=("role",)),
        canary_fraction=reg.gauge(
            "pio_deploy_canary_fraction",
            "Traffic fraction currently routed to the canary (0 = none)"),
        canary_splitter_acc=reg.gauge(
            "pio_deploy_canary_splitter_acc",
            "Canary splitter's error-diffusion accumulator — persisted "
            "through the telemetry store so a restarted server resumes "
            "the exact mid-stream split instead of re-seeding at zero"),
        active_version=reg.gauge(
            "pio_deploy_active_release_version",
            "Release version currently serving full traffic (0 = unversioned)"),
        warmup_shapes=reg.counter(
            "pio_deploy_warmup_shapes_total",
            "Bucket shapes driven through warmup passes"),
    )
