"""Shared run instrumentation for the train/evaluate workflows.

One context manager owns the whole harness: run counter by outcome,
end-to-end duration histogram, and the JAX device gauges registered on
the process registry. The stages inside a run are ``obs/tracing.span``s
of the job's trace: each reaches ``pio_span_duration_seconds`` as it
closes, so a failed run has published the spans that closed before it
failed.
"""

from __future__ import annotations

import contextlib
import time

from predictionio_tpu.obs.jax_stats import register_jax_metrics
from predictionio_tpu.obs.registry import default_registry, exponential_buckets

#: 100 ms .. ~27 min doubling — training runs, not request latencies
WORKFLOW_DURATION_BUCKETS = exponential_buckets(0.1, 2.0, 15)


def persist_bytes():
    return default_registry().counter(
        "pio_train_persist_bytes_total",
        "Bytes of serialised models `pio train` wrote to the model store")


#: 1 ms .. ~33 s doubling — one sample a persist, a release of KBs to GBs
PERSIST_SECONDS_BUCKETS = exponential_buckets(0.001, 2.0, 16)


def observe_persist(size: int, streamed: bool, write_seconds: float,
                    hash_seconds: float, device_bytes: int,
                    fetch_wait_seconds: float) -> None:
    """One persisted release: its bytes, whether the pickler wrote them
    into the store's own file (the one-pass route) or into a buffer for a
    row insert, where the writing thread and the hash thread spent
    their time, how many of the bytes reached the pickler as device
    arrays and how long it waited for their host copies."""
    registry = default_registry()
    persist_bytes().inc(size)
    registry.counter(
        "pio_train_persist_streamed_bytes_total",
        "Bytes of serialised models the pickler wrote straight into the "
        "model store's file, of pio_train_persist_bytes_total"
    ).inc(size if streamed else 0)
    registry.histogram(
        "pio_train_persist_write_seconds",
        "Time the pickling thread spent inside the model store's write, "
        "one sample a persist", buckets=PERSIST_SECONDS_BUCKETS
    ).observe(write_seconds)
    registry.histogram(
        "pio_train_persist_hash_seconds",
        "Time the digest thread spent in sha256 update, one sample a "
        "persist", buckets=PERSIST_SECONDS_BUCKETS
    ).observe(hash_seconds)
    registry.counter(
        "pio_train_persist_device_bytes_total",
        "Bytes of the release that reached the pickler as device arrays, "
        "of pio_train_persist_bytes_total"
    ).inc(device_bytes)
    registry.histogram(
        "pio_train_persist_fetch_wait_seconds",
        "Time the pickling thread waited for device arrays' host copies "
        "still in flight, one sample a persist",
        buckets=PERSIST_SECONDS_BUCKETS
    ).observe(fetch_wait_seconds)


@contextlib.contextmanager
def workflow_run_metrics(workflow: str, metric_prefix: str):
    """Instrument one workflow run.

    ``workflow`` names the run in the help texts ("train"/"evaluate");
    ``metric_prefix`` names the run metrics ("pio_train" ->
    pio_train_runs_total + pio_train_duration_seconds).
    """
    registry = register_jax_metrics(default_registry())
    runs = registry.counter(f"{metric_prefix}_runs_total",
                            f"{workflow} workflow runs by outcome",
                            labelnames=("status",))
    duration = registry.histogram(
        f"{metric_prefix}_duration_seconds",
        f"End-to-end {workflow} workflow wall time by outcome",
        labelnames=("status",), buckets=WORKFLOW_DURATION_BUCKETS)
    t0 = time.perf_counter()
    try:
        yield
    except BaseException:
        runs.inc(status="failed")
        duration.observe(time.perf_counter() - t0, status="failed")
        raise
    runs.inc(status="completed")
    duration.observe(time.perf_counter() - t0, status="completed")
