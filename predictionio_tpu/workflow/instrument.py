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
import dataclasses
import time
from typing import Dict, Optional, Tuple

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


#: 1 MB .. 262 GB, x4 — what the store's earlier writes left unwritten
DIRTY_BYTES_BUCKETS = exponential_buckets(1e6, 4.0, 10)

MEMINFO = "/proc/meminfo"


def process_faults() -> Optional[Tuple[int, int]]:
    """(bytes of the pages this process has touched for the first time,
    its major faults) so far, every thread's: ``getrusage``; None on a
    platform without it."""
    try:
        import resource

        usage = resource.getrusage(resource.RUSAGE_SELF)
        return usage.ru_minflt * resource.getpagesize(), usage.ru_majflt
    except (ImportError, OSError):
        return None


def unwritten_bytes() -> Optional[int]:
    """``Dirty`` + ``Writeback`` of ``/proc/meminfo``: what the
    machine's earlier writes have not reached the disk with; None where
    there is no such file."""
    try:
        with open(MEMINFO) as f:
            rows = dict(line.split(":", 1) for line in f if ":" in line)
        return sum(int(rows[key].split()[0]) for key in
                   ("Dirty", "Writeback")) * 1024
    except (OSError, KeyError, ValueError, IndexError):
        return None


def pickling_parts(dump_seconds: float, leaf_wait_seconds: float, writer,
                   collector_seconds: float) -> Dict[str, float]:
    """The pickling thread's seconds of one ``persist_dump``, by span
    label: the four sums its clocks took, ``persist_walk`` as what they
    leave of the span (the pickler's walk and whatever nobody timed;
    never below 0), and the slowest single write, which is one of
    ``persist_store_write``'s calls and no sixth term of the sum."""
    parts = {"persist_leaf_wait": leaf_wait_seconds,
             "persist_put_wait": writer.put_wait_seconds,
             "persist_store_write": writer.write_seconds,
             "persist_gc": collector_seconds}
    parts["persist_walk"] = max(0.0, dump_seconds - sum(parts.values()))
    parts["persist_slowest_write"] = writer.slowest_write_seconds
    return parts


def digest_parts(writer) -> Dict[str, float]:
    """The digest thread's whole loop by span label, final once the
    writer is closed."""
    return {"persist_hash": writer.hash_seconds,
            "persist_hash_starved": writer.starved_seconds}


@dataclasses.dataclass
class PersistRecord:
    """One persisted release, as `workflow.train._persist` measured it."""

    size: int
    #: the pickler wrote into the store's own file (the one-pass route),
    #: not into a buffer for a row insert
    streamed: bool
    #: bytes that reached the pickler as device arrays
    device_bytes: int
    dump_seconds: float
    #: span label -> seconds: `pickling_parts` and `digest_parts`
    parts: Dict[str, float]
    slowest_write_bytes: int
    slowest_write_offset: int
    #: the writer's construction to the end of its close
    digest_life_seconds: float
    #: over `persist_dump`; None without `getrusage`
    faulted_bytes: Optional[int]
    major_faults: Optional[int]
    #: at `persist_dump`'s start; None without `/proc/meminfo`
    dirty_bytes: Optional[int]

    def line(self) -> str:
        """Where an operator looks after a slow `pio train`."""
        p = {k[len("persist_"):]: v for k, v in self.parts.items()}
        return (
            f"release of {self.size} bytes: persist_dump "
            f"{self.dump_seconds:.4f} s = leaf_wait {p['leaf_wait']:.4f} + "
            f"put_wait {p['put_wait']:.4f} + store_write "
            f"{p['store_write']:.4f} + gc {p['gc']:.4f} + walk "
            f"{p['walk']:.4f}; slowest write {p['slowest_write']:.4f} s "
            f"({self.slowest_write_bytes} bytes at offset "
            f"{self.slowest_write_offset}); digest thread hash "
            f"{p['hash']:.4f} + starved {p['hash_starved']:.4f} of "
            f"{self.digest_life_seconds:.4f} s; faulted "
            f"{self.faulted_bytes} bytes, major faults {self.major_faults}, "
            f"dirty at start {self.dirty_bytes} bytes")


def observe_persist(record: PersistRecord) -> None:
    """One persisted release: its bytes, whether the pickler wrote them
    into the store's own file or into a buffer for a row insert, where
    the writing thread and the hash thread spent their time, how many
    of the bytes reached the pickler as device arrays and how long it
    waited for their host copies, and the machine's state beside them.
    (The eight parts are samples of the span histogram, published by
    `_persist` where each becomes final.)"""
    registry = default_registry()
    persist_bytes().inc(record.size)
    registry.counter(
        "pio_train_persist_streamed_bytes_total",
        "Bytes of serialised models the pickler wrote straight into the "
        "model store's file, of pio_train_persist_bytes_total"
    ).inc(record.size if record.streamed else 0)
    registry.histogram(
        "pio_train_persist_write_seconds",
        "Time the pickling thread spent inside the model store's write, "
        "one sample a persist", buckets=PERSIST_SECONDS_BUCKETS
    ).observe(record.parts["persist_store_write"])
    registry.histogram(
        "pio_train_persist_hash_seconds",
        "Time the digest thread spent in sha256 update, one sample a "
        "persist", buckets=PERSIST_SECONDS_BUCKETS
    ).observe(record.parts["persist_hash"])
    registry.counter(
        "pio_train_persist_device_bytes_total",
        "Bytes of the release that reached the pickler as device arrays, "
        "of pio_train_persist_bytes_total"
    ).inc(record.device_bytes)
    registry.histogram(
        "pio_train_persist_fetch_wait_seconds",
        "Time the pickling thread waited for device arrays' host copies "
        "still in flight, one sample a persist",
        buckets=PERSIST_SECONDS_BUCKETS
    ).observe(record.parts["persist_leaf_wait"])
    if record.faulted_bytes is not None:
        registry.counter(
            "pio_train_persist_faulted_bytes_total",
            "Bytes of pages the process touched for the first time (minor "
            "faults x the page size) while persist_dump was open"
        ).inc(record.faulted_bytes)
        registry.counter(
            "pio_train_persist_major_faults_total",
            "Major page faults of the process while persist_dump was open"
        ).inc(record.major_faults)
    if record.dirty_bytes is not None:
        registry.histogram(
            "pio_train_persist_dirty_bytes",
            "Dirty + Writeback of /proc/meminfo when persist_dump opened: "
            "what the machine's earlier writes had not reached the disk "
            "with, one sample a persist", buckets=DIRTY_BYTES_BUCKETS
        ).observe(record.dirty_bytes)


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
