"""Multi-host (multi-process) runtime initialization.

The single-controller analog of the reference's driver/executor control plane
(SURVEY.md section 2.9 P5): every host runs the same program,
`jax.distributed.initialize` wires them into one JAX runtime, and
`jax.devices()` then spans all hosts — meshes built afterwards schedule XLA
collectives over ICI within a slice and DCN across slices. Training scripts
call initialize_distributed() first (a no-op single-host).

Env contract (standard JAX):
  PIO_COORDINATOR_ADDRESS  host:port of process 0 (or JAX autodetects on TPU pods)
  PIO_NUM_PROCESSES        total process count
  PIO_PROCESS_ID           this process's index
"""

from __future__ import annotations

import logging
import os
from typing import Optional

logger = logging.getLogger("pio.distributed")

_initialized = False


def initialize_distributed(coordinator_address: Optional[str] = None,
                           num_processes: Optional[int] = None,
                           process_id: Optional[int] = None) -> None:
    """Idempotent jax.distributed.initialize with PIO_* env fallbacks.

    On TPU pods with no explicit configuration, jax autodetects topology;
    single-host runs skip initialization entirely.
    """
    global _initialized
    if _initialized:
        return
    import jax

    coordinator_address = coordinator_address or os.environ.get(
        "PIO_COORDINATOR_ADDRESS")
    num_processes = num_processes if num_processes is not None else (
        int(os.environ["PIO_NUM_PROCESSES"])
        if "PIO_NUM_PROCESSES" in os.environ else None)
    process_id = process_id if process_id is not None else (
        int(os.environ["PIO_PROCESS_ID"])
        if "PIO_PROCESS_ID" in os.environ else None)

    if coordinator_address is None and num_processes is None:
        logger.info("single-process run; jax.distributed not initialized")
        _initialized = True
        return
    if (num_processes or 0) > 1:
        _enable_cpu_collectives(jax)
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id)
    logger.info("jax.distributed initialized: process %s/%s",
                jax.process_index(), jax.process_count())
    _initialized = True


def _enable_cpu_collectives(jax) -> None:
    """Multi-process runs on the CPU backend need a cross-process
    collectives transport: without one, the first computation over a
    cross-process mesh dies with XLA's "Multiprocess computations aren't
    implemented on the CPU backend". Select jaxlib's Gloo transport
    BEFORE the backend initializes (a no-op on TPU — the flag only
    affects the CPU client)."""
    if os.environ.get("JAX_PLATFORMS", "").lower() not in ("", "cpu"):
        return
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    logger.info("CPU collectives transport: gloo")


def resolve_worker(rank: Optional[int] = None,
                   size: Optional[int] = None) -> "tuple[int, int]":
    """This process's (rank, size) under the PIO_* process contract.

    Explicit arguments win; then the ``PIO_PROCESS_ID`` /
    ``PIO_NUM_PROCESSES`` env pair (the same contract
    `initialize_distributed` reads — offline batch workers honor it
    WITHOUT requiring the collective runtime, so a `pio batchpredict`
    shard fleet is just N processes with two env vars each); then an
    already-initialized multi-process jax runtime; else (0, 1).
    """
    if rank is not None and size is not None:
        if not 0 <= rank < size:
            raise ValueError(f"worker rank {rank} outside [0, {size})")
        return rank, size
    if "PIO_NUM_PROCESSES" in os.environ:
        size = int(os.environ["PIO_NUM_PROCESSES"])
        rank = int(os.environ.get("PIO_PROCESS_ID", "0"))
        if not 0 <= rank < size:
            raise ValueError(
                f"PIO_PROCESS_ID={rank} outside [0, PIO_NUM_PROCESSES={size})")
        return rank, size
    if _initialized:
        import jax

        return jax.process_index(), jax.process_count()
    return 0, 1


def worker_env(rank: int, size: int, base: Optional[dict] = None,
               trace_context=None) -> dict:
    """The environment for spawning one shard of a fleet run: the
    ``PIO_PROCESS_ID``/``PIO_NUM_PROCESSES`` contract plus the parent's
    trace context as ``PIO_TRACE_CONTEXT`` (obs/trace_context.py), so
    one trace id spans the parent and every shard it launches. The
    parent's context defaults to whatever trace is active at call time
    (``tracing.adopt`` the parent run first); pass ``trace_context``
    explicitly to pin one."""
    if not 0 <= rank < size:
        raise ValueError(f"worker rank {rank} outside [0, {size})")
    from predictionio_tpu.obs.trace_context import child_env
    from predictionio_tpu.obs.tracing import capture_context

    ctx = trace_context if trace_context is not None else capture_context()
    env = child_env(ctx, base)
    env["PIO_PROCESS_ID"] = str(rank)
    env["PIO_NUM_PROCESSES"] = str(size)
    return env


def contiguous_range(n: int, rank: int, size: int) -> "tuple[int, int]":
    """Row range [lo, hi) owned by `rank` of `size` over `n` rows:
    contiguous, disjoint, covering, balanced to within one row (the
    JdbcRDD-style partition bounds the sharded readers use)."""
    if size <= 0 or not 0 <= rank < size:
        raise ValueError(f"bad shard ({rank}, {size})")
    base, extra = divmod(max(0, n), size)
    lo = rank * base + min(rank, extra)
    return lo, lo + base + (1 if rank < extra else 0)


def process_count() -> int:
    import jax

    return jax.process_count()


def process_index() -> int:
    import jax

    return jax.process_index()


def global_array_from_local(mesh, local: "object", axis: str = "data"):
    """Assemble a mesh-sharded global array from each process's local shard.

    The sharded event-log reader contract (SURVEY.md P2): each host loads its
    slice of the training data, and this stitches them into one global array
    sharded along `axis` without gathering to any single host.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(mesh, P(axis))
    return jax.make_array_from_process_local_data(sharding, local)
