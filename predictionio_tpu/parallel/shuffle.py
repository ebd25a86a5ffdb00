"""Cross-process row exchange over the device interconnect.

The reference redistributes training rows by key with a Spark shuffle
(sort-based, spilled to disk, shipped executor-to-executor). The
TPU-native answer keeps the thesis of SURVEY.md §2.9 P4 — "the shuffle
becomes an XLA collective" — for the DATA path too: each process bins its
locally-loaded rows by destination, and ONE jitted `lax.all_to_all` over
a process-spanning mesh moves every bin to its owner, riding ICI/DCN
instead of a TCP shuffle service. Combined with the storage shard readers
(`find_columnar(shard=...)`, the JDBCPEvents.scala:89-101 partition
analog) this completes the partitioned input pipeline: no process ever
materializes the full event set.

Host-object collectives (`allgather_object`) cover the tiny metadata the
exchange needs (vocabularies, row counts, digests); they ride the same
jax runtime via `jax.experimental.multihost_utils`.
"""

from __future__ import annotations

import pickle
from typing import List, Sequence, Tuple

import numpy as np


def _exchange_mesh():
    """1-axis mesh with ONE device per process (the exchange granularity
    is processes; multi-device processes just funnel through their first
    chip — the host-side bin/unbin is per-process anyway)."""
    import jax
    from jax.sharding import Mesh

    per_proc = {}
    for d in jax.devices():
        per_proc.setdefault(d.process_index, d)
    devs = [per_proc[p] for p in sorted(per_proc)]
    return Mesh(np.asarray(devs), axis_names=("proc",))


def allgather_object(obj) -> List:
    """Every process contributes one picklable object; all receive the
    list ordered by process index. Two fixed-shape device all-gathers
    (lengths, then padded bytes) — no host-side network path exists in
    the runtime, and none is needed."""
    import jax
    from jax.experimental import multihost_utils

    if jax.process_count() == 1:
        return [obj]
    payload = np.frombuffer(pickle.dumps(obj), np.uint8)
    sizes = multihost_utils.process_allgather(
        np.asarray([payload.size], np.int64))
    cap = int(sizes.max())
    padded = np.zeros(cap, np.uint8)
    padded[:payload.size] = payload
    gathered = multihost_utils.process_allgather(padded)
    return [pickle.loads(gathered[p, :int(sizes[p, 0])].tobytes())
            for p in range(jax.process_count())]


def global_vocab(local_values: np.ndarray) -> np.ndarray:
    """Sorted union of every process's local distinct values — the
    deterministic global id assignment for partitioned loads (same ids on
    every process regardless of which shard saw which entity; the
    collective replacement for BiMap.scala:126's collect-to-driver)."""
    locals_ = allgather_object(np.unique(local_values))
    return np.unique(np.concatenate(locals_))


def exchange_rows(dest: np.ndarray, payload: np.ndarray) -> np.ndarray:
    """Redistribute host rows across processes by destination.

    dest: [n] int32 destination process per row. payload: [n, k] int32
    (bitcast other 4-byte dtypes through `.view(np.int32)`). Returns the
    [m, k] rows destined to THIS process, grouped by source process and
    preserving each source's local order within the group.

    Mechanics: bin rows by dest, pad bins to the global max (the exact
    per-(source, dest) counts ride one tiny metadata all-gather and
    delimit the unbinning — padding rows are simply never sliced in),
    and run one jitted shard_map all_to_all over the process mesh.
    Single-process: a pass-through reorder.
    """
    import jax

    payload = np.ascontiguousarray(payload, np.int32)
    n, k = payload.shape
    nproc = jax.process_count()
    order = np.argsort(dest, kind="stable")
    payload_s, dest_s = payload[order], dest[order]
    starts = np.searchsorted(dest_s, np.arange(nproc + 1))
    if nproc == 1:
        return payload_s

    me = jax.process_index()
    counts = np.diff(starts)                       # rows per destination
    all_counts = np.stack(allgather_object(counts))    # [P src, P dst]
    m = int(all_counts.max())

    send = np.zeros((nproc, m, k), np.int32)
    for d in range(nproc):
        lo, hi = int(starts[d]), int(starts[d + 1])
        send[d, :hi - lo] = payload_s[lo:hi]

    recv = _all_to_all(send)                       # [P src, m, k]
    rows = []
    for s in range(nproc):
        cnt = int(all_counts[s, me])
        rows.append(recv[s, :cnt])
    out = np.concatenate(rows) if rows else np.zeros((0, k), np.int32)
    assert out.shape[0] == int(all_counts[:, me].sum())
    return out


def _all_to_all(send: np.ndarray) -> np.ndarray:
    """One lax.all_to_all step: send[d] goes to process d; returns
    recv[s] = the block process s sent here."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from predictionio_tpu.ops.fn_cache import mesh_cached_fn

    mesh = _exchange_mesh()
    nproc, m, kk = send.shape

    def build():
        from jax import shard_map

        def step(x):        # local block [1, nproc, m, kk]
            return jax.lax.all_to_all(
                x, "proc", split_axis=1, concat_axis=0)

        return jax.jit(shard_map(
            step, mesh=mesh, in_specs=P("proc"),
            out_specs=P(None, "proc"), check_vma=False))

    # cached per (mesh, shape): a per-call jit(shard_map(closure)) would
    # re-trace every exchange (the ops/fn_cache rule; Mesh hashes by
    # devices+axis names, so the freshly-built equal mesh still hits)
    run = mesh_cached_fn("shuffle_all_to_all", mesh, (nproc, m, kk), build)

    global_shape = (nproc, nproc, m, kk)
    arr = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("proc")), send[None], global_shape)
    out = run(arr)
    # each process's addressable slice of the axis-1-sharded result is
    # exactly its received blocks [nproc, 1, m, kk]
    local = [s.data for s in out.addressable_shards]
    assert len(local) == 1
    return np.asarray(local[0]).reshape(nproc, m, kk)
