"""Item cooccurrence counting for similar-product recommendation.

TPU-native replacement for the reference CooccurrenceAlgorithm's Spark
self-join (examples/scala-parallel-similarproduct/multi-events-multi-algos/
src/main/scala/CooccurrenceAlgorithm.scala:71-105): distinct (user, item)
pairs -> per-item-pair counts -> top-N per item.

Design: counting cooccurrences is C = A^T A for the binary user x item
interaction matrix. When A fits the device budget:

* A is scattered on the HOST as uint8 (numpy fancy indexing —
  microseconds; the r2 version used XLA `.at[u,i].set` and lost to
  numpy 0.59x because a big one-hot scatter is a terrible XLA op),
  shipped once and kept device-resident (ops/device_cache), and widened
  on device: bf16 on the MXU (0/1 exact, f32 accumulation, exact below
  2^24), f32 on CPU.
* C's ROW BLOCKS are sharded over the mesh's first axis via shard_map:
  device d assembles full-width A with ONE on-device all_gather (riding
  ICI/DCN — this also serves multi-process meshes), then computes its
  block C[block_d, :] = A[:, block_d]^T @ A in 512-row SLABS, reducing
  each slab to its per-row top-N immediately. Neither the full
  [n_items, n_items] count matrix nor even one device's whole block
  ever materializes — the item-space ceiling is O(nu * ni) HBM, not
  O(ni^2) (SURVEY.md §2.9 P1/P4: the Spark self-join becomes a sharded
  slab matmul + top-k).

Item spaces past the HBM budget fall back to vectorized host counting
over sorted per-user pair enumeration (the same work the Spark join
materializes, without the shuffle).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Tuple

import numpy as np

from predictionio_tpu.data.bimap import vocab_index
from predictionio_tpu.obs.tracing import span
from predictionio_tpu.utils.device import memory_limit_bytes, on_tpu

#: max dense A entries before falling back to host counting (f32 ~2GB)
#: off-TPU. Not re-measured since it was set.
DENSE_BUDGET = 500_000_000
#: share of each TPU chip's own reported memory limit the slabbed kernel
#: may plan for (the rest is XLA workspace). The dominant term is the
#: REPLICATED bf16 all-gather of A on every chip — it does not shard,
#: so the budget must not scale with device count. On a 16 GB chip this
#: covers similarproduct at the ML-20M shape (138k x 27k: ~11.3GB/chip).
DEVICE_HBM_SHARE = 0.75
#: kernel slab height (rows of the count block materialized at once)
KERNEL_SLAB = 512


def distinct_pairs(user_idx: np.ndarray, item_idx: np.ndarray
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """De-duplicate (user, item) events (the reference's .distinct())."""
    combined = user_idx.astype(np.int64) * (item_idx.max() + 1 if item_idx.size else 1) \
        + item_idx.astype(np.int64)
    _, keep = np.unique(combined, return_index=True)
    return user_idx[keep], item_idx[keep]


def cooccurrence_topn(mesh, user_idx: np.ndarray, item_idx: np.ndarray,
                      n_users: int, n_items: int, n_top: int
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """Top-N cooccurrence (counts [n_items, k], item idx [n_items, k])
    via the sharded MXU matmul described in the module docstring. Rows
    with fewer than k nonzero cooccurrents pad with count 0 (filter on
    count > 0). k = min(n_top, n_items)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    axis = mesh.axis_names[0]
    # shard_map below shards over the FIRST mesh axis only (other axes
    # replicate), so block geometry must follow that axis's size — the
    # total device count mis-addresses the diagonal on multi-axis meshes
    n_shards = int(mesh.shape[axis])
    k = int(min(n_top, n_items))

    if int(np.prod(mesh.devices.shape)) == 1 and jax.default_backend() == "cpu":
        # single-device CPU fallback: BLAS syrk exploits the symmetry of
        # A^T A (half the FLOPs); XLA lowers it to a generic gemm and
        # loses 2x. The dispatch-aware backend pick mirrors the serving
        # path (models/als.py _use_host).
        from predictionio_tpu.ops.topk import host_topk

        a = np.zeros((n_users, n_items), np.float32)
        a[user_idx, item_idx] = 1.0
        c = a.T @ a
        np.fill_diagonal(c, 0.0)
        return host_topk(c, k)

    # pad items to a multiple of 128 lanes x shard count: zero columns
    # count nothing and padded rows are sliced off after the gather
    blk = -(-n_items // (128 * n_shards)) * 128
    ni_pad = blk * n_shards

    def _put_incidence():
        # build uint8 on host (quarter the f32 bytes over the host->device
        # link) — the kernel widens to the compute dtype on device, where
        # the cast fuses into the matmul read for free
        with span("incidence_build"):
            a = np.zeros((n_users, ni_pad), np.uint8)
            a[user_idx, item_idx] = 1
        with span("incidence_transfer"):
            a_dev = jax.device_put(a, NamedSharding(mesh, P(None, axis)))
            jax.block_until_ready(a_dev)
        return a_dev

    # resident across calls keyed on the pair arrays: eval sweeps and
    # warm/timed reruns over the same interactions upload A once
    # (ops/device_cache — the ALSData.put rule for ad-hoc inputs)
    from predictionio_tpu.ops import device_cache

    # the hashable Mesh itself keys the layout — id(mesh) could alias
    # after the mesh is GC'd (the fn_cache.py rule)
    a_dev = device_cache.resident(
        [user_idx, item_idx],
        ("cooc_a", mesh, axis, n_users, ni_pad), _put_incidence)
    run = _sharded_topn_fn(mesh, axis, n_shards, blk, ni_pad, k)
    vals, idx = jax.device_get(run(a_dev))
    return np.asarray(vals)[:n_items], np.asarray(idx)[:n_items]


def _sharded_topn_fn(mesh, axis: str, n_dev: int, blk: int, ni_pad: int,
                     k: int):
    """Compiled sharded count+topk fn, cached per (mesh, shape params) —
    a per-call jit wrapper would re-trace every fold of an eval sweep."""
    from predictionio_tpu.ops.fn_cache import mesh_cached_fn

    def build():
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from jax.sharding import NamedSharding

        # uint8 A widens on device: bf16 on the MXU (0/1 exact, f32
        # accumulate), f32 on CPU where XLA emulates bf16 matmuls slowly
        cdt = jnp.bfloat16 if on_tpu() else jnp.float32
        # row-SLAB the count block: the full [blk, ni_pad] C block would
        # put an O(n_items^2 / n_dev) buffer in HBM (2.9GB at ML-20M's
        # 27k items on one chip); slabs of 512 rows reduce the count to
        # top-k immediately, so HBM holds only A and a [512, ni_pad]
        # slab — the item-space ceiling becomes O(nu * ni), not O(ni^2).
        # Small blocks (f32 C block <= 256MB, e.g. the ML-1M shape) keep
        # the single-matmul fast path: one big MXU dispatch, no loop.
        slab = blk if blk * ni_pad * 4 <= (1 << 28) else min(KERNEL_SLAB, blk)
        n_slabs = -(-blk // slab)
        blk_pad = n_slabs * slab

        def block(a_cols):
            # a_cols [nu, blk]: this device's item column block; the full
            # width is assembled on-device by ONE all-gather riding
            # ICI/DCN — no host ever feeds a replicated copy, which also
            # makes the same kernel serve multi-process meshes
            a_full = jax.lax.all_gather(
                a_cols.astype(cdt), axis, axis=1, tiled=True)
            row0 = jax.lax.axis_index(axis) * blk
            cols = jnp.arange(ni_pad)[None, :]
            a_pad = jnp.pad(a_cols, ((0, 0), (0, blk_pad - blk)))

            def one_slab(j):
                sl = jax.lax.dynamic_slice(
                    a_pad, (0, j * slab), (a_pad.shape[0], slab))
                c = jnp.dot(sl.T.astype(cdt), a_full,
                            preferred_element_type=jnp.float32)
                rows = row0 + j * slab + jnp.arange(slab)[:, None]
                c = jnp.where(rows == cols, 0.0, c)      # zero diagonal
                # padded slab rows (rows >= row0+blk) only ever produce
                # zeros: their a_pad columns are zero
                return jax.lax.top_k(c, k)
            vals, idx = jax.lax.map(one_slab, jnp.arange(n_slabs))
            return (vals.reshape(1, blk_pad, k)[:, :blk],
                    idx.reshape(1, blk_pad, k)[:, :blk])

        sharded = shard_map(
            block, mesh=mesh,
            in_specs=P(None, axis),
            out_specs=(P(axis, None, None), P(axis, None, None)),
            check_vma=False)

        # replicated output: every process can device_get the full top-N
        # (multi-host safe); on one process the final gather is free
        @functools.partial(
            jax.jit, out_shardings=NamedSharding(mesh, P()))
        def run(a_dev):
            vals, idx = sharded(a_dev)
            return (vals.reshape(ni_pad, k), idx.reshape(ni_pad, k))

        return run

    return mesh_cached_fn("cooccurrence_topn", mesh,
                          (axis, blk, ni_pad, k), build)


def cooccurrence_topn_distributed(mesh, local_user_idx: np.ndarray,
                                  local_item_idx: np.ndarray,
                                  n_users: int, n_items: int, n_top: int
                                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Multi-process top-N cooccurrence from PER-PROCESS event shards.

    Each process passes only the (user, item) pairs its own storage shard
    produced (`find_columnar(shard=...)`); pairs are re-keyed to their
    item-column-block owners by one `lax.all_to_all`
    (parallel/shuffle.py), de-duplicated locally, and each process builds
    + commits only ITS column block of the incidence matrix. The same
    sharded matmul kernel then runs with the full-width gather riding the
    interconnect. No process ever materializes the global pair set or the
    full incidence matrix — the Spark distinct+self-join as collectives.
    """
    import jax
    import jax.numpy as jnp  # noqa: F401  (backend probe inside kernel)
    from jax.sharding import NamedSharding, PartitionSpec as P

    from predictionio_tpu.models.als import _process_shard_range
    from predictionio_tpu.parallel.shuffle import exchange_rows

    axis = mesh.axis_names[0]
    n_shards = int(mesh.shape[axis])
    assert n_shards == int(np.prod(mesh.devices.shape)), (
        "distributed cooccurrence requires a 1-axis mesh")
    k = int(min(n_top, n_items))
    blk = -(-n_items // (128 * n_shards)) * 128
    ni_pad = blk * n_shards

    lo, hi = _process_shard_range(mesh)
    shards_per_proc = hi - lo
    # owner read off the mesh (not arithmetic — uneven devices-per-
    # process or non-ascending process order would mis-route rows)
    proc_of_shard = np.asarray(
        [d.process_index for d in mesh.devices.flat], np.int32)
    dest = proc_of_shard[np.minimum(
        local_item_idx.astype(np.int64) // blk, n_shards - 1)]
    payload = np.stack([np.ascontiguousarray(local_user_idx, np.int32),
                        np.ascontiguousarray(local_item_idx, np.int32)],
                       axis=1)
    mine = exchange_rows(dest, payload)
    # global dedup is now local: every copy of a pair landed here
    u, i = distinct_pairs(mine[:, 0], mine[:, 1]) if len(mine) else (
        mine[:, 0], mine[:, 1])
    assert i.size == 0 or (i.min() >= lo * blk and i.max() < hi * blk), (
        "exchange delivered items outside this process's column range")

    a_local = np.zeros((n_users, shards_per_proc * blk), np.uint8)
    a_local[u, i - lo * blk] = 1
    a_dev = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P(None, axis)), a_local, (n_users, ni_pad))
    run = _sharded_topn_fn(mesh, axis, n_shards, blk, ni_pad, k)
    vals, idx = jax.device_get(run(a_dev))
    return np.asarray(vals)[:n_items], np.asarray(idx)[:n_items]


def cooccurrence_topn_host(user_idx: np.ndarray, item_idx: np.ndarray,
                           n_items: int, n: int) -> Dict[int, List[Tuple[int, int]]]:
    """Host fallback: enumerate per-user item pairs vectorized, count, top-N."""
    order = np.argsort(user_idx, kind="stable")
    u_s, i_s = user_idx[order], item_idx[order]
    # pair enumeration per user: for each user's item list, all i1 < i2 combos
    pairs: Dict[Tuple[int, int], int] = {}
    start = 0
    while start < len(u_s):
        end = start
        while end < len(u_s) and u_s[end] == u_s[start]:
            end += 1
        items = np.sort(i_s[start:end])
        if len(items) > 1:
            i1, i2 = np.triu_indices(len(items), k=1)
            for a, b in zip(items[i1], items[i2]):
                if a != b:
                    pairs[(int(a), int(b))] = pairs.get((int(a), int(b)), 0) + 1
        start = end
    top: Dict[int, List[Tuple[int, int]]] = {}
    for (a, b), c in pairs.items():
        top.setdefault(a, []).append((b, c))
        top.setdefault(b, []).append((a, c))
    return {k: sorted(v, key=lambda x: -x[1])[:n] for k, v in top.items()}


def train_cooccurrence(user_idx: np.ndarray, item_idx: np.ndarray,
                       n_users: int, n_items: int, n: int, mesh=None
                       ) -> Dict[int, List[Tuple[int, int]]]:
    """Top-N cooccurring (item, count) per item (trainCooccurrence parity).

    With a mesh, C's row blocks spread over its first axis; without one,
    a single-device mesh on the default backend."""
    if len(user_idx) == 0:
        return {}
    user_idx, item_idx = distinct_pairs(user_idx, item_idx)
    # budget check BEFORE any jax backend init (jax.devices() claims the
    # chip — pointless when the host path is going to run anyway). The
    # slabbed kernel never materializes the [n_items, n_items] count
    # matrix; its PER-CHIP working set is the uint8 A shard + the
    # replicated bf16 all-gather of full-width A (which does NOT shrink
    # with more chips) + one [slab, ni_pad] f32 count block. With a mesh
    # already claimed we can ask its devices for their memory limit; the
    # off-TPU budget stays conservative.
    n_shards = int(mesh.shape[mesh.axis_names[0]]) if mesh is not None else 1
    ni_pad = -(-n_items // (128 * n_shards)) * 128 * n_shards
    fits = n_users * ni_pad <= DENSE_BUDGET
    if not fits and mesh is not None and on_tpu():
        n_dev = int(np.prod(mesh.devices.shape))
        per_chip = (n_users * ni_pad // n_dev       # uint8 shard
                    + 2 * n_users * ni_pad          # bf16 gather
                    + 4 * KERNEL_SLAB * ni_pad)     # f32 slab block
        fits = per_chip <= DEVICE_HBM_SHARE * memory_limit_bytes(
            list(mesh.devices.flat))
    if fits:
        if mesh is None:
            import jax
            from jax.sharding import Mesh

            mesh = Mesh(np.asarray(jax.devices())[:1], axis_names=("data",))
        vals, idx = cooccurrence_topn(mesh, user_idx, item_idx,
                                      n_users, n_items, n)
        top: Dict[int, List[Tuple[int, int]]] = {}
        for item in range(n_items):
            cands = [(int(j), int(c)) for j, c in zip(idx[item], vals[item])
                     if c > 0]
            if cands:
                top[item] = cands       # top_k output is already sorted desc
        return top
    return cooccurrence_topn_host(user_idx, item_idx, n_items, n)


@dataclasses.dataclass
class CooccurrenceModel:
    """CooccurrenceModel parity: top-N lists + id maps."""

    item_vocab: np.ndarray                      # sorted distinct item ids
    top_cooccurrences: Dict[int, List[Tuple[int, int]]]

    def item_index(self, item_id: str) -> Optional[int]:
        return vocab_index(self.item_vocab, item_id)

    def similar(self, item_ids: List[str], num: int,
                exclude_query: bool = True,
                white_list: Optional[List[str]] = None,
                black_list: Optional[List[str]] = None,
                candidate_filter=None,
                ) -> List[Tuple[str, float]]:
        """Combine the query items' top lists (predict parity: sum counts
        per candidate, filter, sort desc). candidate_filter(idx) -> bool
        applies engine-specific rules (e.g. category matching)."""
        query_idx = {i for i in (self.item_index(x) for x in item_ids)
                     if i is not None}
        white = None
        if white_list is not None:
            white = {i for i in (self.item_index(x) for x in white_list)
                     if i is not None}
        black = set()
        if black_list is not None:
            black = {i for i in (self.item_index(x) for x in black_list)
                     if i is not None}
        counts: Dict[int, int] = {}
        for q in query_idx:
            for cand, c in self.top_cooccurrences.get(q, []):
                counts[cand] = counts.get(cand, 0) + c
        out = []
        for cand, c in sorted(counts.items(), key=lambda x: -x[1]):
            if exclude_query and cand in query_idx:
                continue
            if white is not None and cand not in white:
                continue
            if cand in black:
                continue
            if candidate_filter is not None and not candidate_filter(cand):
                continue
            out.append((str(self.item_vocab[cand]), float(c)))
            if len(out) >= num:
                break
        return out
