"""Fused low-precision top-k scoring for large catalogs.

The serving cost of every ALS-backed surface (query server micro-batches,
``pio batchpredict``, fold-in warm-up) is one ``[B,K] @ [K,N]`` matmul
followed by a top-k — and the exact implementation materializes the full
``[B,N]`` score matrix with float32 factors resident. At 10M-item
catalogs that is an HBM-bandwidth wall, not a FLOP wall (ROADMAP item 4).
This module is the kernel layer that replaces it:

* **Quantized factor residency** — item factors stored ``bfloat16`` or
  ``int8`` (per-row scales, f32 accumulation in the matmul). ALX
  (arXiv:2112.02194) demonstrates bf16 factor storage at quality parity
  on TPU; int8 halves it again. The f32 copy stays on HOST (the model
  already holds it) — device factor bytes drop 2-4x.

* **Tiled streaming top-k** (modes ``fused``/``fused_bf16``/
  ``fused_int8``) — item tiles of ``tile_items`` rows are dequantized,
  matmul'd and folded into a per-query *running* top-k carried through a
  ``lax.scan``, so the ``[B,N]`` score matrix never exists; the seen-item
  mask folds into each tile as a ``-inf`` sentinel, so masked and
  unmasked queries ride one kernel family.

* **Two-stage scan→rescore** (mode ``twostage``) — for catalogs where
  even fused-exact is too slow: the factors are rotated into the
  eigenbasis of ``V^T V`` (exactness-preserving — scores are invariant
  under a shared orthogonal rotation) and the scan reads only the
  leading principal columns that carry ``ENERGY_TARGET`` of the spectrum,
  quantized int8. Each tile emits its local top-c into a shortlist, and
  the shortlist alone is rescored EXACTLY in f32 from the host factor
  copy — final scores are exact; only shortlist membership is
  approximate. This is the heavy-offline/light-online split of
  parallel-and-stream (arXiv:2111.00032) applied inside one query.

Every compile registers in the ``ops/fn_cache`` families
``scoring_fused`` / ``scoring_shortlist``, so the ledger stays bounded by
the bucket ladder x scorer-mode families. Every non-exact scorer is
gated at build time (i.e. at deploy warm-up, which drives the first
batch) on recall@k parity against the exact scorer: a build whose probe
recall falls under ``min_recall`` FALLS BACK to exact serving and counts
``pio_scoring_parity_fallback_total`` — a bad quantization can never
silently degrade answers.

Mode selection rides the established knob chain (env > engine.json
``"scorer"`` > server.json ``"scorer"``): ``PIO_SCORER_MODE``,
``PIO_SCORER_TILE_ITEMS``, ``PIO_SCORER_SHORTLIST`` — resolved by
:func:`predictionio_tpu.utils.server_config.scorer_config` and pinned
per process via :func:`set_process_scorer_config` (``pio deploy`` /
``pio batchpredict`` pass the engine.json-aware config through).
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import threading
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.ops.bucketing import bucket_size
from predictionio_tpu.ops.fn_cache import shape_cached_fn
from predictionio_tpu.ops.topk import host_topk, merge_topk

logger = logging.getLogger("pio.scoring")

#: selectable scoring kernels, weakest-assumption first. "exact" is the
#: materialize-then-top_k path (models/als.py); everything else routes
#: through this module.
SCORER_MODES = ("exact", "fused", "fused_bf16", "fused_int8", "twostage")

#: compile-ledger family of the running-top-k streaming kernel: one
#: entry per (quant dtype, batch bucket, k bucket, tile grid, rank,
#: masked) program — bounded by the bucket ladders x modes, never by
#: traffic
FUSED_FAMILY = "scoring_fused"
#: compile-ledger family of the two-stage shortlist scan (k-independent:
#: the final top-k runs on host after the exact rescore)
TWOSTAGE_FAMILY = "scoring_shortlist"

#: spectrum fraction the two-stage scan's truncated principal columns
#: must carry. ALS factor Gramians decay (the data is low-rank plus
#: noise); on a flat-spectrum matrix this keeps nearly every column and
#: the mode degrades gracefully to fused-int8 + exact rescore.
ENERGY_TARGET = 0.96

#: queries in the build-time parity probe (rows sampled from the catalog
#: itself — item-to-item scoring, the similarproduct case, and a span
#: the user rows live in). Small because the exact side runs on host
#: BLAS over the full catalog.
PARITY_PROBE_QUERIES = 8
PARITY_PROBE_K = 10

#: factor rows sampled for the quantization-error gauge (the full-matrix
#: error would re-touch all N*K bytes for a number a sample pins down)
QUANT_ERROR_SAMPLE_ROWS = 4096

#: quantized fused scans carry OVERFETCH*k candidates (min FUSED_MIN_CARRY)
#: through the running top-k and exact-rescore them on host: the true
#: top-k only has to land in the quantized top-(OVERFETCH*k), which
#: quantization noise essentially cannot prevent, instead of surviving
#: near-tie reorderings inside the top-k itself
FUSED_OVERFETCH = 4
FUSED_MIN_CARRY = 32


# ---------------------------------------------------------------------------
# process-level scorer selection
# ---------------------------------------------------------------------------

_PROCESS_CFG = None
_CFG_LOCK = threading.Lock()


def set_process_scorer_config(cfg) -> None:
    """Pin the resolved scorer knobs for this process (``pio deploy`` /
    ``pio batchpredict`` / the query server pass the engine.json-aware
    config through; ``None`` resets to lazy env>server.json resolution —
    the test hook)."""
    global _PROCESS_CFG
    with _CFG_LOCK:
        _PROCESS_CFG = cfg


def process_scorer_config():
    """The scorer knobs every model in this process scores under.

    Resolved lazily from env > server.json when nothing pinned one
    (standalone model use, tests); servers pin the engine.json-aware
    config at startup."""
    global _PROCESS_CFG
    with _CFG_LOCK:
        if _PROCESS_CFG is None:
            from predictionio_tpu.utils.server_config import scorer_config

            _PROCESS_CFG = scorer_config(None)
        return _PROCESS_CFG


def holder_scorer_config(holder):
    """The scorer knobs THIS holder scores under: a per-holder override
    stamped by the multi-tenant server (``_scorer_cfg_override``) beats
    the process pin — one process can keep tenant A's factors int8 and
    tenant B's bf16, each tenant's residency chosen to fit the shared
    device-memory budget."""
    override = getattr(holder, "_scorer_cfg_override", None)
    return override if override is not None else process_scorer_config()


# ---------------------------------------------------------------------------
# streaming kernels (module-level jits shared across shapes; the
# shape_cached_fn wrappers below are the per-bucket compile ledger)
# ---------------------------------------------------------------------------

def _tile_scores(u, v_tile, s_tile):
    """One tile's [B, T] f32 scores: dequantize + matmul with f32
    accumulation. ``s_tile is None`` means the tile needs no scale
    (f32/bf16 storage); int8 tiles carry per-row scales."""
    if v_tile.dtype == jnp.bfloat16:
        # bf16 x bf16 -> f32 accumulation (the MXU-native ALX layout);
        # u is tiny, so casting it costs nothing while the tile read —
        # the bandwidth hog — stays half-width
        sc = jax.lax.dot_general(
            u.astype(jnp.bfloat16), v_tile, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    elif v_tile.dtype == jnp.int8:
        sc = u @ v_tile.T.astype(jnp.float32)
    else:
        sc = u @ v_tile.T
    if s_tile is not None:
        sc = sc * s_tile[None, :]
    return sc


def _scan_xs(b, v_tiles, scales, mask, tile):
    """Assemble one tile-scan's xs tuple: factor tiles, optional scales,
    the optional mask re-laid [B, n_pad] -> [n_tiles, B, T] so each scan
    step carries one tile of mask alongside one tile of factors, and the
    per-tile id bases."""
    n_tiles = v_tiles.shape[0]
    xs = [v_tiles]
    if scales is not None:
        xs.append(scales)
    if mask is not None:
        xs.append(jnp.moveaxis(mask.reshape(b, n_tiles, tile), 1, 0))
    xs.append(jnp.arange(n_tiles, dtype=jnp.int32) * tile)
    return tuple(xs)


def _step_scores(u, xs, has_scales: bool, has_mask: bool, n_items):
    """Unpack one scan step's xs (as `_scan_xs` packed them) into the
    tile's sentineled [B, T] scores + global ids: dequantize + matmul,
    then ``-inf`` out padding rows (ids >= n_items) and masked items —
    the single definition of the sentinel rule both scans share."""
    parts = list(xs)
    v_tile = parts.pop(0)
    s_tile = parts.pop(0) if has_scales else None
    m_tile = parts.pop(0) if has_mask else None
    base = parts.pop(0)
    sc = _tile_scores(u, v_tile, s_tile)
    ids = base + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
    sentinel = ids >= n_items
    if m_tile is not None:
        sentinel = sentinel | m_tile
    return jnp.where(sentinel, -jnp.inf, sc), ids


@functools.partial(jax.jit, static_argnames=("num", "tile"))
def _fused_topk_scan(u, v_tiles, scales, n_items, mask, num: int,
                     tile: int):
    """Streaming top-k: scan item tiles, fold each into a per-query
    running top-``num`` — the [B, N] score matrix never exists. ``mask``
    (optional [B, n_pad] bool, True = excluded) folds into each tile as
    a ``-inf`` sentinel, so masked and unmasked queries share this one
    program family."""
    b = u.shape[0]
    has_scales, has_mask = scales is not None, mask is not None

    def step(carry, xs):
        vals, idx = carry
        sc, ids = _step_scores(u, xs, has_scales, has_mask, n_items)
        cv = jnp.concatenate([vals, sc], axis=1)
        ci = jnp.concatenate([idx, ids], axis=1)
        tv, ti = jax.lax.top_k(cv, num)
        return (tv, jnp.take_along_axis(ci, ti, axis=1)), None

    init = (jnp.full((b, num), -jnp.inf, jnp.float32),
            jnp.full((b, num), -1, jnp.int32))
    (tv, ti), _ = jax.lax.scan(step, init,
                               _scan_xs(b, v_tiles, scales, mask, tile))
    return tv, ti


@functools.partial(jax.jit, static_argnames=("cand", "tile"))
def _shortlist_scan(u, v_tiles, scales, n_items, mask, cand: int,
                    tile: int):
    """Two-stage stage 1: each tile emits its LOCAL top-``cand``
    (approximate scores) — no cross-tile merge, which the exact rescore
    makes unnecessary: the shortlist only has to CONTAIN the true top-k,
    and a true winner is in its own tile's local top-c long before it is
    in the global top-S. Output is [B, n_tiles * cand] candidate ids."""
    b = u.shape[0]
    has_scales, has_mask = scales is not None, mask is not None

    def step(_, xs):
        sc, ids = _step_scores(u, xs, has_scales, has_mask, n_items)
        tv, ti = jax.lax.top_k(sc, cand)
        return None, (tv, jnp.take_along_axis(ids, ti, axis=1))

    _, (tv, ti) = jax.lax.scan(step, None,
                               _scan_xs(b, v_tiles, scales, mask, tile))
    # [n_tiles, B, c] -> [B, n_tiles * c]
    return (jnp.moveaxis(tv, 0, 1).reshape(b, -1),
            jnp.moveaxis(ti, 0, 1).reshape(b, -1))


# ---------------------------------------------------------------------------
# quantization + packing
# ---------------------------------------------------------------------------

def _pow2_tile(tile_items: int, n_items: int) -> int:
    """The static tile width: the configured tile rounded up to a power
    of two, shrunk to one tile for small catalogs — the tile grid is
    part of the compile key, so the rounding rule must be a single
    definition (the bucketing discipline applied to the item axis)."""
    t = bucket_size(max(1, tile_items))
    return min(t, bucket_size(n_items))


def _pack_tiles(arr: np.ndarray, tile: int):
    """[N, K] -> ([n_tiles, tile, K], n_pad): pad item rows up to a
    whole tile grid (pad rows are sentineled by id inside the kernels,
    so their values never matter)."""
    n = arr.shape[0]
    n_pad = -(-n // tile) * tile
    if n_pad != n:
        arr = np.concatenate(
            [arr, np.zeros((n_pad - n,) + arr.shape[1:], arr.dtype)])
    return arr.reshape(n_pad // tile, tile, *arr.shape[1:]), n_pad


def _quantize_int8(v: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Symmetric per-row int8: q = round(v / s), s = row-max / 127.
    Zero rows get scale 1 so dequantization stays finite."""
    s = np.abs(v).max(axis=1) / 127.0
    s = np.where(s == 0, 1.0, s).astype(np.float32)
    q = np.clip(np.rint(v / s[:, None]), -127, 127).astype(np.int8)
    return q, s


def _principal_rotation(v: np.ndarray) -> Tuple[np.ndarray, int]:
    """Eigenbasis of V^T V (descending eigenvalue) and the column count
    carrying ``ENERGY_TARGET`` of the spectrum. Scores are invariant
    under rotating BOTH sides by W (orthogonal), which is what lets the
    stage-1 scan truncate to the leading columns without approximating
    anything except the discarded tail's contribution."""
    g = (v.T @ v).astype(np.float64)
    w, vecs = np.linalg.eigh(g)
    order = np.argsort(w)[::-1]
    w, vecs = np.maximum(w[order], 0.0), vecs[:, order]
    total = w.sum()
    if total <= 0:
        return vecs.astype(np.float32), v.shape[1]
    energy = np.cumsum(w) / total
    dims = int(np.searchsorted(energy, ENERGY_TARGET) + 1)
    # round up to 8 (lane-friendly) and clamp into [8, K]
    dims = min(v.shape[1], max(8, -(-dims // 8) * 8))
    return vecs.astype(np.float32), dims


# ---------------------------------------------------------------------------
# the scorer
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ItemScorer:
    """Device-resident (possibly quantized) item factors plus the tiled
    streaming top-k over them, for ONE factor matrix identity.

    Built lazily on the first device-scored batch (which, through the
    deploy warm-up ladder, means at deploy time, off the serving path)
    and cached per V identity by the model — a fold-in apply that swaps
    V requantizes by rebuilding, exactly like the resident f32 copy.
    ``active_mode`` is the mode actually serving: the build-time parity
    probe demotes a scorer whose recall@10 against the exact path falls
    under ``min_recall`` to ``"exact"`` (the caller then routes down the
    legacy materialized path), so a catalog that quantizes badly keeps
    its exact answers.
    """

    mode: str                 # requested mode
    active_mode: str          # mode after the parity gate
    n_items: int
    rank: int
    tile: int
    n_tiles: int
    scan_rank: int            # truncated rank of the stage-1 scan
    shortlist: int            # candidates per query (twostage; else 0)
    cand_per_tile: int        # local top-c per tile (twostage; else 0)
    quantization: str         # "float32" | "bfloat16" | "int8"
    factor_bytes: int         # device-resident factor + scale bytes
    exact_bytes: int          # the f32 baseline those bytes replace
    recall_probe: float       # build-time probe recall@PARITY_PROBE_K
    quant_error: float        # sampled max relative dequantization error
    _tiles: Optional[jax.Array] = None      # [n_tiles, T, scan_rank]
    _scales: Optional[jax.Array] = None     # [n_tiles, T] (int8 only)
    _v_host: Optional[np.ndarray] = None    # f32 rescore source
    _rotation: Optional[np.ndarray] = None  # [K, scan_rank] (twostage)

    @property
    def active(self) -> bool:
        """False when the parity gate demoted this scorer to exact."""
        return self.active_mode != "exact"

    # -- scoring -------------------------------------------------------------

    def topk(self, u_batch: np.ndarray, k: int,
             mask: Optional[np.ndarray] = None
             ) -> Tuple[np.ndarray, np.ndarray]:
        """Top-``k`` (scores, ids) for ``u_batch`` [B, K] f32 rows;
        ``mask`` [B, n_items] bool excludes items (True = excluded).
        Batch and k are bucketed internally (ops/bucketing), so the
        compile ledger stays on the power-of-two ladder; results come
        back trimmed to [B, k]."""
        from predictionio_tpu.obs.scoring_stats import scoring_metrics

        if not self.active:
            raise RuntimeError(
                "scorer was parity-demoted to exact and holds no device "
                "residency — callers must check .active and route the "
                "exact path")
        b = u_batch.shape[0]
        k = min(k, self.n_items)
        b_pad = bucket_size(b)
        u = np.zeros((b_pad, self.rank), np.float32)
        u[:b] = u_batch
        mask_pad = None
        if mask is not None:
            n_pad = self.n_tiles * self.tile
            mask_pad = np.ones((b_pad, n_pad), bool)
            mask_pad[:b, :self.n_items] = mask
        m = scoring_metrics()
        m.batches.inc(mode=self.active_mode)
        m.tiles.inc(self.n_tiles)
        if self.active_mode == "twostage":
            scores, idx = self._topk_twostage(u, k, mask_pad)
        else:
            scores, idx = self._topk_fused(u, k, mask_pad)
        return scores[:b, :k], idx[:b, :k]

    def _topk_fused(self, u: np.ndarray, k: int,
                    mask_pad: Optional[np.ndarray]):
        quantized = self.quantization != "float32"
        # quantized scans OVERFETCH the running carry: quantization noise
        # (~0.2-0.4% relative) reorders near-ties, so the true top-k is
        # asked to sit in the quantized top-(OVERFETCH*k) — a far weaker
        # requirement — and the small carried set is rescored EXACTLY in
        # f32 from the host factor copy. Final scores are exact; only
        # carry membership is approximate (the FAISS-style rescore
        # discipline). f32 tiles need neither.
        want = max(k, 1) if not quantized else max(FUSED_OVERFETCH * k,
                                                   FUSED_MIN_CARRY)
        k_pad = min(bucket_size(want), self.n_items)
        key = (self.quantization, u.shape, k_pad, self.n_tiles,
               self.tile, self.scan_rank, self.n_items,
               mask_pad is not None)
        # shape_cached_fn returns the SAME shared jit (executables live
        # in jit's cache); its build counter is the per-bucket compile
        # ledger pio_jax_compile_total{family=scoring_fused} reads
        fn = shape_cached_fn(FUSED_FAMILY, key, lambda: _fused_topk_scan)
        out = fn(jnp.asarray(u), self._tiles, self._scales,
                 jnp.int32(self.n_items),
                 jnp.asarray(mask_pad) if mask_pad is not None else None,
                 k_pad, self.tile)
        scores, idx = jax.device_get(out)    # one fetch
        if not quantized:
            return scores, idx
        return self._rescore_exact(np.asarray(u, np.float32),
                                   np.asarray(scores), np.asarray(idx), k)

    def _rescore_exact(self, u: np.ndarray, approx: np.ndarray,
                       cand: np.ndarray, k: int):
        """Exact f32 rescore of per-query candidate ids from the host
        factor copy + host top-k. Candidates the scan sentineled to
        -inf (masked / padding / carry inits) stay -inf."""
        valid = np.isfinite(approx) & (cand >= 0) & (cand < self.n_items)
        safe = np.where(valid, cand, 0)
        sc = np.einsum("bk,bsk->bs", u, self._v_host[safe],
                       dtype=np.float32, casting="same_kind")
        sc = np.where(valid, sc, -np.inf)
        # the shared shortlist merge (ops/topk): one candidate set is
        # just a 1-way merge, which buys the deterministic id tie-break
        # the cross-shard path relies on
        return merge_topk([(sc, np.where(valid, cand, -1))], k)

    def _topk_twostage(self, u: np.ndarray, k: int,
                       mask_pad: Optional[np.ndarray]):
        from predictionio_tpu.obs.scoring_stats import scoring_metrics

        u_scan = u if self._rotation is None else \
            np.ascontiguousarray((u @ self._rotation).astype(np.float32))
        # a request wanting more than the configured shortlist widens
        # the per-tile candidate count for THIS call (bucketed to the
        # power-of-two ladder so the widened shapes stay ledger-bounded)
        # — the rescore can only return ids the scan emitted, so the
        # candidate set must always be at least k wide
        cand = self.cand_per_tile
        if self.n_tiles * cand < k:
            cand = min(self.tile, bucket_size(-(-k // self.n_tiles)))
        if mask_pad is not None:
            # masked batches widen to k candidates PER TILE: a
            # concentrated mask (a whitelist whose survivors share one
            # tile) leaves every other tile fully sentineled, so the
            # per-tile-containment argument the configured shortlist
            # relies on — and the unmasked parity probe validates —
            # does not hold under masking
            cand = max(cand, min(self.tile, bucket_size(k)))
        key = (u.shape, cand, self.n_tiles, self.tile,
               self.scan_rank, self.n_items, mask_pad is not None)
        fn = shape_cached_fn(TWOSTAGE_FAMILY, key,
                             lambda: _shortlist_scan)
        out = fn(jnp.asarray(u_scan), self._tiles, self._scales,
                 jnp.int32(self.n_items),
                 jnp.asarray(mask_pad) if mask_pad is not None else None,
                 cand, self.tile)
        approx, cand = (np.asarray(a) for a in jax.device_get(out))
        m = scoring_metrics()
        m.shortlist.observe(float(cand.shape[1]))
        m.rescore_fraction.observe(cand.shape[1] / max(1, self.n_items))
        # stage 2: EXACT f32 rescore of the shortlist — final scores are
        # exact, only membership is approximate; candidates the scan
        # sentineled (masked items, padding ids) carry -inf approx
        # scores and stay -inf
        return self._rescore_exact(u, approx, cand, k)

    # -- status --------------------------------------------------------------

    def status(self) -> dict:
        """The /deploy/status.json block."""
        return {
            "mode": self.mode,
            "activeMode": self.active_mode,
            "quantization": self.quantization,
            "items": self.n_items,
            "rank": self.rank,
            "scanRank": self.scan_rank,
            "tileItems": self.tile,
            "tiles": self.n_tiles,
            "shortlist": self.shortlist,
            "factorBytes": self.factor_bytes,
            "exactBytes": self.exact_bytes,
            "recallProbe": round(self.recall_probe, 4),
            "quantError": round(self.quant_error, 6),
        }


def build_scorer(V: np.ndarray, cfg=None,
                 min_recall: Optional[float] = None,
                 device=None) -> ItemScorer:
    """Build an :class:`ItemScorer` over item factors ``V`` [N, K] f32
    under the resolved scorer knobs, running the parity gate before it
    may serve. ``cfg`` defaults to the process scorer config.
    ``device`` pins the quantized residency to one device of the mesh
    (the model-parallel sharded path); None keeps jax's default."""
    if cfg is None:
        cfg = process_scorer_config()
    mode = cfg.mode
    if mode not in SCORER_MODES:
        raise ValueError(f"unknown scorer mode {mode!r}: expected one of "
                         f"{'|'.join(SCORER_MODES)}")
    if mode == "exact":
        raise ValueError("exact mode never builds an ItemScorer — the "
                         "caller serves the legacy materialized path")
    v = np.ascontiguousarray(np.asarray(V), np.float32)
    n_items, rank = v.shape
    tile = _pow2_tile(cfg.tile_items, n_items)
    exact_bytes = v.nbytes
    rotation = None
    scan_rank = rank
    quant_error = 0.0

    if mode == "twostage":
        rot, dims = _principal_rotation(v)
        rotation = np.ascontiguousarray(rot[:, :dims])
        scan_rank = dims
        v_scan = np.ascontiguousarray((v @ rotation).astype(np.float32))
        q, s = _quantize_int8(v_scan)
        quant_error = _sampled_quant_error(v_scan, q, s)
        tiles, _ = _pack_tiles(q, tile)
        scales, _ = _pack_tiles(s, tile)
        quantization = "int8"
    elif mode == "fused_int8":
        q, s = _quantize_int8(v)
        quant_error = _sampled_quant_error(v, q, s)
        tiles, _ = _pack_tiles(q, tile)
        scales, _ = _pack_tiles(s, tile)
        quantization = "int8"
    elif mode == "fused_bf16":
        vb = v.astype(jnp.bfloat16)
        quant_error = _sampled_quant_error(
            v, np.asarray(vb, np.float32), None)
        tiles, _ = _pack_tiles(np.asarray(vb), tile)
        scales = None
        quantization = "bfloat16"
    else:   # fused (f32, tiled — memory unchanged, [B,N] never built)
        tiles, _ = _pack_tiles(v, tile)
        scales = None
        quantization = "float32"

    n_tiles = tiles.shape[0]
    shortlist = 0
    cand_per_tile = 0
    if mode == "twostage":
        shortlist = max(1, int(cfg.shortlist))
        cand_per_tile = min(tile, max(1, -(-shortlist // n_tiles)))
        shortlist = cand_per_tile * n_tiles

    tiles_dev = (jax.device_put(tiles, device) if device is not None
                 else jax.device_put(tiles))
    scales_dev = None
    if scales is not None:
        scales_dev = (jax.device_put(scales, device) if device is not None
                      else jax.device_put(scales))
    factor_bytes = int(tiles.nbytes
                       + (scales.nbytes if scales is not None else 0))
    scorer = ItemScorer(
        mode=mode, active_mode=mode, n_items=n_items, rank=rank,
        tile=tile, n_tiles=n_tiles, scan_rank=scan_rank,
        shortlist=shortlist, cand_per_tile=cand_per_tile,
        quantization=quantization, factor_bytes=factor_bytes,
        exact_bytes=exact_bytes, recall_probe=1.0,
        quant_error=quant_error,
        _tiles=tiles_dev, _scales=scales_dev, _v_host=v,
        _rotation=rotation)
    _parity_gate(scorer, v,
                 cfg.min_recall if min_recall is None else min_recall)
    _observe_build(scorer)
    return scorer


def _sampled_quant_error(v: np.ndarray, q: np.ndarray,
                         s: Optional[np.ndarray]) -> float:
    """Max relative dequantization error over a row sample — the
    ``pio_scoring_quant_error`` gauge (a sample: the full-matrix number
    would re-touch every byte the quantization just wrote)."""
    n = v.shape[0]
    rows = np.linspace(0, n - 1,
                       num=min(QUANT_ERROR_SAMPLE_ROWS, n)).astype(int)
    vv = v[rows]
    deq = (q[rows].astype(np.float32) * s[rows, None] if s is not None
           else q[rows].astype(np.float32))
    denom = max(float(np.abs(vv).max()), 1e-30)
    return float(np.abs(deq - vv).max() / denom)


def _parity_gate(scorer: ItemScorer, v: np.ndarray,
                 min_recall: float) -> None:
    """Recall@k parity probe vs the exact scorer: catalog rows as probe
    queries, exact side on host BLAS. Runs ONCE per scorer build — at
    deploy warm-up, since the warm-up ladder drives the first batch —
    and demotes a failing scorer to exact."""
    n = scorer.n_items
    k = min(PARITY_PROBE_K, n)
    if k == 0:
        return
    rows = np.linspace(0, n - 1,
                       num=min(PARITY_PROBE_QUERIES, n)).astype(int)
    probe = np.ascontiguousarray(v[rows])
    _, exact_idx = host_topk(probe @ v.T, k)
    _, got_idx = scorer.topk(probe, k)
    hits = sum(len(set(a.tolist()) & set(b.tolist()))
               for a, b in zip(exact_idx, got_idx))
    recall = hits / float(exact_idx.shape[0] * k)
    scorer.recall_probe = recall
    if recall < min_recall:
        from predictionio_tpu.obs.scoring_stats import scoring_metrics

        logger.warning(
            "scorer parity gate failed: mode=%s recall@%d=%.4f < %.4f "
            "on a %dx%d catalog — falling back to exact serving",
            scorer.mode, k, recall, min_recall, scorer.n_items,
            scorer.rank)
        scoring_metrics().parity_fallback.inc(mode=scorer.mode)
        scorer.active_mode = "exact"
        # drop the device residency: a demoted scorer must not hold
        # quantized copies nobody will read
        scorer._tiles = None
        scorer._scales = None
        scorer.factor_bytes = 0


def _observe_build(scorer: ItemScorer) -> None:
    from predictionio_tpu.obs.scoring_stats import scoring_metrics

    m = scoring_metrics()
    m.quant_error.set(scorer.quant_error, mode=scorer.mode)
    m.parity_recall.set(scorer.recall_probe, mode=scorer.mode)


# ---------------------------------------------------------------------------
# model-parallel sharded scorer (ALX-style: factors past one device's HBM)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ShardedScorer:
    """Item factors sharded row-wise over the device mesh, one
    :class:`ItemScorer` per shard, merged on host.

    The shard map is ``parallel/distributed.contiguous_range`` — the
    same contiguous disjoint row ranges batchpredict's shard->merge
    shape uses — so shard ``r`` of ``S`` owns rows ``[lo, hi)`` of ``V``
    and its residency lands on device ``r % n_devices``. Each shard runs
    the configured kernel over ITS rows only and emits a local top-k
    shortlist with exact f32 scores (quantized shards exact-rescore from
    their host slice, exactly as unsharded); shard-local ids shift by
    ``lo`` into catalog ids and :func:`ops.topk.merge_topk` folds the
    shortlists into the global top-k. Because every shard's scores are
    exact and every catalog row belongs to exactly one shard, a global
    top-k winner is necessarily inside its own shard's local top-k — so
    the merge is exact whenever the per-shard kernels are (mode
    ``exact``/``fused``: always; quantized modes: whenever shortlist
    membership holds, the same recall contract the unsharded scorer is
    parity-gated on).

    Mode ``exact`` shards the host BLAS matmul instead of device
    residency (the dispatch-crossover discipline: exact mode never held
    device factors to begin with); a shard whose parity gate demoted it
    likewise serves exact host BLAS over its own rows — per-shard
    fallback, never a silent whole-catalog degrade.
    """

    mode: str                  # requested mode
    active_mode: str           # mode when ALL shards serve it, else "exact"
    n_items: int
    rank: int
    n_shards: int
    ranges: tuple              # ((lo, hi), ...) per shard
    shards: tuple              # per-shard ItemScorer; None = exact serving
    factor_bytes: int          # device-resident bytes across all shards
    max_shard_factor_bytes: int   # the per-device budget a shard must fit
    exact_bytes: int
    recall_probe: float
    _v_shards: tuple = ()      # per-shard host f32 slices

    @property
    def active(self) -> bool:
        """A sharded scorer always serves — a demoted shard falls back
        to exact host BLAS over its own rows, not to the caller."""
        return True

    def topk(self, u_batch: np.ndarray, k: int,
             mask: Optional[np.ndarray] = None
             ) -> Tuple[np.ndarray, np.ndarray]:
        """Global top-``k`` (scores, catalog ids): per-shard local
        shortlists merged via the shared k-way merge. ``mask``
        [B, n_items] slices per shard columns, so a whitelist
        concentrated in one shard sentinels every other shard entirely
        and the merge keeps only real survivors."""
        b = u_batch.shape[0]
        k = min(k, self.n_items)
        if k <= 0:
            empty = np.zeros((b, 0))
            return empty.astype(np.float32), empty.astype(np.int64)
        u = np.ascontiguousarray(np.asarray(u_batch, np.float32))
        shortlists = []
        for (lo, hi), scorer, v_shard in zip(
                self.ranges, self.shards, self._v_shards):
            m = None
            if mask is not None:
                m = np.ascontiguousarray(mask[:, lo:hi])
            k_s = min(k, hi - lo)
            if scorer is not None and scorer.active:
                vals, ids = scorer.topk(u, k_s, mask=m)
            else:
                sc = u @ v_shard.T
                if m is not None:
                    sc = np.where(m, -np.inf, sc)
                vals, ids = host_topk(sc, k_s)
            shortlists.append((vals, np.asarray(ids, np.int64) + lo))
        return merge_topk(shortlists, k)

    def status(self) -> dict:
        """The /deploy/status.json block (sharded form)."""
        return {
            "mode": self.mode,
            "activeMode": self.active_mode,
            "sharded": True,
            "shards": self.n_shards,
            "ranges": [list(r) for r in self.ranges],
            "items": self.n_items,
            "rank": self.rank,
            "factorBytes": self.factor_bytes,
            "maxShardFactorBytes": self.max_shard_factor_bytes,
            "exactBytes": self.exact_bytes,
            "recallProbe": round(self.recall_probe, 4),
            "shardStatus": [s.status() for s in self.shards
                            if s is not None],
        }


def build_sharded_scorer(V: np.ndarray, cfg=None,
                         min_recall: Optional[float] = None,
                         shards: Optional[int] = None) -> ShardedScorer:
    """Build a :class:`ShardedScorer` over ``V`` [N, K] f32: row-shard
    via ``contiguous_range``, build one per-shard kernel under the same
    config (each parity-gated against ITS shard's exact top-k), then
    probe the MERGED result against the global exact top-k for the
    status block's recall figure."""
    from predictionio_tpu.parallel.distributed import contiguous_range

    if cfg is None:
        cfg = process_scorer_config()
    if shards is None:
        shards = int(getattr(cfg, "shards", 1) or 1)
    v = np.ascontiguousarray(np.asarray(V), np.float32)
    n_items, rank = v.shape
    shards = max(1, min(shards, n_items))
    devices = jax.devices()
    ranges, shard_scorers, v_shards = [], [], []
    for r in range(shards):
        lo, hi = contiguous_range(n_items, r, shards)
        v_shard = np.ascontiguousarray(v[lo:hi])
        scorer = None
        if cfg.mode != "exact":
            scorer = build_scorer(v_shard, cfg, min_recall,
                                  device=devices[r % len(devices)])
        ranges.append((lo, hi))
        shard_scorers.append(scorer)
        v_shards.append(v_shard)
    all_active = all(s is not None and s.active for s in shard_scorers)
    factor_bytes = sum(s.factor_bytes for s in shard_scorers
                       if s is not None)
    max_shard = max((s.factor_bytes for s in shard_scorers
                     if s is not None), default=0)
    out = ShardedScorer(
        mode=cfg.mode,
        active_mode=cfg.mode if (all_active and cfg.mode != "exact")
        else "exact",
        n_items=n_items, rank=rank, n_shards=shards,
        ranges=tuple(ranges), shards=tuple(shard_scorers),
        factor_bytes=factor_bytes, max_shard_factor_bytes=max_shard,
        exact_bytes=v.nbytes, recall_probe=1.0,
        _v_shards=tuple(v_shards))
    # global probe: merged shortlists vs whole-catalog exact top-k (the
    # per-shard gates already ran inside build_scorer; this one feeds
    # the status block AND catches a merge regression outright)
    n = n_items
    k = min(PARITY_PROBE_K, n)
    if k > 0:
        rows = np.linspace(0, n - 1,
                           num=min(PARITY_PROBE_QUERIES, n)).astype(int)
        probe = np.ascontiguousarray(v[rows])
        _, exact_idx = host_topk(probe @ v.T, k)
        _, got_idx = out.topk(probe, k)
        hits = sum(len(set(a.tolist()) & set(b.tolist()))
                   for a, b in zip(exact_idx, got_idx))
        out.recall_probe = hits / float(exact_idx.shape[0] * k)
    return out


# ---------------------------------------------------------------------------
# model-side cache + status helpers
# ---------------------------------------------------------------------------

#: serializes scorer BUILDS (not lookups): a cold cache under the query
#: server's multi-threaded predict executor would otherwise pay N
#: duplicate multi-second quantize+probe builds of the SAME factor
#: matrix at once — and transiently hold N device copies
_BUILD_LOCK = threading.Lock()


def scorer_for(holder, V: np.ndarray) -> Optional[ItemScorer]:
    """The cached :class:`ItemScorer` for ``holder``'s factor matrix
    ``V`` under the CURRENT process scorer config, (re)building when V's
    identity or the config changed — the ``V_device`` residency
    discipline applied to quantized copies, which is also what makes a
    fold-in apply requantize: an item fold swaps V, the identity check
    misses, and the next scored batch (the fold-in controller's pre-swap
    warm drive) rebuilds from the updated rows. Returns ``None`` in
    unsharded exact mode (callers keep the legacy path); with
    ``shards > 1`` every mode — exact included — routes through the
    model-parallel :class:`ShardedScorer`. A per-holder
    ``_scorer_cfg_override`` (multi-tenant serving) beats the process
    pin, so co-hosted tenants can hold different quantized residencies."""
    cfg = holder_scorer_config(holder)
    shards = int(getattr(cfg, "shards", 1) or 1)
    if cfg.mode == "exact" and shards <= 1:
        return None
    key = cfg.cache_key()
    cached = getattr(holder, "_scorer_cache", None)
    if cached is not None and cached[0] is V and cached[1] == key:
        return cached[2]
    with _BUILD_LOCK:
        cached = getattr(holder, "_scorer_cache", None)   # lost the race?
        if cached is None or cached[0] is not V or cached[1] != key:
            built = (build_sharded_scorer(V, cfg) if shards > 1
                     else build_scorer(V, cfg))
            cached = (V, key, built)
            holder._scorer_cache = cached
    return cached[2]


def unit_scorer_status(result) -> list:
    """Per-model scorer echo for /deploy/status.json: the status dict of
    every model in a TrainResult that has built a scorer (quantized
    residency is lazy, so a unit that never scored on device reports
    none)."""
    out = []
    for model in getattr(result, "models", ()) or ():
        cached = getattr(model, "_scorer_cache", None)
        if cached is not None:
            out.append(cached[2].status())
    return out
