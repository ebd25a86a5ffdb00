"""Long-context attention with sequence parallelism over a device mesh.

The reference has no attention models (SURVEY.md §5: "long-context /
sequence parallelism — absent"), but this framework treats long-context and
distributed execution as first-class: engines that embed sequence models
(session-based recommendation, event-stream encoders) need attention that
scales past a single chip's HBM. Two strategies, one contract:

* ``mha`` — dense reference implementation (single device, or fully
  replicated); the numerical ground truth the parallel paths are tested
  against.
* ``ring_attention`` — sequence parallelism: Q/K/V sharded along the
  sequence axis of a ``Mesh``; K/V blocks rotate around the ring via
  ``lax.ppermute`` while each device accumulates its queries' output with
  the flash-attention running-max/denominator recurrence. HBM per device is
  O(L/p); comms ride ICI neighbor-to-neighbor, overlapping with the block
  matmuls (the Ring Attention construction, cf. PAPERS.md).

All paths use the same [batch, seq, heads, head_dim] layout, jit/shard_map
compile to static shapes, and keep the softmax in float32 regardless of
input dtype (bfloat16 QKV with f32 accumulation is the TPU-native recipe:
matmuls hit the MXU in bf16, the recurrence stays stable in f32).
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import functools
import math
from typing import Iterator, Optional, Set, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from predictionio_tpu.ops import attention_pallas
from predictionio_tpu.ops.attention_pallas import NEG_INF


def _causal_mask(scores: jax.Array, q_off, k_off,
                 window: Optional[int] = None) -> jax.Array:
    """Mask scores [..., Lq, Lk] so query i attends to keys j with
    global_j <= global_i, where globals are local indices + offsets;
    under a window, to the `window` keys up to its own alone
    (`attention_pallas.sees`)."""
    lq, lk = scores.shape[-2], scores.shape[-1]
    qi = q_off + jnp.arange(lq)[:, None]
    kj = k_off + jnp.arange(lk)[None, :]
    return jnp.where(attention_pallas.sees(qi, kj, True, window), scores,
                     NEG_INF)


def mha(q: jax.Array, k: jax.Array, v: jax.Array,
        causal: bool = False,
        key_mask: Optional[jax.Array] = None) -> jax.Array:
    """Dense multi-head attention. q,k,v: [B, L, H, D] -> [B, L, H, D].
    key_mask: optional [B, Lk] bool, False = key is padding (ignored)."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        s = _causal_mask(s, 0, 0)
    if key_mask is not None:
        s = jnp.where(key_mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # fully-masked query rows (e.g. pad queries) output 0, not mean-of-V
    p = p * (s.max(axis=-1, keepdims=True) > NEG_INF / 2)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def _flash_step(q, k_j, v_j, o, m, l, q_off, k_off, causal: bool,
                scale: float, key_mask_j=None):
    """One flash-attention accumulation step: fold K/V block (k_j, v_j) at
    global key offset k_off into the running (o, m, l) state for queries q
    at global offset q_off. Shared by the single-device blockwise kernel
    and the ring (the only difference between them is where the next block
    comes from)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k_j,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        s = _causal_mask(s, q_off, k_off)
    if key_mask_j is not None:
        s = jnp.where(key_mask_j[:, None, None, :], s, NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    alpha = jnp.exp(m - m_new)
    # explicit zero for masked scores: with the finite NEG_INF sentinel,
    # exp(s - m_new) would be 1 (not 0) in all-masked rows
    p = jnp.where(s > NEG_INF / 2, jnp.exp(s - m_new[..., None]), 0.0)
    l = l * alpha + p.sum(axis=-1)
    o = o * alpha[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p, v_j.astype(jnp.float32))
    return o, m_new, l


def _flash_finish(o, l, dtype):
    out = o / jnp.where(l == 0.0, 1.0, l)[..., None]
    return jnp.einsum("bhqd->bqhd", out).astype(dtype)


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """A rotary table stretched to `factor` times the `original_max_len`
    positions it was trained on (YaRN, arXiv:2309.00071, as transformers'
    `_compute_yarn_parameters`): a pair of dimensions that turns more
    than `beta_fast` times over the original length keeps its frequency,
    one that turns fewer than `beta_slow` times has it divided by
    `factor`, a linear ramp over the pairs between; cosines and sines
    times `attention_factor` (0.1 ln(factor) + 1 where none is given), so
    a score's rotary part carries its square."""

    factor: float
    original_max_len: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None

    def amplitude(self) -> float:
        if self.attention_factor is not None:
            return self.attention_factor
        return 0.1 * math.log(self.factor) + 1.0

    def frequencies(self, theta: float, width: int) -> jax.Array:
        """[width / 2] float32: the inverse frequencies of a rotary
        part `width` wide."""
        def pair_of(turns):     # the pair that turns this often (real)
            return width * math.log(self.original_max_len / (
                2 * math.pi * turns)) / (2 * math.log(theta))

        low = max(math.floor(pair_of(self.beta_fast)), 0)
        high = min(math.ceil(pair_of(self.beta_slow)), width - 1)
        pairs = jnp.arange(width // 2, dtype=jnp.float32)
        kept = theta ** (-pairs / (width // 2))
        ramp = jnp.clip((pairs - low) / max(high - low, 0.001), 0.0, 1.0)
        return kept / self.factor * ramp + kept * (1.0 - ramp)


def _rotary_angles(positions: jax.Array, theta: float, width: int,
                   scaling: Optional[YarnScaling]) -> jax.Array:
    """[(B,) L, width / 2] float32: what pair i of a rotary part `width`
    wide turns by at each position, position * theta^(-2i/width) or a
    `scaling`'s frequency."""
    half = width // 2
    if scaling is None:
        freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    else:
        freq = scaling.frequencies(theta, width)
    return positions.astype(jnp.float32)[..., None] * freq


def rope(x: jax.Array, positions: jax.Array, theta: float,
         rotary_dim: Optional[int] = None,
         scaling: Optional[YarnScaling] = None) -> jax.Array:
    """Rotary positions on the last axis of x [B, L, H, D] (D even), in
    the "halves" pairing: dimension i rotates with dimension i + D/2 by
    the angle position * theta^(-2i/D). positions: [L] or [B, L]. With
    `rotary_dim` < D only the leading `rotary_dim` dimensions rotate
    (halves pairing within them) and the others pass through. With a
    `scaling` the frequencies and the amplitude are its own."""
    if rotary_dim is not None and rotary_dim < x.shape[-1]:
        return jnp.concatenate(
            [rope(x[..., :rotary_dim], positions, theta, scaling=scaling),
             x[..., rotary_dim:]], axis=-1)
    half = x.shape[-1] // 2
    ang = _rotary_angles(positions, theta, x.shape[-1], scaling)
    if ang.ndim == 2:
        ang = ang[None]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    if scaling is not None:
        cos, sin = (t * scaling.amplitude() for t in (cos, sin))
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(
        jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def split_heads(qkv: jax.Array, heads: int,
                positions: Optional[jax.Array] = None,
                theta: Optional[float] = None):
    """A projection's columns [q | k | v], qkv [B, L, 3 x H x D] -> q, k,
    v [B, L, H, D]; with `positions`, q and k turned by `rope`. (The one
    place that knows this layout head-first; `attention_pallas.
    rotary_attention_pallas` knows it token-first.)"""
    b, l, _ = qkv.shape
    q, k, v = (t.reshape(b, l, heads, -1)
               for t in jnp.split(qkv, 3, axis=-1))
    if positions is not None:
        q, k = (rope(t, positions, theta) for t in (q, k))
    return q, k, v


def _block_scores(q_i, k_j, mask_j, i, j, block_q, block_k, causal, scale,
                  window=None, ids_i=None):
    """Masked scores [B, H, bq, bk] of query block i against key block j
    (NEG_INF where the key is padding, lies in the causal future or
    behind the window). `ids_i` (packed rows): the query block's session
    ids [B, bq] beside the keys' in `mask_j`; a key of another session
    is masked too."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q_i, k_j,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        s = _causal_mask(s, i * block_q, j * block_k, window)
    if ids_i is None:
        return jnp.where(mask_j[:, None, None, :], s, NEG_INF)
    seen = (mask_j > 0)[:, None, :] & (mask_j[:, None, :] == ids_i[:, :, None])
    return jnp.where(seen[:, None], s, NEG_INF)


def _block_pairs(n_q: int, n_k: int, block_q: int, block_k: int,
                 causal: bool, window: Optional[int] = None) -> jax.Array:
    """The (query block, key block) pairs that hold an unmasked score,
    query-major: a causal pair whose keys all lie in the future is left
    out, so causal attention does half the work, and under a window a
    pair whose keys all lie behind the band. The kernels' table
    (`attention_pallas._block_pairs`): the two routes cannot disagree
    about an edge."""
    return jnp.asarray(attention_pallas._block_pairs(
        n_q, n_k, block_q, block_k, causal, False, window))


def _rows(x, i, n):
    """Rows [i*n, (i+1)*n) of axis 2 of x [B, H, L, ...]."""
    return jax.lax.dynamic_slice_in_dim(x, i * n, n, axis=2)


def _put_rows(x, i, n, rows):
    return jax.lax.dynamic_update_slice_in_dim(x, rows, i * n, axis=2)


def _cols(mask, j, n):
    """Columns [j*n, (j+1)*n) of a key mask (or a row's ids) [B, L]."""
    return jax.lax.dynamic_slice_in_dim(mask, j * n, n, axis=1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _blockwise(q, k, v, key_mask, block_q, block_k, causal, window=None,
               packed=False):
    return _blockwise_fwd(q, k, v, key_mask, block_q, block_k, causal,
                          window, packed)[0]


def _blockwise_fwd(q, k, v, key_mask, block_q, block_k, causal, window=None,
                   packed=False):
    """q [B, H, Lq, Dk], k [B, H, Lk, Dk], v [B, H, Lk, Dv], lengths
    block multiples. One scan over the block pairs; the running
    (output, max, denominator) of every query block live in the carry.
    `packed`: key_mask holds a row's session ids (0 = padding), the
    queries' as the keys' (Lq = Lk)."""
    b, h, lq, dk = q.shape
    dv = v.shape[-1]
    scale = dk ** -0.5
    pairs = _block_pairs(lq // block_q, k.shape[2] // block_k, block_q,
                         block_k, causal, window)

    def step(carry, ij):
        o, m, l = carry
        i, j = ij[0], ij[1]
        s = _block_scores(_rows(q, i, block_q), _rows(k, j, block_k),
                          _cols(key_mask, j, block_k),
                          i, j, block_q, block_k, causal, scale, window,
                          _cols(key_mask, i, block_q) if packed else None)
        m_i, l_i = _rows(m, i, block_q), _rows(l, i, block_q)
        m_new = jnp.maximum(m_i, s.max(axis=-1))
        alpha = jnp.exp(m_i - m_new)
        # explicit zero for masked scores: with the finite NEG_INF
        # sentinel, exp(s - m_new) would be 1 (not 0) in all-masked rows
        p = jnp.where(s > NEG_INF / 2, jnp.exp(s - m_new[..., None]), 0.0)
        o_i = _rows(o, i, block_q) * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, _rows(v, j, block_k).astype(jnp.float32))
        return (_put_rows(o, i, block_q, o_i),
                _put_rows(m, i, block_q, m_new),
                _put_rows(l, i, block_q, l_i * alpha + p.sum(axis=-1))), None

    (o, m, l), _ = jax.lax.scan(
        step, (jnp.zeros((b, h, lq, dv), jnp.float32),
               jnp.full((b, h, lq), NEG_INF, jnp.float32),
               jnp.zeros((b, h, lq), jnp.float32)), pairs)
    l = jnp.where(l == 0.0, 1.0, l)
    out = (o / l[..., None]).astype(q.dtype)
    return out, (q, k, v, key_mask, out, m + jnp.log(l))


def _blockwise_bwd(block_q, block_k, causal, window, packed, res, d_out):
    """The backward pass recomputes each block's probabilities from the
    saved log-sum-exp instead of keeping them: O(L x block) memory where
    differentiating the forward scan keeps every block's."""
    q, k, v, key_mask, out, lse = res
    scale = q.shape[-1] ** -0.5
    d_out = d_out.astype(jnp.float32)
    delta = (d_out * out.astype(jnp.float32)).sum(axis=-1)   # [B, H, Lq]
    pairs = _block_pairs(q.shape[2] // block_q, k.shape[2] // block_k,
                         block_q, block_k, causal, window)

    def step(carry, ij):
        dq, dk, dv = carry
        i, j = ij[0], ij[1]
        q_i, k_j, v_j = (_rows(q, i, block_q), _rows(k, j, block_k),
                         _rows(v, j, block_k))
        s = _block_scores(q_i, k_j, _cols(key_mask, j, block_k),
                          i, j, block_q, block_k, causal, scale, window,
                          _cols(key_mask, i, block_q) if packed else None)
        p = jnp.where(s > NEG_INF / 2,
                      jnp.exp(s - _rows(lse, i, block_q)[..., None]), 0.0)
        do_i = _rows(d_out, i, block_q)
        dp = jnp.einsum("bhqd,bhkd->bhqk", do_i, v_j.astype(jnp.float32))
        ds = p * (dp - _rows(delta, i, block_q)[..., None]) * scale
        dq_i = _rows(dq, i, block_q) + jnp.einsum(
            "bhqk,bhkd->bhqd", ds, k_j.astype(jnp.float32))
        dk_j = _rows(dk, j, block_k) + jnp.einsum(
            "bhqk,bhqd->bhkd", ds, q_i.astype(jnp.float32))
        dv_j = _rows(dv, j, block_k) + jnp.einsum(
            "bhqk,bhqd->bhkd", p, do_i)
        return (_put_rows(dq, i, block_q, dq_i),
                _put_rows(dk, j, block_k, dk_j),
                _put_rows(dv, j, block_k, dv_j)), None

    (dq, dk, dv), _ = jax.lax.scan(
        step, tuple(jnp.zeros(t.shape, jnp.float32) for t in (q, k, v)),
        pairs)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), None


_blockwise.defvjp(_blockwise_fwd, _blockwise_bwd)


def _blocks_and_pads(lq: int, lk: int, block_k: int,
                     block_q: Optional[int]) -> Tuple[int, int, int, int]:
    """(block_q, block_k, pad_q, pad_k) of `blockwise_attention`."""
    block_k = min(block_k, lk)
    block_q = min(block_q or block_k, lq)
    return block_q, block_k, -lq % block_q, -lk % block_k


def attention_route(device_kind: str, lq: int, lk: int, dk: int, dv: int,
                    block_k: int = 512, block_q: Optional[int] = None,
                    devices: int = 1, window: Optional[int] = None) -> str:
    """Which implementation `blockwise_attention` runs for these sizes on
    a device of this kind (`jax.Device.device_kind`), in a program traced
    for `devices` devices: "pallas", the kernels of
    ops/attention_pallas.py, on the TPUs their blocks and VMEM limits
    were measured on (`attention_pallas.KINDS`), for shapes they tile
    (the lengths as `blockwise_attention` pads them), in a program for
    one device (the compiler partitions no Mosaic kernel: a program
    sharded over a mesh keeps the scan); "xla", the scan over block
    pairs, everywhere else. A `window` goes either way: the kernels
    tile it in blocks of the band's own."""
    _, _, pad_q, pad_k = _blocks_and_pads(lq, lk, block_k, block_q)
    if (device_kind in attention_pallas.KINDS and devices == 1
            and attention_pallas.tiles(lq + pad_q, lk + pad_k, dk, dv,
                                       window)):
        return "pallas"
    return "xla"


def band_pairs(device_kind: str, length: int, dk: int, dv: int, window: int,
               block_k: int = 512, devices: int = 1) -> Tuple[int, int]:
    """Of one session and head of `length` positions under a `window`:
    (the (query, key) pairs inside the band, `sees`' own count; the
    pairs of the blocks that `attention_route`'s implementation visits
    for them, its table's pairs times a block pair's scores). Their
    ratio is how much of the computed scores counts."""
    near = min(window, length)
    inside = near * (near + 1) // 2 + (length - near) * near
    block, _, pad, _ = _blocks_and_pads(length, length, block_k, None)
    if attention_route(device_kind, length, length, dk, dv, block_k,
                       devices=devices, window=window) == "pallas":
        block = attention_pallas._block(length + pad, window)
    n = (length + pad) // block
    visited = len(attention_pallas._block_pairs(n, n, block, block, True,
                                                False, window))
    return inside, visited * block * block


def session_pairs(device_kind: str, ids, dk: int, dv: int,
                  window: Optional[int] = None, block_k: int = 512,
                  devices: int = 1) -> Tuple[int, int]:
    """Of packed rows and one head, ids [B, L] (numpy: each position's
    session id, 0 = padding, rising by one along a row), causal, under a
    `window` or none: (the (query, key) pairs that count: of one
    session, `sees`' own count; the pairs of the block pairs that
    `attention_route`'s implementation multiplies for them: on the
    kernels' route the pairs of its table that hold a query and a key
    of one session (`attention_pallas.session_pair`), on the scan's its
    whole table). `band_pairs` for rows of many sessions."""
    import numpy as np

    ids = np.asarray(ids)
    length = ids.shape[1]
    block, _, pad, _ = _blocks_and_pads(length, length, block_k, None)
    ids = np.pad(ids, ((0, 0), (0, pad)))
    kernels = attention_route(device_kind, length, length, dk, dv, block_k,
                              devices=devices, window=window) == "pallas"
    if kernels:
        block = attention_pallas._block(length + pad, window)
    n = (length + pad) // block
    pairs = attention_pallas._block_pairs(n, n, block, block, True, False,
                                          window)
    inside = multiplied = 0
    for row in ids:
        sizes = np.bincount(row[row > 0]).astype(np.int64)
        near = sizes if window is None else np.minimum(sizes, window)
        inside += int((near * (near + 1) // 2 + (sizes - near) * near).sum())
        if kernels:
            first, last = attention_pallas.session_blocks(row[None], block)
            does, _ = attention_pallas.session_pair(
                first[0, pairs[:, 0]], last[0, pairs[:, 0]],
                first[0, pairs[:, 1]], last[0, pairs[:, 1]])
            multiplied += int(does.sum()) * block * block
        else:
            multiplied += len(pairs) * block * block
    return inside, multiplied


def attention_layout(device_kind: Optional[str], lq: int, lk: int, dk: int,
                     dv: int, block_k: int = 512,
                     block_q: Optional[int] = None, devices: int = 1,
                     window: Optional[int] = None) -> str:
    """Where the operands of `rotary_attention` (fused [q | k | v], as
    many key/value heads as query heads) and of `grouped_attention` (q,
    k, v of three products, grouped heads, a window or none) lie at these
    sizes on their way through `attention_route`'s implementation:
    "rows", the kernels reading a head as a block of columns of the
    projection's own [B, L, heads x width] (`attention_pallas.layout`:
    both widths whole lane tiles); "heads", the kernels on [B, heads, L,
    width]; "xla", the scan. The entry comes first, the widths second:
    those two may be token-first, and `blockwise_attention` is head-first
    on the kernels' route whatever the widths (its callers hold [B, L,
    H, D]: heads with norms of their own, widths off the lane tiles, the
    latent form). `device_kind` None: this process's device."""
    if attention_route(device_kind or _device_kind(), lq, lk, dk, dv,
                       block_k, block_q, devices, window) == "xla":
        return "xla"
    return attention_pallas.layout(dk, dv)


def _device_kind() -> str:
    return jax.devices()[0].device_kind


_ROUTES: contextvars.ContextVar[
    Optional[Tuple[Set[str], Optional[Set[str]]]]] = contextvars.ContextVar(
    "blockwise_attention_routes", default=None)


@contextlib.contextmanager
def routes_into(routes: Set[str],
                layouts: Optional[Set[str]] = None) -> Iterator[None]:
    """While the block runs (a trace), every `blockwise_attention`,
    `rotary_attention` or `grouped_attention` call adds the route it took
    to `routes` and, on
    the kernels' route, where they read a head ("rows" | "heads":
    `attention_layout`) to `layouts`."""
    token = _ROUTES.set((routes, layouts))
    try:
        yield
    finally:
        _ROUTES.reset(token)


def _hear(layout: str) -> None:
    """Tell a listener the `attention_layout` a call took."""
    heard = _ROUTES.get()
    if heard is None:
        return
    routes, layouts = heard
    routes.add("xla" if layout == "xla" else "pallas")
    if layouts is not None and layout != "xla":
        layouts.add(layout)


def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        block_k: int = 512, causal: bool = False,
                        key_mask: Optional[jax.Array] = None,
                        block_q: Optional[int] = None,
                        devices: int = 1,
                        window: Optional[int] = None,
                        packed: bool = False) -> jax.Array:
    """Flash-style single-device attention: stream over blocks of queries
    and of keys with the running-max/denominator recurrence so the
    [Lq, Lk] score matrix never materializes, forward or backward
    (custom_vjp: the backward pass recomputes a block's probabilities).
    O(L * block) memory; exact (not approximate). q: [B, Lq, H, Dk]; k:
    [B, Lk, Hkv, Dk]; v: [B, Lk, Hkv, Dv], Dv free (latent attention has
    192 and 128); -> [B, Lq, H, Dv]. Hkv divides H: key/value head j
    serves the query heads [j H / Hkv, (j + 1) H / Hkv) (grouped query
    heads), and takes the sum of their gradients; the kernels read each
    key/value head where it lies, the scan repeats it. block_q defaults
    to block_k.
    key_mask: optional [B, Lk] bool, False = key is padding (ignored).
    Lengths that are not a block multiple are handled by padding up to
    one: pad keys are masked out, pad queries cut off the result.
    `attention_route` says from the device's kind, the sizes and
    `devices` (how many devices the calling program is traced for: a
    mesh's size) whether the blocks are folded by Pallas kernels (with
    blocks of their own, on operands head-first behind a transpose:
    `rotary_attention` and `grouped_attention` are the entries that keep
    them token-first) or by a scan of XLA operations. With a `window`
    (causal, >= 1) a query sees its own key and the window - 1 before it
    (`attention_pallas.sees`); block pairs wholly behind the band are
    visited on neither route. `packed` (causal, Lq = Lk): a row holds
    several sessions, key_mask [B, L] int32 each position's session id (0
    = padding, ids rising by one along a row), and a query sees the keys
    of its own session alone, on either route."""
    if packed and not (causal and key_mask is not None
                       and q.shape[1] == k.shape[1]):
        raise ValueError("packed rows are causal, attend to themselves "
                         "and come with their session ids")
    if window is not None and not (causal and window >= 1):
        raise ValueError(f"a window of {window} keys, causal {causal}: a "
                         f"window is causal and holds the query's own key")
    b, lq, h, dk = q.shape
    lk, dv = k.shape[1], v.shape[-1]
    if h % k.shape[2] or k.shape[2] != v.shape[2]:
        raise ValueError(f"{k.shape[2]} key and {v.shape[2]} value heads "
                         f"under {h} query heads")
    group = h // k.shape[2]
    block_q, block_k, pad_q, pad_k = _blocks_and_pads(lq, lk, block_k,
                                                      block_q)
    if key_mask is None:
        key_mask = jnp.ones((b, lk), bool)
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        key_mask = jnp.pad(key_mask, ((0, 0), (0, pad_k)))
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    heads_first = lambda t: jnp.swapaxes(t, 1, 2)
    route = attention_route(_device_kind(), lq, lk, dk, dv, block_k, block_q,
                            devices, window)
    _hear("heads" if route == "pallas" else "xla")
    if route == "pallas":
        ops = heads_first(q), heads_first(k), heads_first(v), key_mask
        if packed:
            out = attention_pallas.packed_attention_pallas(*ops, window)
        elif window is None:
            out = attention_pallas.flash_attention_pallas(*ops, causal)
        else:
            out = attention_pallas.window_attention_pallas(*ops, window)
    else:
        if group > 1:
            k, v = (jnp.repeat(t, group, axis=2) for t in (k, v))
        out = _blockwise(heads_first(q), heads_first(k), heads_first(v),
                         key_mask, block_q, block_k, causal, window, packed)
    return heads_first(out)[:, :lq]


def rotary_attention(qkv: jax.Array, heads: int, theta: float,
                     block_k: int = 512, causal: bool = False,
                     key_mask: Optional[jax.Array] = None,
                     block_q: Optional[int] = None,
                     devices: int = 1, grad_dtype=None) -> jax.Array:
    """Multi-head attention with rotary positions (0 .. L - 1, `rope`'s
    pairing) on a projection's output: qkv [B, L, 3 x H x D], the columns
    [q | k | v] of `x @ wqkv` -> [B, L, H x D], on `attention_layout`'s
    route. "rows": the arrays stay token-first from the projection to
    the output (`attention_pallas.rotary_attention_pallas`: one pass over
    the projection's columns rotates and rounds, the kernels read a head
    as a block of columns, the gradient comes back as one array).
    Elsewhere `split_heads` and `blockwise_attention` on [B, L, H, D].
    `grad_dtype`: the type the "rows" route rounds qkv's gradient to
    where it writes it, float32 (none) unless the caller says otherwise.
    A caller whose `x @ wqkv` runs at the TPU's default precision may say
    bfloat16: that product's two backward products round their operands
    so, and so they do on the other routes, where this is not read."""
    b, l, width = qkv.shape
    d = width // (3 * heads)
    if attention_layout(None, l, l, d, d, block_k, block_q,
                        devices) != "rows":
        return blockwise_attention(
            *split_heads(qkv, heads, jnp.arange(l), theta), block_k=block_k,
            causal=causal, key_mask=key_mask, block_q=block_q,
            devices=devices).reshape(b, l, -1)
    _hear("rows")
    _, _, _, pad = _blocks_and_pads(l, l, block_k, block_q)
    if key_mask is None:
        key_mask = jnp.ones((b, l), bool)
    if pad:     # pad keys are masked out, pad queries cut off the result
        qkv = jnp.pad(qkv, ((0, 0), (0, pad), (0, 0)))
        key_mask = jnp.pad(key_mask, ((0, 0), (0, pad)))
    return attention_pallas.rotary_attention_pallas(
        qkv, key_mask, heads, theta, causal, grad_dtype=grad_dtype)[:, :l]


def rotary_tables(length: int, width: int, theta: float,
                  rotary_dim: Optional[int] = None,
                  scaling: Optional[YarnScaling] = None,
                  positions: Optional[jax.Array] = None):
    """`rope` on positions 0 .. L - 1 (or on `positions` [B, L], a batch
    row's own: tables [B, L, width]) as tables for a head's columns
    where they lie -> ((cos, a signed sine a roll) [L, width] float32,
    the rolls' distances): a head x [., width] turns into x cos + sum_s
    roll(x, s) sin_s, roll(x, s)[i] = x[i - s]. Column i of the leading
    `rotary_dim` turns with column i +- rotary_dim / 2: at the whole
    width both partners are one roll by half of it and the one sine is
    [-sin | sin]; at a part of it the lower half's partner lies a roll
    by width - rotary_dim / 2 away and the upper half's a roll by
    rotary_dim / 2, each sine 0 off its half, and the passing columns
    carry cos 1. A `scaling`'s frequencies and amplitude are in the
    numbers (`YarnScaling`), the angles, cosines and sines `rope`'s own
    float32 expressions."""
    rotary_dim = width if rotary_dim is None else min(rotary_dim, width)
    half, rest = rotary_dim // 2, width - rotary_dim
    ang = _rotary_angles(jnp.arange(length) if positions is None
                         else positions, theta, rotary_dim, scaling)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scaling is not None:
        cos, sin = (t * scaling.amplitude() for t in (cos, sin))
    none = jnp.zeros_like(sin)
    passing = lambda fill: jnp.full((*ang.shape[:-1], rest), fill,
                                    jnp.float32)
    cos = jnp.concatenate([cos, cos, passing(1.0)], axis=-1)
    if not rest:
        return (cos, jnp.concatenate([-sin, sin], axis=-1)), (half,)
    return (cos, jnp.concatenate([-sin, none, passing(0.0)], axis=-1),
            jnp.concatenate([none, sin, passing(0.0)], axis=-1)), \
        (width - half, half)


def grouped_attention(q: jax.Array, k: jax.Array, v: jax.Array, width: int,
                      gate: Optional[jax.Array] = None,
                      theta: Optional[float] = None,
                      rotary_dim: Optional[int] = None,
                      scaling: Optional[YarnScaling] = None,
                      window: Optional[int] = None, block_k: int = 512,
                      key_mask: Optional[jax.Array] = None,
                      operand_dtype=None,
                      positions: Optional[jax.Array] = None) -> jax.Array:
    """Causal attention of grouped query heads on three projections'
    outputs, token-first from them to its output: q [B, L, H x width],
    k, v [B, L, Hkv x width] -> [B, L, H x width], key/value head j
    serving the query heads [j H / Hkv, (j + 1) H / Hkv); rotary
    positions 0 .. L - 1 at base `theta` as `rope` turns them
    (`rotary_dim`, `scaling`; `theta` None: none), under a `window` the
    band `blockwise_attention` takes, `gate` [B, L, H] a sigmoid gate of
    one column a head on the output. Only for the sizes and the program
    `attention_layout` answers "rows" for: the caller asks it, once, and
    elsewhere holds heads apart and calls `blockwise_attention` (this
    entry has no other route to fall back on). One pass rotates and
    rounds, the kernels read a head as a block of columns, one pass
    gates, and the gradients come back as three arrays
    (`attention_pallas.grouped_attention_pallas`). `operand_dtype`:
    `rotary_attention`'s `grad_dtype` and its rule, for everything the
    caller's products alone read: the three projections' gradients and
    the gated output, `@ wo`'s operand (a caller whose products run at
    the TPU's default precision may say bfloat16: they round these so
    themselves); the results are float32 either way. With `positions`
    [B, L] the rows are packed (`blockwise_attention`'s `packed`): each
    holds several sessions, key_mask [B, L] int32 is a position's session
    id and `positions` its place inside its session, which the rotary
    tables are read by."""
    b, l, _ = q.shape
    assert attention_pallas.layout(width, width) == "rows", width
    _hear("rows")
    _, _, _, pad = _blocks_and_pads(l, l, block_k, None)
    if key_mask is None:
        key_mask = jnp.ones((b, l), bool)
    if pad:     # pad keys are masked out, pad queries cut off the result
        q, k, v = (jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in (q, k, v))
        key_mask = jnp.pad(key_mask, ((0, 0), (0, pad)))
        if gate is not None:
            gate = jnp.pad(gate, ((0, 0), (0, pad), (0, 0)))
        if positions is not None:
            positions = jnp.pad(positions, ((0, 0), (0, pad)))
    tables, shifts = ((), ()) if theta is None else rotary_tables(
        l + pad, width, theta, rotary_dim, scaling, positions)
    return attention_pallas.grouped_attention_pallas(
        q, k, v, gate, key_mask, tables,
        (q.shape[-1] // width, k.shape[-1] // width), shifts, window,
        operand_dtype=operand_dtype, packed=positions is not None)[:, :l]


def _ring_attention_local(q, k, v, key_mask, *, axis: str, causal: bool,
                          batch_axis: Optional[str] = None):
    """shard_map body: q/k/v are the LOCAL sequence shards [B, L/p, H, D];
    key_mask the matching [B, L/p] bool shard (False = padding key). With
    a batch_axis, B is also the local batch shard (dp x sp)."""
    p_size = jax.lax.psum(1, axis)
    r = jax.lax.axis_index(axis)
    b, lq, h, d = q.shape
    lk = k.shape[1]
    scale = d ** -0.5
    q_off = r * lq

    def step(carry, t):
        o, m, l, k_t, v_t, km_t = carry
        # device r holds the kv block originally on device (r + t) mod p
        k_off = ((r + t) % p_size) * lk
        o, m, l = _flash_step(q, k_t, v_t, o, m, l, q_off, k_off,
                              causal, scale, key_mask_j=km_t)
        # rotate: receive the next block from the right neighbor
        perm = [(i, (i - 1) % p_size) for i in range(p_size)]
        k_t = jax.lax.ppermute(k_t, axis, perm)
        v_t = jax.lax.ppermute(v_t, axis, perm)
        km_t = jax.lax.ppermute(km_t, axis, perm)
        return (o, m, l, k_t, v_t, km_t), None

    # zero-init carries must be marked device-varying over every mesh axis
    # the inputs vary over (the ring axis, plus the batch axis under
    # dp x sp) or scan rejects the carry type under shard_map
    vary_axes = (axis,) if batch_axis is None else (axis, batch_axis)

    def _vary(x):
        return jax.lax.pcast(x, vary_axes, to="varying")

    o0 = _vary(jnp.zeros((b, h, lq, d), jnp.float32))
    m0 = _vary(jnp.full((b, h, lq), NEG_INF, jnp.float32))
    l0 = _vary(jnp.zeros((b, h, lq), jnp.float32))
    (o, _, l, _, _, _), _ = jax.lax.scan(
        step, (o0, m0, l0, k, v, key_mask), jnp.arange(p_size))
    return _flash_finish(o, l, q.dtype)


def _batch_axis_of(mesh: Mesh, seq_axis: str) -> Optional[str]:
    """The mesh axis to shard the BATCH dim over inside the ring/Ulysses
    shard_map — "data" when present (dp composes with sp: each data row
    runs its own ring), else replicated."""
    return "data" if ("data" in mesh.axis_names
                      and seq_axis != "data") else None


def _check_seq_divisible(q, mesh, axis):
    if q.shape[1] % mesh.shape[axis]:
        raise ValueError(
            f"seq len {q.shape[1]} not divisible by mesh axis "
            f"'{axis}' size {mesh.shape[axis]}")


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, mesh: Mesh,
                   axis: str = "seq", causal: bool = False,
                   key_mask: Optional[jax.Array] = None) -> jax.Array:
    """Sequence-parallel exact attention over ``mesh[axis]``.

    Inputs [B, L, H, D] are (re)sharded along L; each of the p devices keeps
    its L/p query rows and streams all p K/V blocks through the flash
    recurrence, passing blocks around the ring with ``ppermute`` — peak HBM
    is O(L/p * D) per device, enabling sequences p× longer than one chip
    holds. Returns output sharded the same way. (Host-level entry: places
    the operands, then delegates to ``ring_attention_traced``.)
    """
    _check_seq_divisible(q, mesh, axis)
    ba = _batch_axis_of(mesh, axis)
    sharding = NamedSharding(mesh, P(ba, axis, None, None))
    if key_mask is None:
        key_mask = jnp.ones(q.shape[:2], bool)
    km = jax.device_put(key_mask, NamedSharding(mesh, P(ba, axis)))
    return ring_attention_traced(
        jax.device_put(q, sharding), jax.device_put(k, sharding),
        jax.device_put(v, sharding), mesh, axis, causal, km)


def ring_attention_traced(q: jax.Array, k: jax.Array, v: jax.Array,
                          mesh: Mesh, axis: str = "seq",
                          causal: bool = False,
                          key_mask: Optional[jax.Array] = None) -> jax.Array:
    """`ring_attention` callable from INSIDE a jitted program (a training
    step): no host-side device_put — the shard_map in_specs act as
    sharding constraints and GSPMD inserts the reshard. The batch dim
    shards over "data" when the mesh has one (dp x sp composition). Used
    by the sessionrec train step's sp path (models/seqrec.py)."""
    _check_seq_divisible(q, mesh, axis)
    if key_mask is None:
        key_mask = jnp.ones(q.shape[:2], bool)
    fn = _sharded_fn(_ring_attention_local, mesh, axis, causal,
                     _batch_axis_of(mesh, axis))
    return fn(q, k, v, key_mask)


def _sharded_fn(local_fn, mesh: Mesh, axis: str, causal: bool,
                batch_axis: Optional[str] = None):
    """Cache the jitted shard_map wrapper per (mesh, axis, causal,
    batch_axis) so repeated calls reuse the compiled executable instead
    of re-tracing. Routed through the ops/fn_cache ledger (was a private
    lru_cache) so attention wrapper builds count into
    ``pio_jax_compile_total{family=attention_<impl>}`` and get dispatch
    attribution like every other compiled family. `batch_axis`
    additionally shards the batch dim (dp composed with the sequence
    collective, which only spans `axis`)."""
    from predictionio_tpu.ops.fn_cache import mesh_cached_fn

    def build():
        spec = P(batch_axis, axis, None, None)
        mask_spec = P(batch_axis, axis)
        return jax.jit(shard_map(
            functools.partial(local_fn, axis=axis, causal=causal,
                              batch_axis=batch_axis),
            mesh=mesh, in_specs=(spec, spec, spec, mask_spec),
            out_specs=spec))

    return mesh_cached_fn(f"attention_{local_fn.__name__.strip('_')}",
                          mesh, (axis, causal, batch_axis), build)
