"""Long-context attention with sequence parallelism over a device mesh.

The reference has no attention models (SURVEY.md §5: "long-context /
sequence parallelism — absent"), but this framework treats long-context and
distributed execution as first-class: engines that embed sequence models
(session-based recommendation, event-stream encoders) need attention that
scales past a single chip's HBM. Three strategies, one contract:

* ``mha`` — dense reference implementation (single device, or fully
  replicated); the numerical ground truth the parallel paths are tested
  against.
* ``ring_attention`` — sequence parallelism: Q/K/V sharded along the
  sequence axis of a ``Mesh``; K/V blocks rotate around the ring via
  ``lax.ppermute`` while each device accumulates its queries' output with
  the flash-attention running-max/denominator recurrence. HBM per device is
  O(L/p); comms ride ICI neighbor-to-neighbor, overlapping with the block
  matmuls (the Ring Attention construction, cf. PAPERS.md).
* ``ulysses_attention`` — all-to-all sequence↔head resharding: each device
  gathers the FULL sequence for H/p heads (two ``all_to_all``s), runs dense
  attention locally, and reshards back. Cheaper comms volume than ring for
  moderate L; requires heads % devices == 0.

All paths use the same [batch, seq, heads, head_dim] layout, jit/shard_map
compile to static shapes, and keep the softmax in float32 regardless of
input dtype (bfloat16 QKV with f32 accumulation is the TPU-native recipe:
matmuls hit the MXU in bf16, the recurrence stays stable in f32).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NEG_INF = -1e30    # large-negative instead of -inf: avoids NaN in exp(m - m)


def _causal_mask(scores: jax.Array, q_off, k_off) -> jax.Array:
    """Mask scores [..., Lq, Lk] so query i attends to keys j with
    global_j <= global_i, where globals are local indices + offsets."""
    lq, lk = scores.shape[-2], scores.shape[-1]
    qi = q_off + jnp.arange(lq)[:, None]
    kj = k_off + jnp.arange(lk)[None, :]
    return jnp.where(kj <= qi, scores, NEG_INF)


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


def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        block_k: int = 512, causal: bool = False,
                        key_mask: Optional[jax.Array] = None) -> jax.Array:
    """Flash-style single-device attention: stream over K/V blocks with the
    running-max/denominator recurrence so the [Lq, Lk] score matrix never
    materializes. O(L * block_k) memory; exact (not approximate).
    key_mask: optional [B, Lk] bool, False = key is padding (ignored).
    Sequence lengths that are not a block_k multiple are handled by padding
    K/V up to one and masking the pad keys out."""
    b, lq, h, d = q.shape
    lk = k.shape[1]
    block_k = min(block_k, lk)
    # non-divisible lengths: pad K/V up to a block multiple and mask the
    # pad keys out (cheaper than shrinking the block and re-tiling)
    pad = -lk % block_k
    if key_mask is None:
        key_mask = jnp.ones((b, lk), bool)
    if pad:
        zeros = jnp.zeros((b, pad, h, d), k.dtype)
        k = jnp.concatenate([k, zeros], axis=1)
        v = jnp.concatenate([v, zeros], axis=1)
        key_mask = jnp.concatenate(
            [key_mask, jnp.zeros((b, pad), bool)], axis=1)
    n_blocks = (lk + pad) // block_k
    scale = d ** -0.5
    kb = k.reshape(b, n_blocks, block_k, h, d)
    vb = v.reshape(b, n_blocks, block_k, h, d)
    mb = key_mask.reshape(b, n_blocks, block_k)

    def step(carry, xs):
        j, k_j, v_j, m_j = xs
        o, m, l = _flash_step(q, k_j, v_j, *carry, 0, j * block_k,
                              causal, scale, key_mask_j=m_j)
        return (o, m, l), None

    o0 = jnp.zeros((b, h, lq, d), jnp.float32)
    m0 = jnp.full((b, h, lq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, lq), jnp.float32)
    (o, _, l), _ = jax.lax.scan(
        step, (o0, m0, l0),
        (jnp.arange(n_blocks), jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0),
         jnp.moveaxis(mb, 1, 0)))
    return _flash_finish(o, l, q.dtype)


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


def _ulysses_local(q, k, v, key_mask, *, axis: str, causal: bool,
                   batch_axis=None):  # batch_axis: spec-only, unused here
    """shard_map body: reshard seq-sharded -> head-sharded, dense attention
    on the full sequence for the local head group, reshard back. The key
    mask is all-gathered to full length (tiny: [B, L] bool)."""
    # [B, L/p, H, D] --all_to_all--> [B, L, H/p, D]
    def seq_to_heads(x):
        return jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                  tiled=True)

    def heads_to_seq(x):
        return jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                  tiled=True)

    full_mask = jax.lax.all_gather(key_mask, axis, axis=1, tiled=True)
    out = mha(seq_to_heads(q), seq_to_heads(k), seq_to_heads(v),
              causal=causal, key_mask=full_mask)
    return heads_to_seq(out)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array, mesh: Mesh,
                      axis: str = "seq", causal: bool = False,
                      key_mask: Optional[jax.Array] = None) -> jax.Array:
    """All-to-all sequence parallelism (DeepSpeed-Ulysses construction):
    two ``all_to_all``s swap the sharded dimension seq↔heads so each device
    runs dense attention over the FULL sequence for H/p heads. Requires
    heads divisible by the axis size. Same sharded [B, L, H, D] contract as
    ``ring_attention``."""
    p_size = mesh.shape[axis]
    if q.shape[2] % p_size:
        raise ValueError(
            f"heads {q.shape[2]} not divisible by mesh axis size {p_size}")
    if q.shape[1] % p_size:
        raise ValueError(
            f"seq len {q.shape[1]} not divisible by mesh axis size {p_size}")
    fn = _sharded_fn(_ulysses_local, mesh, axis, causal)
    sharding = NamedSharding(mesh, P(None, axis, None, None))
    if key_mask is None:
        key_mask = jnp.ones(q.shape[:2], bool)
    km = jax.device_put(key_mask, NamedSharding(mesh, P(None, axis)))
    return fn(jax.device_put(q, sharding), jax.device_put(k, sharding),
              jax.device_put(v, sharding), km)
