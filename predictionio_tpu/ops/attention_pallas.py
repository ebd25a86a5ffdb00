"""Blockwise attention as Pallas TPU kernels, forward and backward.

The same mathematics as ``ops/attention._blockwise`` (the flash
recurrence over blocks of queries and keys, a backward pass that
recomputes a block's probabilities from the saved log-sum-exp), with the
block pair's scores and the running accumulators held in VMEM: a query
block's output, maximum and denominator are written to HBM once, and so
is every row of ``dq``, ``dk`` and ``dv``.

Two kernels, each a grid over (batch, head, block pair). The pairs are a
table the kernel is handed (scalar prefetch), so a causal pair whose
keys all lie in the future is no grid step at all; only a pair on the
diagonal, or one that holds a padding key, builds a mask. Under a
`window` (query t sees the keys s with t - window < s <= t) the band has
a second edge: a pair whose keys all lie behind it is no grid step
either, a pair that edge cuts builds a mask too, the block is the band's
own (`WINDOW_BLOCK`) and the kernels are named `window_attention_pallas_*`.
Under `packed` a row holds several sessions one after another and the
key mask is each position's session id (0 = padding, ids rising by one
along a row): a query sees a key of its own session alone (`_keep`
compares the two ids), and the per-block flags become each block's first
and last id (`session_pair`): a pair whose blocks are one session
throughout builds no mask for it, a pair whose blocks share no session
does no products.

* ``flash_attention_pallas_fwd``: pairs query-major, scores [bq, bk];
  output, maximum and denominator of a query block accumulate over its
  key blocks.
* ``flash_attention_pallas_bwd``: pairs key-major, scores transposed
  [bk, bq] so that ``lse`` and ``delta`` lie along lanes and four of the
  five products need no transposed operand; ``dk`` and ``dv`` of a key
  block accumulate over its query blocks, and ``dq`` of one (batch row,
  head) stays in VMEM whole until every pair has added to it: that bounds
  the length the kernels take (``tiles``).

Two layouts of the operands in HBM, one kernel pair: a head's block is
[block, width] of VMEM either way, and only the index maps know where it
came from. The entry chooses. `flash_attention_pallas` and
`window_attention_pallas` take them head-first, q [B, H, L, Dk]: a head
is an index of axis 1 (any width the kernels tile, grouped query heads).
Two entries take a projection's output token-first, q [B, L, H x Dk]: a
head is a block of columns, which Mosaic takes where a head's width is a
whole number of lane tiles (`layout`: their callers ask first).
`rotary_attention_pallas` takes one product's [q | k | v] with as many
key/value heads as query heads; `grouped_attention_pallas` three
products' q, k, v at any head counts (key/value head h // group a block
of its own array's columns), whole-causal or under a window, with
whichever rotary table and a gate of one column a head. There nothing
between a projection and a kernel changes an array's layout, and one
pass lies between them: rotary positions and the rounding.

Precision: every product takes its operands in bfloat16 (what the TPU's
default does to float32 operands), rounded once on their way in, and
accumulates in float32; scores, maxima, exponentials, denominators,
``lse``, ``delta`` and all accumulators are float32.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30    # large-negative instead of -inf: avoids NaN in exp(m - m)

#: query and key block of both kernels: the largest of 1024, 512, 256, 128
#: that divides the length. A constant, from chip runs of each kernel alone
#: at 2 x 16 heads x 8,192 positions, widths 192 / 128 (PERF.md section 6,
#: PR 30), query x key block: a forward call took 11.4 ms at 512 x 512,
#: 8.5 at 512 x 1024, 8.2 at 1024 x 1024, 9.2 at 1024 x 2048, 15.0 at
#: 2048 x 2048; a backward call 17.5 at 512 x 512, 17.2 at 512 x 1024,
#: 16.8 at 1024 x 1024, 29.6 at 2048 x 1024. At 1 x 32 (8 key/value) heads
#: x 32,768 positions, widths 64 / 64 (PR 33), one block for both: forward
#: 122.4 ms at 512, 68.6 at 1024, 74.4 at 2048; forward + backward 271.9,
#: 198.6, 217.5: the block does not follow the width.
BLOCK = 1024
#: the block under a window: the largest of 512, 256, 128 that divides the
#: length. A constant, from chip runs of each kernel alone at 1 x 64 (8
#: key/value) heads x 16,384 positions x 128 under a window of 512 (PERF.md
#: section 6, PR 44), one block for both: a forward call took 29.8 ms at
#: 128, 16.7 at 256, 11.2 at 512, 13.8 at 1024; forward + backward 64.6,
#: 36.1, 25.5, 34.6 (the whole causal triangle there: 37.9 and 107.0). Of
#: the scores the visited blocks compute, 80%, 67%, 50% and 25% lie inside
#: the band: a small block wastes less and pays more grid steps, and the
#: triangle's 1024 computes four scores for one that counts. Under a window
#: of 1,024 at 2 x 32 (4 key/value) heads x 8,192 positions x 128 (PR 47,
#: `tools/seqrec_packed_probe.py --sweep`), a session a row: forward 7.14
#: ms at 512, 6.74 at 1024; forward + backward 16.14, 16.75 (67% and 50%
#: of the visited scores inside the band); on packed rows (`packed`) 6.89,
#: 7.19 and 16.06, 18.32 (37% and 22% inside band and session): 512 stays.
WINDOW_BLOCK = 512

#: the device kinds (`jax.Device.device_kind`) the block and the two limits
#: below were measured on; `ops/attention.attention_route` sends no other
#: kind here. A v5e core has 128 MiB of VMEM: a generation with less would
#: refuse at compile time what these limits let through, and one with more
#: is a chip run away from being listed.
KINDS = ("TPU v5 lite",)

#: what Mosaic may use of a v5e core's 128 MiB (its default is 16): a
#: pair's scores and their temporaries take some 30 MiB at 1024 x 1024,
#: `dq` of one (batch row, head) twice its size (an output block has two
#: buffers)
_VMEM_LIMIT = 100 * 1024 * 1024
_DQ_VMEM = 48 * 1024 * 1024

_NT = (((1,), (1,)), ((), ()))     # a @ b.T


def _block(length: int, window: Optional[int] = None) -> int:
    """The largest of BLOCK, BLOCK / 2, / 4, / 8 that divides the length,
    0 if none does; under a window, of WINDOW_BLOCK down to one lane
    tile."""
    largest = BLOCK if window is None else WINDOW_BLOCK
    return next((b for b in (largest, largest // 2, largest // 4,
                             largest // 8)
                 if b >= 128 and length % b == 0), 0)


def tiles(lq: int, lk: int, dk: int, dv: int,
          window: Optional[int] = None) -> bool:
    """Whether the kernels lay these shapes out: lengths in whole lane
    tiles, widths in half lane tiles (a block's trailing dimension is
    the array's whole width), and `dq` of a (batch row, head), float32
    on whole lane tiles, within its VMEM (whole whatever the window: a
    key block's pairs add to the rows of its band alone, but the block
    that holds them is the head's)."""
    return (_block(lq, window) > 0 and _block(lk, window) > 0
            and dk % 64 == 0 and dv % 64 == 0
            and 2 * lq * -(-dk // 128) * 128 * 4 <= _DQ_VMEM)


def layout(dk: int, dv: int) -> str:
    """Where the kernels may read a head of these widths: "rows",
    token-first, as a block of columns of [B, L, heads x width], where
    both are whole lane tiles (what `rotary_attention_pallas` needs);
    "heads", head-first [B, heads, L, width] alone, everywhere else."""
    return "rows" if dk % 128 == 0 and dv % 128 == 0 else "heads"


def sees(query, key, causal: bool, window: Optional[int] = None):
    """Whether the query at position `query` sees the key at `key`: if
    causal, no key in its future; under a window, none `window`
    positions or more behind it either (t - window < s <= t with both).
    The one statement of the band's edges, on numbers or on arrays: the
    kernels' masks and the scan's (ops/attention.py) are this, and the
    pair tables hold it at a block pair's corners."""
    seen = key <= query if causal else True
    if window is not None:
        seen = seen & (key > query - window)
    return seen


#: the id a padding position takes in a packed row's block table, past
#: every session's: ids then rise along the whole row (padding is a
#: row's tail)
PAD_ID = 2 ** 30


def session_pair(first_q, last_q, first_k, last_k):
    """Of a block pair of a packed row, from the first and the last
    session id of its query block and of its key block (ids rise by one
    along a row, padding reads `PAD_ID`; numbers or arrays): (whether
    the pair holds a query and a key of one session: the two ranges of
    ids meet and neither block is all padding; whether both blocks are
    one and the same session throughout, so that no id need be
    compared). The three states of a packed pair: no products, a mask,
    no mask."""
    does = (first_q < PAD_ID) & (first_k < PAD_ID) \
        & (last_k >= first_q) & (first_k <= last_q)
    one = (first_k == last_k) & (first_q == last_q) & (first_k == first_q)
    return does, one


def session_blocks(ids, block: int):
    """ids [B, L] of a packed batch (0 = padding) -> (first, last) [B,
    L / block]: each block's first and last session id, padding as
    `PAD_ID` (numpy or jax arrays)."""
    b, l = ids.shape
    rising = (jnp if isinstance(ids, jax.Array) else np).where(
        ids > 0, ids, PAD_ID).reshape(b, l // block, block)
    return rising[:, :, 0], rising[:, :, -1]


def _block_pairs(n_q: int, n_k: int, bq: int, bk: int, causal: bool,
                 key_major: bool,
                 window: Optional[int] = None) -> np.ndarray:
    """[pairs, 2] (query block, key block) with a score that counts, the
    one table both routes walk (`ops/attention._block_pairs` is this
    one, query-major). The distances query - key of a pair's scores are
    every whole number between its corners', so it holds a score that
    `sees` iff its last query is not before its first key (causal) and
    its first query less than a window past its last key."""
    def holds(i, j):
        first_q, first_k = i * bq, j * bk
        return sees(first_q + bq - 1, first_k, causal) and (
            window is None or first_k + bk - 1 > first_q - window)

    pairs = [(i, j) for i in range(n_q) for j in range(n_k) if holds(i, j)]
    if key_major:
        pairs.sort(key=lambda ij: (ij[1], ij[0]))
    return np.asarray(pairs, np.int32).reshape(-1, 2)


def _keep(mask, i, j, bq, bk, causal, keys_on_rows, window=None,
          queries=None):
    """Which scores of pair (i, j) count: the key is no padding and the
    query `sees` it. mask: the key block's, laid along the scores' key
    axis; `queries` (a packed row): the query block's session ids along
    the scores' query axis, beside which `mask` holds the keys' ids: a
    query sees the keys of its own session alone."""
    keep = mask > 0
    if queries is not None:
        keep = keep & (mask == queries)
    if not causal:
        return keep
    shape = (bk, bq) if keys_on_rows else (bq, bk)
    key_axis = 0 if keys_on_rows else 1
    keys = j * bk + jax.lax.broadcasted_iota(jnp.int32, shape, key_axis)
    queries = i * bq + jax.lax.broadcasted_iota(jnp.int32, shape,
                                                1 - key_axis)
    return keep & sees(queries, keys, causal, window)


def _masked_or_not(fold, full_ref, b, i, j, n_k, bq, bk, causal,
                   window=None, packed=None):
    """Run fold(masked) with masked True only where the pair needs it: a
    padding key, or a corner an edge cuts off (the first query and the
    last key at the causal edge, the last query and the first key at the
    band's trailing one). `packed` (a packed row; where the blocks' last
    ids start in the table, behind their first): a pair of more than one
    session needs it too, and one whose blocks share no session is not
    run at all (`session_pair`)."""
    if packed is not None:
        at_q, at_k = b * n_k + i, b * n_k + j
        does, one = session_pair(full_ref[at_q], full_ref[packed + at_q],
                                 full_ref[at_k], full_ref[packed + at_k])
        needs = jnp.logical_not(one)
        if causal:
            needs = needs | (j * bk + bk - 1 > i * bq)
        if window is not None:
            needs = needs | (j * bk <= i * bq + bq - 1 - window)
        pl.when(does & needs)(lambda: fold(True))
        pl.when(does & jnp.logical_not(needs))(lambda: fold(False))
        return
    needs = full_ref[b * n_k + j] == 0
    if causal:
        needs = needs | (j * bk + bk - 1 > i * bq)
    if window is not None:
        needs = needs | (j * bk <= i * bq + bq - 1 - window)
    pl.when(needs)(lambda: fold(True))
    pl.when(jnp.logical_not(needs))(lambda: fold(False))


def _fwd_kernel(qi_ref, kj_ref, full_ref, q_ref, k_ref, v_ref, mask_ref,
                *refs, scale, causal, bq, bk, n_k, save_lse, window=None,
                packed=None):
    qid_ref = None
    if packed is not None:      # the query block's session ids [bq, 1]
        qid_ref, *refs = refs
    if save_lse:
        o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    else:
        o_ref, m_scr, l_scr, acc_scr = refs
    b, p = pl.program_id(0), pl.program_id(2)
    i, j = qi_ref[p], kj_ref[p]
    last_j = jnp.minimum(((i + 1) * bq - 1) // bk, n_k - 1) \
        if causal else n_k - 1
    # the first key block of the query block's pairs: under a window, the
    # one that holds the last key its first query sees
    first_j = 0 if window is None \
        else jnp.maximum(i * bq - window + 1, 0) // bk

    @pl.when(j == first_j)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def fold(masked):
        s = jax.lax.dot_general(q_ref[0, 0], k_ref[0, 0], _NT,
                                preferred_element_type=jnp.float32) * scale
        if masked:
            keep = _keep(mask_ref[0], i, j, bq, bk, causal, False, window,
                         None if qid_ref is None else qid_ref[0])
            s = jnp.where(keep, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        prob = jnp.exp(s - m_new)
        if masked:
            # explicit zero for masked scores: with the finite NEG_INF
            # sentinel, exp(s - m_new) would be 1 (not 0) in all-masked
            # rows
            prob = jnp.where(keep, prob, 0.0)
        l_scr[...] = l_scr[...] * alpha + prob.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            prob.astype(v_ref.dtype), v_ref[0, 0],
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    _masked_or_not(fold, full_ref, b, i, j, n_k, bq, bk, causal, window,
                   packed)

    @pl.when(j == last_j)
    def _():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)     # a fully masked row gives 0
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
        if save_lse:
            lse_ref[0, 0] = m_scr[...] + jnp.log(l)


def _bwd_kernel(qi_ref, kj_ref, full_ref, q_ref, k_ref, v_ref, mask_ref,
                *refs, scale, causal, bq, bk, n_q, n_k, window=None,
                packed=None):
    qid_ref = None
    if packed is not None:      # the query block's session ids [1, bq]
        qid_ref, *refs = refs
    do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref, dk_scr, dv_scr = refs
    b, p = pl.program_id(0), pl.program_id(2)
    i, j = qi_ref[p], kj_ref[p]
    first_i = (j * bk) // bq if causal else 0
    # the last query block of the key block's pairs: under a window, the
    # one that holds the last query that sees its last key
    last_i = n_q - 1 if window is None else jnp.minimum(
        (j * bk + bk + window - 2) // bq, n_q - 1)

    @pl.when(p == 0)
    def _():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    @pl.when(i == first_i)
    def _():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def fold(masked):
        q, k, do = q_ref[0, 0], k_ref[0, 0], do_ref[0, 0]
        s = jax.lax.dot_general(k, q, _NT,
                                preferred_element_type=jnp.float32) * scale
        prob = jnp.exp(s - lse_ref[0, 0])
        if masked:
            prob = jnp.where(_keep(
                mask_ref[0], i, j, bq, bk, causal, True, window,
                None if qid_ref is None else qid_ref[0]), prob, 0.0)
        dv_scr[...] += jnp.dot(prob.astype(do.dtype), do,
                               preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v_ref[0, 0], do, _NT,
                                 preferred_element_type=jnp.float32)
        ds = prob * (dp - delta_ref[0, 0]) * scale
        dk_scr[...] += jnp.dot(ds.astype(q.dtype), q,
                               preferred_element_type=jnp.float32)
        rows = pl.ds(pl.multiple_of(i * bq, bq), bq)
        dq_ref[0, 0, rows, :] += jnp.dot(ds.T.astype(k.dtype), k,
                                         preferred_element_type=jnp.float32)

    _masked_or_not(fold, full_ref, b, i, j, n_k, bq, bk, causal, window,
                   packed)

    @pl.when(i == last_i)
    def _():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _call(kernel, name, key_mask, pairs, heads, bk, in_specs, out_specs,
          out_shape, scratch_shapes, interpret, packed=False):
    """The pallas_call over (batch row, head, pair), applied to the pair
    table: each pair's query and key block and, per (batch row, key
    block), whether every key in it is real (`packed`: its first session
    id and, behind those, its last: `session_blocks`). Index maps see
    (b, h, pair, qi, kj, full)."""
    b, lk = key_mask.shape
    if packed:
        full = jnp.stack(session_blocks(key_mask, bk))
    else:
        full = key_mask.reshape(b, lk // bk, bk).all(axis=-1)
    return functools.partial(
        pl.pallas_call(
            kernel, name=name, out_shape=out_shape,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=(b, heads, len(pairs)),
                in_specs=in_specs, out_specs=out_specs,
                scratch_shapes=scratch_shapes),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret),
        jnp.asarray(pairs[:, 0]), jnp.asarray(pairs[:, 1]),
        full.reshape(-1).astype(jnp.int32))


def _specs(bq, bk, group=1, rows=False):
    """Block specs by what a block follows: the pair's query block, its
    key block (per query head: the gradients), or the key block of the
    key/value head that serves the query head (`group` query heads
    each); `whole`, all of a head's positions. A head is an index of
    axis 1 of [B, heads, L, width] or, under `rows`, a block of columns
    of [B, 1, L, heads x width]."""
    def spec(length, width, block, head=lambda h: h):
        def index(b, h, p, qi, kj, full):
            if rows:
                return (b, 0, block(p, qi, kj), head(h))
            return (b, head(h), block(p, qi, kj), 0)
        return pl.BlockSpec((1, 1, length, width), index)

    def by_query(width):
        return spec(bq, width, lambda p, qi, kj: qi[p])

    def by_key(width):
        return spec(bk, width, lambda p, qi, kj: kj[p])

    def by_kv_head(width):
        return spec(bk, width, lambda p, qi, kj: kj[p], lambda h: h // group)

    def whole(length, width):
        return spec(length, width, lambda p, qi, kj: 0)

    return by_query, by_key, by_kv_head, whole


def _sizes(q, k, v, heads):
    """(batch, query heads, key/value heads, lq, lk, dk, dv) of operands
    head-first (`heads` None) or token-first, q [B, 1, L, H x Dk], k, v
    [B, 1, L, Hkv x .] (`heads` = (H, Hkv): the shapes alone do not say
    where one head ends)."""
    if heads is None:
        return (q.shape[0], q.shape[1], k.shape[1], q.shape[2], k.shape[2],
                q.shape[3], v.shape[3])
    h, kv = heads
    return (q.shape[0], h, kv, q.shape[2], k.shape[2], q.shape[3] // h,
            v.shape[3] // kv)


def _shape(b, h, length, width, rows):
    return (b, 1, length, h * width) if rows else (b, h, length, width)


def _kernel(kernel, name: str, window, packed=None, **sizes):
    """The kernel at its sizes and its name in a device trace: a
    windowed call's is its own (`window_attention_pallas_*`), so that the
    band's time reads apart from the whole-causal calls'. `packed`: of a
    packed batch, where the blocks' last ids start in the table."""
    if packed is not None:
        sizes["packed"] = packed
    if window is None:
        return functools.partial(kernel, **sizes), \
            f"flash_attention_pallas_{name}"
    return functools.partial(kernel, window=window, **sizes), \
        f"window_attention_pallas_{name}"


def _packed_at(packed, b, lq, lk, bq, bk):
    """Where a packed batch's table holds its blocks' last ids (None:
    the batch is not packed). One row of ids serves queries and keys, so
    both lengths and both blocks are the same."""
    if not packed:
        return None
    if lq != lk or bq != bk:
        raise ValueError(f"packed rows attend to themselves: {lq} queries "
                         f"and {lk} keys in blocks of {bq} and {bk}")
    return b * (lk // bk)


def _forward(q, k, v, key_mask, causal, bq, bk, interpret, save_lse,
             heads=None, window=None, packed=False):
    b, h, kv, lq, lk, dk, dv = _sizes(q, k, v, heads)
    rows = heads is not None
    pairs = _block_pairs(lq // bq, lk // bk, bq, bk, causal, False, window)
    by_query, _, by_kv_head, _ = _specs(bq, bk, h // kv, rows)
    # a packed row's ids once more, along the scores' query axis
    query_ids = [pl.BlockSpec((1, bq, 1),
                              lambda b, h, p, qi, kj, full: (b, qi[p], 0))] \
        if packed else []
    out_shape = [jax.ShapeDtypeStruct(_shape(b, h, lq, dv, rows),
                                      jnp.float32)]
    out_specs = [by_query(dv)]
    if save_lse:        # head-first in either layout
        out_shape.append(jax.ShapeDtypeStruct((b, h, lq, 1), jnp.float32))
        out_specs.append(_specs(bq, bk)[0](1))
    return _call(
        *_kernel(_fwd_kernel, "fwd", window,
                 _packed_at(packed, b, lq, lk, bq, bk), scale=dk ** -0.5,
                 causal=causal, bq=bq, bk=bk, n_k=lk // bk,
                 save_lse=save_lse),
        key_mask, pairs, h, bk,
        [by_query(dk), by_kv_head(dk), by_kv_head(dv),
         pl.BlockSpec((1, 1, bk),
                      lambda b, h, p, qi, kj, full: (b, 0, kj[p]))]
        + query_ids,
        out_specs, out_shape,
        [pltpu.VMEM((bq, 1), jnp.float32), pltpu.VMEM((bq, 1), jnp.float32),
         pltpu.VMEM((bq, dv), jnp.float32)], interpret, packed)(
        q, k, v, key_mask.astype(jnp.int32)[:, None, :],
        *([key_mask.astype(jnp.int32)[:, :, None]] if packed else []))


def _backward(q, k, v, key_mask, d_out, lse, delta, causal, bq, bk,
              interpret, heads=None, window=None, packed=False):
    b, h, kv, lq, lk, dk, dv = _sizes(q, k, v, heads)
    rows = heads is not None
    pairs = _block_pairs(lq // bq, lk // bk, bq, bk, causal, True, window)
    group = h // kv
    by_query, by_key, by_kv_head, whole = _specs(bq, bk, group, rows)
    row = pl.BlockSpec((1, 1, 1, bq),
                       lambda b, h, p, qi, kj, full: (b, h, 0, qi[p]))
    query_ids = [pl.BlockSpec((1, 1, bq),
                              lambda b, h, p, qi, kj, full: (b, 0, qi[p]))] \
        if packed else []
    dq, d_k, d_v = _call(
        *_kernel(_bwd_kernel, "bwd", window,
                 _packed_at(packed, b, lq, lk, bq, bk), scale=dk ** -0.5,
                 causal=causal, bq=bq, bk=bk, n_q=lq // bq, n_k=lk // bk),
        key_mask, pairs, h, bk,
        [by_query(dk), by_kv_head(dk), by_kv_head(dv),
         pl.BlockSpec((1, bk, 1),
                      lambda b, h, p, qi, kj, full: (b, kj[p], 0))]
        + query_ids + [by_query(dv), row, row],
        [whole(lq, dk), by_key(dk), by_key(dv)],
        [jax.ShapeDtypeStruct(_shape(b, h, length, width, rows), jnp.float32)
         for length, width in ((lq, dk), (lk, dk), (lk, dv))],
        [pltpu.VMEM((bk, dk), jnp.float32),
         pltpu.VMEM((bk, dv), jnp.float32)], interpret, packed)(
        q, k, v, key_mask.astype(jnp.int32)[:, :, None],
        *([key_mask.astype(jnp.int32)[:, None, :]] if packed else []), d_out,
        lse[:, :, None, :], delta[:, :, None, :])
    seen = -(-lq // bk) * bk      # causal keys past every query: no pair
    if causal and seen < lk:
        d_k, d_v = (t.at[:, :, seen:].set(0.0) for t in (d_k, d_v))
    if group > 1 and not rows:
        # a key/value head's gradient: its query heads' sum. (Token-first
        # they stay a block of columns a query head, which the pass behind
        # the kernels adds up as it reads them: `_grouped_back`.)
        d_k, d_v = (t.reshape(b, h // group, group, lk, -1).sum(axis=2)
                    for t in (d_k, d_v))
    return dq, d_k, d_v


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def flash_attention_pallas(q, k, v, key_mask, causal: bool,
                           interpret: bool = False):
    """q [B, H, Lq, Dk], k [B, Hkv, Lk, Dk], v [B, Hkv, Lk, Dv] of one
    dtype, Hkv a divisor of H (key/value head j serves the query heads
    [j H / Hkv, (j + 1) H / Hkv): the kernels' index maps read it where
    it lies, and the backward kernel's per-query-head `dk`, `dv` are
    summed over the group after it), lengths multiples of 128; key_mask
    [B, Lk] bool, False = padding -> [B, H, Lq, Dv] in that dtype.
    The operands lie head-first; `rotary_attention_pallas` and
    `grouped_attention_pallas` (grouped heads too) are the entries that
    reach the same kernels token-first.
    `interpret` runs the kernels in the Pallas interpreter (the CPU
    tests). `window_attention_pallas` is the same call over a band."""
    return _fwd(q, k, v, key_mask, causal, interpret, save_lse=False)[0]


def _fwd(q, k, v, key_mask, causal, interpret, save_lse=True, heads=None,
         dtype=None, window=None, packed=False):
    """-> (the output in `dtype`, the operands' by default; what the
    backward pass keeps). With `heads` = (H, Hkv) the operands lie
    token-first, where a projection writes them (`layout` "rows"): q [B,
    L, H x Dk], k [B, L, Hkv x Dk], v [B, L, Hkv x Dv] -> [B, L, H x Dv],
    and so do `_bwd`'s gradients. `packed`: key_mask [B, L] holds each
    position's session id (0 = padding) and a query sees its own
    session's keys alone."""
    if not q.dtype == k.dtype == v.dtype:
        raise ValueError(f"q, k, v of one dtype expected, got {q.dtype}, "
                         f"{k.dtype}, {v.dtype}")
    ops = tuple(t.astype(jnp.bfloat16) for t in (q, k, v))
    if heads is not None:       # [B, 1, L, .]: no array moves for it
        ops = tuple(t[:, None] for t in ops)
    bq, bk = (_block(t.shape[2], window) for t in ops[:2])
    out, *lse = _forward(*ops, key_mask, causal, bq, bk, interpret, save_lse,
                         heads, window, packed)
    out = out.astype(dtype or q.dtype)
    shown = out if heads is None else out[:, 0]
    if not save_lse:
        return shown, None
    return shown, (*ops, key_mask, out, lse[0][..., 0])


def _bwd(causal, interpret, res, d_out, heads=None, window=None, delta=None,
         packed=False):
    """-> (dq, dk, dv, None) in the output's type. Token-first under
    grouped heads `dk` and `dv` are [B, L, H x .], a block of columns a
    QUERY head (`_grouped_back` adds a group's up). `delta` [B, H, Lq]:
    each head's sum of d_out x out over its width, from the caller that
    has it already."""
    q, k, v, key_mask, out, lse = res
    if heads is not None:
        d_out = d_out[:, None]
    if delta is None:
        delta = _head_sums(
            d_out.astype(jnp.float32) * out.astype(jnp.float32),
            None if heads is None else heads[0])                # [B, H, Lq]
    grads = _backward(q, k, v, key_mask, d_out.astype(jnp.bfloat16), lse,
                      delta, causal, _block(q.shape[2], window),
                      _block(k.shape[2], window), interpret, heads, window,
                      packed)
    if heads is not None:
        grads = tuple(g[:, 0] for g in grads)
    return (*(g.astype(out.dtype) for g in grads), None)


def _head_sums(t, heads):
    """Each head's sum over its width -> [B, H, L]: of t [B, H, L, D],
    or of token-first t [B, 1, L, H x D] as a product with the heads'
    indicator columns (a sum over a block of lanes with no array split
    into heads), exact to float32."""
    if heads is None:
        return t.sum(axis=-1)
    ones = jnp.repeat(jnp.eye(heads, dtype=t.dtype), t.shape[-1] // heads,
                      axis=0)
    return jnp.einsum("blc,ch->bhl", t[:, 0], ones,
                      precision=jax.lax.Precision.HIGHEST)


flash_attention_pallas.defvjp(_fwd, _bwd)


def _causal_call(name: str, packed: bool, doc: str):
    """A causal custom_vjp call (q, k, v, key_mask, window, interpret)
    under `name`: the band's or the whole-causal kernels by `window`,
    `packed` or not (`_fwd`)."""
    def call(q, k, v, key_mask, window, interpret=False):
        return _fwd(q, k, v, key_mask, True, interpret, save_lse=False,
                    window=window, packed=packed)[0]

    call.__name__ = call.__qualname__ = name
    call.__doc__ = doc
    call = jax.custom_vjp(call, nondiff_argnums=(4, 5))
    call.defvjp(
        lambda q, k, v, key_mask, window, interpret: _fwd(
            q, k, v, key_mask, True, interpret, window=window,
            packed=packed),
        lambda window, interpret, res, d_out: _bwd(
            True, interpret, res, d_out, window=window, packed=packed))
    return call


window_attention_pallas = _causal_call(
    "window_attention_pallas", False,
    """`flash_attention_pallas`, causal, over a band: a query sees its
    own key and the `window` - 1 before it (`sees`; window >= 1). The
    same kernel bodies on tables that leave out every pair wholly behind
    the band, in blocks of the band's own (`WINDOW_BLOCK`), under names
    of their own (`window_attention_pallas_fwd` / `_bwd`).""")

packed_attention_pallas = _causal_call(
    "packed_attention_pallas", True,
    """`flash_attention_pallas`, causal (under a `window` the band's
    kernels, else None), over packed rows: key_mask [B, L] int32, each
    position's session id (0 = padding, ids rising by one along a row);
    a query sees the keys of its own session alone. Lq = Lk: a row
    attends to itself.""")


# -- rotary positions and the cast, on the projection's columns ----------

def rotary_table(length: int, width: int, theta: float):
    """(cos, sin) [L, width] float32 of positions 0 .. L - 1 in the
    "halves" pairing of `ops/attention.rope`, laid out for a head's
    columns where they lie: column i of a head rotates with column
    i +- width / 2, its partner a roll of the head's lanes by half its
    width, so the sine carries the sign: [-sin | sin], beside [cos |
    cos]."""
    half = width // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(length, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return (jnp.concatenate([cos, cos], axis=-1),
            jnp.concatenate([-sin, sin], axis=-1))


def _rotary_fwd_kernel(q_ref, k_ref, v_ref, cos_ref, sin_ref, qo_ref, ko_ref,
                       vo_ref, *, half):
    cos, sin = cos_ref[...], sin_ref[...]
    for x_ref, o_ref in ((q_ref, qo_ref), (k_ref, ko_ref)):
        x = x_ref[0]
        o_ref[0] = (x * cos + pltpu.roll(x, half, 1) * sin).astype(
            o_ref.dtype)
    vo_ref[0] = v_ref[0].astype(vo_ref.dtype)


def _rotary_bwd_kernel(dq_ref, dk_ref, dv_ref, cos_ref, sin_ref, o_ref, *,
                       heads, half):
    c = pl.program_id(2)

    def turned_back(d_ref):
        # the rotation's transpose: the partner's product, rolled home
        d = d_ref[0]
        o_ref[0] = (d * cos_ref[...] + pltpu.roll(d * sin_ref[...], half, 1)
                    ).astype(o_ref.dtype)

    pl.when(c < heads)(lambda: turned_back(dq_ref))
    pl.when((c >= heads) & (c < 2 * heads))(lambda: turned_back(dk_ref))

    @pl.when(c >= 2 * heads)
    def _():
        o_ref[0] = dv_ref[0].astype(o_ref.dtype)


def _rotary_call(kernel, name, grid, in_specs, out_specs, out_shape,
                 interpret):
    return pl.pallas_call(
        kernel, name=name, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret)


def _rotary(qkv, cos, sin, heads, interpret):
    """The projection [B, L, 3 x H x D] float32, read where it lies ->
    q, k (rotated in float32) and v [B, L, H x D], each rounded once to
    bfloat16: a grid over (batch row, row block, head)."""
    b, l, width = qkv.shape
    d, rows = width // (3 * heads), _block(l)
    block = lambda first: pl.BlockSpec(
        (1, rows, d), lambda b, i, c: (b, i, first + c))
    table = pl.BlockSpec((rows, d), lambda b, i, c: (i, 0))
    return _rotary_call(
        functools.partial(_rotary_fwd_kernel, half=d // 2),
        "attention_rotary_fwd", (b, l // rows, heads),
        [block(0), block(heads), block(2 * heads), table, table],
        [block(0)] * 3,
        [jax.ShapeDtypeStruct((b, l, heads * d), jnp.bfloat16)] * 3,
        interpret)(qkv, qkv, qkv, cos, sin)


def _rotary_backward(dq, dk, dv, cos, sin, heads, interpret,
                     dtype=jnp.float32):
    """dq, dk, dv [B, L, H x D] float32 -> the projection's gradient
    [B, L, 3 x H x D] in `dtype`, the rotation transposed in float32 (and
    rounded once, where `dtype` is narrower): a grid over (batch row, row
    block, the projection's 3 H blocks of columns), each gradient's block
    read while the grid is in its columns."""
    b, l, width = dq.shape
    d, rows = width // heads, _block(l)
    part = lambda n: pl.BlockSpec(
        (1, rows, d), lambda b, i, c: (
            b, i, jnp.clip(c - n * heads, 0, heads - 1)))
    table = pl.BlockSpec((rows, d), lambda b, i, c: (i, 0))
    return _rotary_call(
        functools.partial(_rotary_bwd_kernel, heads=heads, half=d // 2),
        "attention_rotary_bwd", (b, l // rows, 3 * heads),
        [part(0), part(1), part(2), table, table],
        pl.BlockSpec((1, rows, d), lambda b, i, c: (b, i, c)),
        jax.ShapeDtypeStruct((b, l, 3 * width), dtype),
        interpret)(dq, dk, dv, cos, sin)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def rotary_attention_pallas(qkv, key_mask, heads: int, theta: float,
                            causal: bool, interpret: bool = False,
                            grad_dtype=None):
    """Multi-head attention with rotary positions from its projection to
    its output, token-first all the way: qkv [B, L, 3 x H x D] float32,
    the columns [q | k | v] of `x @ wqkv` (D a whole number of lane
    tiles, L a multiple of 128), key_mask [B, L] bool -> [B, L, H x D]
    float32. One pass over the projection's columns rotates q and k in
    float32 and rounds q, k, v once to bfloat16 (`attention_rotary_fwd`);
    the kernels read its outputs where they lie; backward the kernels'
    float32 `dq`, `dk`, `dv` go through the rotation's transpose into one
    [B, L, 3 x H x D] array (`attention_rotary_bwd`): no array between
    the projection and the kernels is split, joined or relaid. That
    gradient is float32 as the kernels gave it, unless the caller names
    a `grad_dtype`: then the pass writes it rounded once to that type,
    after the float32 transpose (the cotangent keeps qkv's type and
    holds the rounded values). That is for the caller that owns `x @
    wqkv` to ask for: where its product takes its operands in one
    bfloat16 pass, the two backward products that read this gradient
    round it so themselves, and bfloat16 here is their rounding a pass
    earlier at half the bytes; under a product of higher precision it
    would lose what that product keeps."""
    return _rotary_attention_fwd(qkv, key_mask, heads, theta, causal,
                                 interpret, grad_dtype, save_lse=False)[0]


def _rotary_attention_fwd(qkv, key_mask, heads, theta, causal, interpret,
                          grad_dtype=None, save_lse=True):
    table = rotary_table(qkv.shape[1], qkv.shape[2] // (3 * heads), theta)
    return _fwd(*_rotary(qkv, *table, heads, interpret), key_mask, causal,
                interpret, save_lse, (heads, heads), qkv.dtype)


def _rotary_attention_bwd(heads, theta, causal, interpret, grad_dtype, res,
                          d_out):
    dq, dk, dv, _ = _bwd(causal, interpret, res, d_out, (heads, heads))
    table = rotary_table(dq.shape[1], dq.shape[2] // heads, theta)
    return _rotary_backward(dq, dk, dv, *table, heads, interpret,
                            grad_dtype or dq.dtype).astype(dq.dtype), None


rotary_attention_pallas.defvjp(_rotary_attention_fwd, _rotary_attention_bwd)


# -- grouped heads token-first: the passes around the kernels ------------
#
# q [B, L, H x D] and k, v [B, L, Hkv x D] are three products' outputs (a
# gate of one column a head, [B, L, H], a fourth's). One pass in front of
# the kernels turns q and k and rounds all three (`_grouped_front`), one
# behind them adds a group's `dk`, `dv` up and turns the gradients back
# (`_grouped_back`), and the gate is a pass over the output's columns
# (`_gated`). Head counts are grids and rotary variants tables: a body
# knows a head's width and the rolls' distances.

def _turned(x, tables, shifts, back=False):
    """x [rows, D] float32 against the tables' blocks (refs: cos, then a
    signed sine a roll): x cos + sum_s roll(x, s) sin_s, the partner of
    column i the column i - s; `back`, its transpose. No table: x. (A
    table by batch row, [B, L, D], comes as a block [1, rows, D].)"""
    if not shifts:
        return x
    at = lambda ref: ref[0] if len(ref.shape) == 3 else ref[...]
    width = x.shape[-1]
    out = x * at(tables[0])
    for sin_ref, shift in zip(tables[1:], shifts):
        out += pltpu.roll(x * at(sin_ref), width - shift, 1) if back \
            else pltpu.roll(x, shift, 1) * at(sin_ref)
    return out


def _grouped_front_kernel(q_ref, k_ref, v_ref, *refs, shifts):
    *tables, qo_ref, ko_ref, vo_ref = refs
    qo_ref[0] = _turned(q_ref[0], tables, shifts).astype(qo_ref.dtype)

    @pl.when(pl.program_id(3) == 0)     # the group's first query head
    def _():
        ko_ref[0] = _turned(k_ref[0], tables, shifts).astype(ko_ref.dtype)
        vo_ref[0] = v_ref[0].astype(vo_ref.dtype)


def _grouped_back_kernel(dq_ref, dk_ref, dv_ref, *refs, shifts):
    *tables, qo_ref, ko_ref, vo_ref, k_scr, v_scr = refs
    g = pl.program_id(3)
    qo_ref[0] = _turned(dq_ref[0], tables, shifts, True).astype(qo_ref.dtype)

    @pl.when(g == 0)
    def _():
        k_scr[...] = dk_ref[0]
        v_scr[...] = dv_ref[0]

    @pl.when(g > 0)
    def _():
        k_scr[...] += dk_ref[0]
        v_scr[...] += dv_ref[0]

    @pl.when(g == pl.num_programs(3) - 1)   # the group's sum is whole
    def _():
        ko_ref[0] = _turned(k_scr[...], tables, shifts, True).astype(
            ko_ref.dtype)
        vo_ref[0] = v_scr[...].astype(vo_ref.dtype)


def _grouped_pass(kernel, name, arrays, tables, heads, shifts, per_query,
                  dtype, interpret, scratch=False):
    """A pass over three arrays' columns: a grid over (batch row, row
    block, key/value head, query head of its group), a head's block
    [rows, D] a step. The first array is q's (a block a step); the other
    two k's and v's, [B, L, Hkv x D] (a block a group) or, `per_query`,
    a block a query head like q's -> three arrays in `dtype`, H, Hkv and
    Hkv heads wide. `tables` [L, D], one for every batch row, or [B, L,
    D], a batch row's own (positions that restart inside a packed
    row)."""
    (h, kv), (b, l, width) = heads, arrays[0].shape
    d, rows, group = width // h, _block(l), h // kv
    by_query = pl.BlockSpec((1, rows, d),
                            lambda b, i, j, g: (b, i, j * group + g))
    by_group = pl.BlockSpec((1, rows, d), lambda b, i, j, g: (b, i, j))
    table = pl.BlockSpec((rows, d), lambda b, i, j, g: (i, 0))
    if tables and tables[0].ndim == 3:
        table = pl.BlockSpec((1, rows, d), lambda b, i, j, g: (b, i, 0))
    taken = by_query if per_query else by_group
    return pl.pallas_call(
        functools.partial(kernel, shifts=shifts), name=name,
        grid=(b, l // rows, kv, group),
        in_specs=[by_query, taken, taken] + [table] * len(tables),
        out_specs=[by_query, by_group, by_group],
        out_shape=[jax.ShapeDtypeStruct((b, l, n * d), dtype)
                   for n in (h, kv, kv)],
        scratch_shapes=[pltpu.VMEM((rows, d), jnp.float32)] * 2
        if scratch else [],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret)(*arrays, *tables)


def _grouped_front(q, k, v, tables, heads, shifts, interpret):
    """q [B, L, H x D], k, v [B, L, Hkv x D] float32, read where three
    products wrote them -> the same in bfloat16, q and k turned in
    float32 against `tables` (cos, a signed sine a roll of `shifts`:
    `ops/attention.rotary_tables`; none: no rotary positions), each
    rounded once."""
    return _grouped_pass(_grouped_front_kernel, "grouped_attention_front",
                         (q, k, v), tables, heads, shifts, False,
                         jnp.bfloat16, interpret)


def _grouped_back(dq, dk, dv, tables, heads, shifts, interpret,
                  dtype=jnp.float32):
    """The kernels' float32 dq, dk, dv [B, L, H x D], `dk` and `dv` a
    block of columns a query head -> the gradients of q [B, L, H x D], k
    and v [B, L, Hkv x D] in `dtype`: a group's blocks added up in VMEM
    in float32 as they are read, the rotation transposed in float32, one
    rounding where `dtype` is narrower."""
    return _grouped_pass(_grouped_back_kernel, "grouped_attention_back",
                         (dq, dk, dv), tables, heads, shifts, True, dtype,
                         interpret, scratch=True)


def _gate_kernel(x_ref, s_ref, *refs, width):
    """A block of n heads of x times their columns of s; behind a second
    operand a (the backward pass: x is the cotangent, a the kernels'
    output), each head's sum of x a over its width too."""
    a_ref, o_ref, u_ref = refs if len(refs) == 3 else (None, *refs, None)
    for g in range(s_ref.shape[-1]):
        cols = slice(g * width, (g + 1) * width)
        x = x_ref[0, :, cols]
        o_ref[0, :, cols] = (x * s_ref[0, 0, :, g:g + 1]).astype(o_ref.dtype)
        if a_ref is not None:
            u_ref[0, 0, :, g:g + 1] = (x * a_ref[0, :, cols]).sum(
                axis=1, keepdims=True)


def _gated(x, s, dtype, interpret, a=None):
    """x [B, L, H x D] float32 times s [B, L, H], a head's column spread
    over its width, in float32 -> `dtype`, with no head axis: a grid
    over (batch row, row block, block of up to 8 heads), s laid out a
    block of heads at a time ([B, H / n, L, n]: 1 / D of x's bytes).
    With `a` [B, L, H x D] float32 -> (that, each head's sum of x a over
    its width [B, L, H] float32), x read once for both."""
    (b, l, width), h = x.shape, s.shape[-1]
    n = next(n for n in (8, 4, 2, 1) if h % n == 0)
    d, rows = width // h, _block(l)
    block = pl.BlockSpec((1, rows, n * d), lambda b, i, c: (b, i, c))
    heads = pl.BlockSpec((1, 1, rows, n), lambda b, i, c: (b, c, i, 0))
    by_block = lambda t: jnp.swapaxes(t.reshape(b, l, h // n, n), 1, 2)
    out = jax.ShapeDtypeStruct(x.shape, dtype)
    sums = jax.ShapeDtypeStruct((b, h // n, l, n), jnp.float32)
    got = pl.pallas_call(
        functools.partial(_gate_kernel, width=d),
        name="attention_head_gate" if a is None else "attention_head_gate_bwd",
        grid=(b, l // rows, h // n),
        in_specs=[block, heads] + ([] if a is None else [block]),
        out_specs=block if a is None else [block, heads],
        out_shape=out if a is None else [out, sums],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret)(x, by_block(s), *(() if a is None else (a,)))
    if a is None:
        return got
    return got[0], jnp.swapaxes(got[1], 1, 2).reshape(b, l, h)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11))
def grouped_attention_pallas(q, k, v, gate, key_mask, tables, heads,
                             shifts=(), window: Optional[int] = None,
                             interpret: bool = False, operand_dtype=None,
                             packed: bool = False):
    """Causal attention of grouped query heads from its projections to
    its (gated) output, token-first all the way: q [B, L, H x D], k, v
    [B, L, Hkv x D] float32 as `x @ wq`, `x @ wk`, `x @ wv` wrote them
    (`heads` = (H, Hkv), Hkv a divisor of H; D a whole number of lane
    tiles, L a multiple of 128), key_mask [B, L] bool -> [B, L, H x D]
    float32; under a `window` over the band (`window_attention_pallas`'s
    kernels). `tables` (cos, a signed sine a roll) [L, D] with the rolls'
    distances `shifts` are the rotary positions, whatever their variant
    (`ops/attention.rotary_tables`); () and (): none. `gate` [B, L, H]
    (or None): the output of head h times sigmoid(gate_h).

    One pass in front of the kernels turns q and k in float32 and rounds
    q, k, v once to bfloat16 (`grouped_attention_front`); the kernels
    read a head as a block of its array's columns, a key/value head at
    column block h // (H / Hkv); the gate is one pass over the output's
    columns (`attention_head_gate`). Backward the cotangent goes through
    that pass again (times the sigmoid, rounded once to bfloat16 as the
    kernels round their operand; `attention_head_gate_bwd`), which also
    sums d_out x out over each head's width: s times that is the `delta`
    the backward kernel needs anyway and `delta` (1 - s) the gate's own
    gradient, with no pass of its own. The
    kernels' float32 dq, dk, dv go through one pass that adds a
    group's `dk`, `dv` up and transposes the rotation
    (`grouped_attention_back`). No array between the products is split
    into heads, transposed, joined or cast by XLA. `operand_dtype`: the
    type the caller's products round their operands to, if it names one
    (`rotary_attention_pallas`'s `grad_dtype`, and its rule): the passes
    then write what only those products read rounded once to it, the
    gradients of q, k and v and the gated output (the operand of `@ wo`:
    the kernels' own `out` stays float32); the results are float32
    either way. `packed`: a row holds several sessions, key_mask [B, L]
    int32 each position's session id (0 = padding, ids rising by one
    along a row) and `tables` [B, L, D] the rotary positions inside the
    session: a query sees the keys of its own session alone."""
    return _grouped_attention_fwd(q, k, v, gate, key_mask, tables, heads,
                                  shifts, window, interpret, operand_dtype,
                                  packed, save_lse=False)[0]


# (the two rules' bodies are jitted: layers of one kind, and a layer's
# forward pass and its recomputation, are ONE traced function and one
# lowering of its kernels, called from each place. The casts back to
# float32 stay OUT of the jitted bodies, beside the caller's products that
# read them: there XLA drops them and hands the products the narrow
# arrays, as it does with no jit at all)
def _grouped_attention_fwd(q, k, v, gate, key_mask, tables, heads, shifts,
                           window, interpret, operand_dtype=None,
                           packed=False, save_lse=True):
    out, saved = _grouped_attention_out(q, k, v, gate, key_mask, tables,
                                        heads, shifts, window, interpret,
                                        operand_dtype, save_lse, packed)
    return out.astype(q.dtype), saved


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10, 11, 12))
def _grouped_attention_out(q, k, v, gate, key_mask, tables, heads, shifts,
                           window, interpret, operand_dtype, save_lse,
                           packed=False):
    out, res = _fwd(*_grouped_front(q, k, v, tables, heads, shifts,
                                    interpret), key_mask, True, interpret,
                    save_lse, heads, q.dtype, window, packed)
    if gate is None:
        return out, (res, None, tables)
    s = jax.nn.sigmoid(gate)
    return _gated(out, s, operand_dtype or out.dtype, interpret), \
        (res, s, tables)


def _grouped_attention_bwd(heads, shifts, window, interpret, operand_dtype,
                           packed, saved, d_out):
    *grads, d_gate = _grouped_attention_grads(
        heads, shifts, window, interpret, operand_dtype, saved, d_out,
        packed)
    return (*(g.astype(d_out.dtype) for g in grads), d_gate, None,
            tuple(None for _ in saved[2]))


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 7))
def _grouped_attention_grads(heads, shifts, window, interpret, operand_dtype,
                             saved, d_out, packed=False):
    res, s, tables = saved
    delta = d_gate = None
    if s is not None:
        # y = s a: da = s dy, and with u = sum_D(dy a) the kernel's
        # delta = sum_D(da a) = s u and d gate = u s (1 - s)
        d_out, u = _gated(d_out, s, jnp.bfloat16, interpret, res[4][:, 0])
        delta = jnp.swapaxes(s * u, 1, 2)                   # [B, H, L]
        d_gate = s * u * (1.0 - s)
    dq, dk, dv, _ = _bwd(True, interpret, res, d_out, heads, window, delta,
                         packed)
    return (*_grouped_back(dq, dk, dv, tables, heads, shifts, interpret,
                           operand_dtype or dq.dtype), d_gate)


grouped_attention_pallas.defvjp(_grouped_attention_fwd,
                                _grouped_attention_bwd)
