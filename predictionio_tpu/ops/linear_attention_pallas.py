"""The chunked gated delta rule as Pallas TPU kernels, forward and
backward, and what a linear-attention layer does around the rule as
fused passes (`gated_delta_chain_pallas`, at the end of the file).

The same mathematics as the scan of ``ops/linear_attention._scan`` (the
WY form: a unit lower triangular system within a chunk, one state update
between chunks), with a head's state S [dk, dv] held in VMEM from its
first chunk to its last and everything a chunk computes on the way (the
decay matrix, the system and its inverse, `u`, `w`, the chunk's scores)
made in VMEM from the chunk's rows of q, k, v and never written to HBM:

    v_new = u - w S        o = q S + qk v_new        S <- decay S + k^T v_new

Two kernels, each a grid over (batch row, heads, chunks), the chunks
innermost and sequential, a few heads and chunks a grid step; q, k, v, o
and their gradients are read and written where they lie, [B, L, H x d],
q and k at their own Hk key heads (a grid step's value heads read the
key head that serves them; the backward kernel adds their `dq`, `dk` up
before it writes them):

* ``gated_delta_rule_pallas_fwd``: writes a chunk's `o` once and, when
  a backward pass will follow, the state the chunk started from
  (B x H x dk x dv float32 a chunk: what the scan keeps too) and its
  system's inverse (C x C: six of the forward pass's nine
  highest-precision products).
* ``gated_delta_rule_pallas_bwd``: the chunks last to first with dS in
  VMEM the same way; a chunk's other operands computed again from its
  rows, the inverse and the state it started from; writes every
  gradient once.

Precision, as the scan's: the system's matrix, its inverse, the two
products with the inverse and their backward products are float32 at
the highest matmul precision; every other product takes its operands in
bfloat16 (what the TPU's default does to float32 operands), rounded once
on their way in, and accumulates in float32; decays, their exponentials,
the states, dS and all sums are float32.

Around the rule (PERF.md section 6, PR 39): four more kernels, each a
grid over (batch row, block of rows, block of columns), float32
elementwise throughout. `gdn_chain_front_fwd` / `_bwd`: the causal
convolution over the projection's output where it lies (a block's
earlier rows read from the block before it), SiLU, and for q and k the
unit length a head and q's scale; `gdn_chain_back_fwd` / `_bwd`: the
rule's output normed a head times SiLU(z). The backward kernels write
the projection's gradient into one array, a block of columns each. The
chain's one backward pass keeps the projection's output, q, k, v, the
rule's output and the chunks' states and inverses of the forward pass
it follows and runs no forward kernel again; nothing is named for a
caller's `jax.checkpoint` to keep (a block recomputed around the chain
runs its forward pass twice).
"""

from __future__ import annotations

import functools
import math
import operator
import types

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: heads and chunks a grid step takes (the largest of these that divide
#: what there is; chunks in pairs, whose two systems are inverted as
#: one). Constants, from chip runs of the kernels alone at 1 x 32 heads x
#: 256 chunks of 64 x 128 (PERF.md section 6, PR 32), heads x chunks:
#: forward + backward took 29.5 ms at 1 x 2, 28.4 at 1 x 4 and 2 x 2,
#: 27.8 at 2 x 4; a forward call 15.4 at each.
HEADS = (2, 1)
CHUNKS = (4, 2)

_VMEM_LIMIT = 64 * 1024 * 1024

_NT = (((1,), (1,)), ((), ()))     # a @ b.T
_TN = (((0,), (0,)), ((), ()))     # a.T @ b
_BF16 = jnp.bfloat16
_HIGHEST = jax.lax.Precision.HIGHEST


def tiles(dk: int, dv: int) -> bool:
    """Whether the kernels lay these widths out: whole lane tiles, and a
    head's state within what was compiled (test_tpu_compile_kernels.py)."""
    return dk % 128 == 0 and dv % 128 == 0 and max(dk, dv) <= 256


def _dot(a, b, dims=None):
    """One bfloat16 pass, float32 accumulation."""
    a, b = a.astype(_BF16), b.astype(_BF16)
    if dims is None:
        return jnp.dot(a, b, preferred_element_type=jnp.float32)
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _dot32(a, b, dims=None):
    """float32 at the highest precision."""
    if dims is None:
        return jnp.dot(a, b, precision=_HIGHEST,
                       preferred_element_type=jnp.float32)
    return jax.lax.dot_general(a, b, dims, precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _unit_lower_inverses(a, b):
    """(I + a)^-1 and (I + b)^-1 for strictly lower triangular a, b
    [C, C], C a power of two, as the diagonal blocks of one [2C, 2C]
    system: two chunks' chains of products in the time of one, over full
    lane tiles. A block is nilpotent, so its inverse is sum_m (-a)^m =
    (I + y)(I + y^2)(I + y^4)... with y = -a; the factors commute, so a
    step takes y t and y y in one product, y [t | y]."""
    c = a.shape[0]
    zero = jnp.zeros_like(a)
    y = -jnp.concatenate([jnp.concatenate([a, zero], axis=1),
                          jnp.concatenate([zero, b], axis=1)], axis=0)
    eye = jax.lax.broadcasted_iota(jnp.int32, y.shape, 0) \
        == jax.lax.broadcasted_iota(jnp.int32, y.shape, 1)
    t = jnp.where(eye, 1.0, 0.0) + y
    y = _dot32(y, y)
    for _ in range(c.bit_length() - 3):
        both = _dot32(y, jnp.concatenate([t, y], axis=1))
        t, y = t + both[:, :2 * c], both[:, 2 * c:]
    t = t + _dot32(y, t)
    return t[:c, :c], t[c:, c:]


def _system(k, v, gc_row, beta_row):
    """A chunk's unit lower triangular system I + a, a = kk strict, from
    its rows k [C, dk], v [C, dv] and the decay since its start and beta
    as rows [1, C], with what it was made from."""
    c = k.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    eye = rows == cols

    def column(row):                    # [1, C] -> [C, 1]
        return jnp.where(eye, row, 0.0).sum(axis=1, keepdims=True)

    gc, beta = column(gc_row), column(beta_row)
    lower = rows >= cols
    # exp(gc_i - gc_j) for j <= i, 0 above the diagonal (whose exponents
    # are positive and may overflow)
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, gc - gc_row, 0.0)), 0.0)
    strict = jnp.where(rows > cols, decay, 0.0)
    k_beta = k * beta
    kk = _dot32(k_beta, k, _NT)
    last = jnp.where(cols[:1] == c - 1, gc_row, 0.0).sum(
        axis=1, keepdims=True)                  # [1, 1]
    return types.SimpleNamespace(
        eye=eye, gc=gc, beta=beta, decay=decay, strict=strict, k_beta=k_beta,
        v_beta=v * beta, kk=kk, a=kk * strict, last=last)


def _chunk_operands(x, q, k, t):
    """What a chunk computes that no other chunk's state enters
    (`ops/linear_attention._scan` has the same, for all chunks at once):
    x its `_system`, t that system's inverse [C, C]; added to x."""
    x.t = t
    x.since = jnp.exp(x.gc)                     # decay since chunk start
    x.until = jnp.exp(x.last - x.gc)            # decay until chunk end
    x.k_since = x.k_beta * x.since
    x.qk = _dot(q, k, _NT)
    x.u, x.w = _dot32(t, x.v_beta), _dot32(t, x.k_since)
    x.p = x.qk * x.decay
    x.q_since, x.k_until = q * x.since, k * x.until
    x.whole = jnp.exp(x.last)
    return x


def _rows_of(refs, j, heads, c, widths):
    """Chunk j of a head of the grid step's blocks [1, chunks C, heads d]:
    of each ref the head `heads` names for it (a value head's own place
    in a block of v, o or their gradients; in a block of q or k that, or
    0 where the step's value heads share one key head)."""
    return [ref[0, j * c:(j + 1) * c, h * d:(h + 1) * d]
            for ref, h, d in zip(refs, heads, widths)]


def _fwd_kernel(q_ref, k_ref, v_ref, gates_ref, o_ref, *refs, heads, chunks,
                shared, save_states):
    if save_states:
        states_ref, t_ref, s_scr = refs
    else:
        (s_scr,) = refs
    c = gates_ref.shape[-1]
    _, dk, dv = s_scr.shape

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    states = [s_scr[h] for h in range(heads)]
    for pair in range(0, chunks, 2):
        for h in range(heads):      # independent chains, side by side
            kh = 0 if shared else h
            rows = [_rows_of((q_ref, k_ref, v_ref), j, (kh, kh, h), c,
                             (dk, dk, dv)) for j in (pair, pair + 1)]
            systems = [_system(k, v, gates_ref[0, h, j, 0:1],
                               gates_ref[0, h, j, 1:2])
                       for j, (_, k, v) in zip((pair, pair + 1), rows)]
            inverses = _unit_lower_inverses(*(x.a for x in systems))
            for j, (q, k, _), x, t in zip((pair, pair + 1), rows, systems,
                                          inverses):
                x = _chunk_operands(x, q, k, t)
                s = states[h]
                if save_states:
                    states_ref[0, h, j] = s
                    t_ref[0, h, j] = t
                v_new = x.u - _dot(x.w, s)
                o_ref[0, j * c:(j + 1) * c, h * dv:(h + 1) * dv] = \
                    _dot(x.q_since, s) + _dot(x.p, v_new)
                states[h] = s * jnp.broadcast_to(x.whole, (1, dv)) \
                    + _dot(x.k_until, v_new, _TN)
    for h in range(heads):
        s_scr[h] = states[h]


def _bwd_kernel(q_ref, k_ref, v_ref, gates_ref, states_ref, t_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dgates_ref, ds_scr, *, heads, chunks,
                shared):
    c = gates_ref.shape[-1]
    _, dk, dv = ds_scr.shape

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    def row_sums(t):
        return t.sum(axis=1, keepdims=True)

    d_states = [ds_scr[h] for h in range(heads)]
    for j in reversed(range(chunks)):
        of_keys = []            # (dq, dk) of the value heads of one key head
        for h in range(heads):
            kh = 0 if shared else h
            q, k, v, do = _rows_of((q_ref, k_ref, v_ref, do_ref), j,
                                   (kh, kh, h, h), c, (dk, dk, dv, dv))
            x = _chunk_operands(
                _system(k, v, gates_ref[0, h, j, 0:1],
                        gates_ref[0, h, j, 1:2]), q, k, t_ref[0, h, j])
            ds = d_states[h]            # of the state the chunk ends in
            s = states_ref[0, h, j]     # the state it started from
            # the recurrence
            v_new = x.u - _dot(x.w, s)
            d_u = _dot(x.p, do, _TN) + _dot(x.k_until, ds)      # = d v_new
            d_p = _dot(do, v_new, _NT)
            d_q_since = _dot(do, s, _NT)
            d_k_until = _dot(v_new, ds, _NT)
            d_w = -_dot(d_u, s, _NT)
            d_whole = (ds * s).sum(axis=0, keepdims=True).sum(
                axis=1, keepdims=True)
            d_states[h] = _dot(x.q_since, do, _TN) \
                + ds * jnp.broadcast_to(x.whole, (1, dv)) \
                - _dot(x.w, d_u, _TN)
            # the chunk's operands: u = t v_beta, w = t k_since,
            # t = (I + kk strict)^-1, p = qk decay
            d_t = _dot32(d_u, x.v_beta, _NT) + _dot32(d_w, x.k_since, _NT)
            d_v_beta = _dot32(x.t, d_u, _TN)
            d_k_since = _dot32(x.t, d_w, _TN)
            d_a = -_dot32(_dot32(x.t, d_t, _TN), x.t, _NT)
            d_kk = d_a * x.strict
            d_k_beta = _dot32(d_kk, k) + d_k_since * x.since
            d_qk = d_p * x.decay
            of_keys.append((
                _dot(d_qk, k) + d_q_since * x.since,
                _dot32(d_kk, x.k_beta, _TN) + _dot(d_qk, q, _TN)
                + d_k_until * x.until + d_k_beta * x.beta))
            dv_ref[0, j * c:(j + 1) * c, h * dv:(h + 1) * dv] = \
                d_v_beta * x.beta
            # decay_ij = exp(gc_i - gc_j): its gradient times itself
            e = d_p * x.qk * x.decay + d_a * x.kk * x.strict
            until = row_sums(d_k_until * x.k_until)
            d_gc = row_sums(e) + row_sums(d_q_since * x.q_since) - until \
                + row_sums(d_k_since * x.k_since)                   # [C, 1]
            d_last = until.sum(axis=0, keepdims=True) + d_whole * x.whole
            d_beta = row_sums(d_k_beta * k) + row_sums(d_v_beta * v)

            def row(column):                    # [C, 1] -> [1, C]
                return jnp.where(x.eye, column, 0.0).sum(axis=0,
                                                         keepdims=True)

            at = jax.lax.broadcasted_iota(jnp.int32, (1, c), 1)
            dgates_ref[0, h, j, 0:1] = row(d_gc) \
                - e.sum(axis=0, keepdims=True) \
                + jnp.where(at == c - 1, d_last, 0.0)
            dgates_ref[0, h, j, 1:2] = row(d_beta)
        if shared:              # one key head's gradients, added up here
            of_keys = [[functools.reduce(operator.add, part)
                        for part in zip(*of_keys)]]
        for kh, grads in enumerate(of_keys):
            for ref, grad in zip((dq_ref, dk_ref), grads):
                ref[0, j * c:(j + 1) * c, kh * dk:(kh + 1) * dk] = grad
    for h in range(heads):
        ds_scr[h] = d_states[h]


def _call(kernel, name, grid, in_specs, out_specs, out_shape, scratch,
          interpret):
    return pl.pallas_call(
        kernel, name=name, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=[scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret)


def _layout(b, l, hk, hv, chunk, backward):
    """The grid, and block specs by what a block holds: rows of v, o
    [B, L, Hv x width]; rows of q, k [B, L, Hk x width], a key head read
    where it lies by every grid step whose value heads it serves; a
    (head, chunk)'s gates [B, Hv, N, 2, C], state [B, Hv, N, dk, dv] or
    inverse [B, Hv, N, C, C]. A grid step's value heads have a key head
    each (Hk = Hv) or share one (`shared`: `HEADS` that divide Hv / Hk);
    its `dq`, `dk` are a block of their own a step (`of_keys`:
    [B, L, steps x width], the step's heads added up where they share).
    The backward pass takes the chunks last to first."""
    n = l // chunk
    if l % chunk or n % CHUNKS[-1]:
        raise ValueError(f"{l} positions are no whole grid steps of "
                         f"{CHUNKS[-1]} chunks of {chunk}")
    if hv % hk:
        raise ValueError(f"{hv} value heads are no whole groups of "
                         f"{hk} key heads")
    group = hv // hk
    shared = group > 1
    heads = next(x for x in HEADS if (group if shared else hv) % x == 0)
    chunks = next(x for x in CHUNKS if n % x == 0)
    last = n // chunks - 1
    at = (lambda i: last - i) if backward else (lambda i: i)

    def rows(width):
        return pl.BlockSpec((1, chunks * chunk, heads * width),
                            lambda b, h, i: (b, at(i), h))

    def keys(width):
        if not shared:
            return rows(width)
        return pl.BlockSpec((1, chunks * chunk, width),
                            lambda b, h, i: (b, at(i), h * heads // group))

    def of_keys(width):
        return pl.BlockSpec(
            (1, chunks * chunk, width if shared else heads * width),
            lambda b, h, i: (b, at(i), h))

    def per_chunk(*shape):
        return pl.BlockSpec((1, heads, chunks, *shape),
                            lambda b, h, i: (b, h, at(i), 0, 0))

    return types.SimpleNamespace(
        heads=heads, chunks=chunks, shared=shared,
        grid=(b, hv // heads, n // chunks), rows=rows, keys=keys,
        of_keys=of_keys, per_chunk=per_chunk)


def _flat(t):           # [B, L, H, width] -> [B, L, H x width], where it lies
    return t.astype(jnp.float32).reshape(*t.shape[:2], -1)


def _gates(g, beta, chunk):
    """g, beta [B, L, H] -> [B, H, N, 2, C]: the decay's logarithm since
    the chunk's start, and beta, as rows."""
    b, l, h = g.shape
    chunked = lambda t: jnp.transpose(
        t.astype(jnp.float32).reshape(b, l // chunk, chunk, h), (0, 3, 1, 2))
    return jnp.stack([jnp.cumsum(chunked(g), axis=-1), chunked(beta)], axis=3)


def _forward(q, k, v, g, beta, chunk, interpret, save_states):
    b, l, hk, dk = q.shape
    h, dv = v.shape[2:]
    at = _layout(b, l, hk, h, chunk, False)
    out_specs = [at.rows(dv)]
    out_shape = [jax.ShapeDtypeStruct((b, l, h * dv), jnp.float32)]
    if save_states:
        for shape in ((dk, dv), (chunk, chunk)):
            out_specs.append(at.per_chunk(*shape))
            out_shape.append(jax.ShapeDtypeStruct(
                (b, h, l // chunk, *shape), jnp.float32))
    o, *kept = _call(
        functools.partial(_fwd_kernel, heads=at.heads, chunks=at.chunks,
                          shared=at.shared, save_states=save_states),
        "gated_delta_rule_pallas_fwd", at.grid,
        [at.keys(dk), at.keys(dk), at.rows(dv), at.per_chunk(2, chunk)],
        out_specs, out_shape, pltpu.VMEM((at.heads, dk, dv), jnp.float32),
        interpret)(_flat(q), _flat(k), _flat(v), _gates(g, beta, chunk))
    return o.reshape(b, l, h, dv), tuple(kept)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def gated_delta_rule_pallas(q, k, v, g, beta, chunk: int,
                            interpret: bool = False):
    """q, k [B, L, Hk, dk], v [B, L, H, dv], g, beta [B, L, H], Hk a
    divisor of H (value head h reads key head h // (H / Hk), where it
    lies), L whole grid steps (`CHUNKS[-1]` chunks) -> o [B, L, H, dv]
    float32, from a state of 0. The backward pass keeps the inputs and,
    of the chunked form, the state each chunk started from and its
    system's inverse; `dq`, `dk` come back at the key heads. `interpret`
    runs the kernels in the Pallas interpreter (the CPU tests)."""
    return _forward(q, k, v, g, beta, chunk, interpret, save_states=False)[0]


def _fwd(q, k, v, g, beta, chunk, interpret):
    o, kept = _forward(q, k, v, g, beta, chunk, interpret, save_states=True)
    return o, (q, k, v, g, beta, *kept)


def _backward(chunk, interpret, res, d_out):
    """-> dq, dk [B, L, Hk, dk], dv [B, L, H, dv], dg, dbeta [B, L, H],
    float32."""
    q, k, v, g, beta, states, inverses = res
    b, l, hk, dk = q.shape
    h, dv = v.shape[2:]
    at = _layout(b, l, hk, h, chunk, True)
    steps = h // at.heads
    of_keys = dk if at.shared else at.heads * dk
    d_q, d_k, d_v, d_gates = _call(
        functools.partial(_bwd_kernel, heads=at.heads, chunks=at.chunks,
                          shared=at.shared),
        "gated_delta_rule_pallas_bwd", at.grid,
        [at.keys(dk), at.keys(dk), at.rows(dv), at.per_chunk(2, chunk),
         at.per_chunk(dk, dv), at.per_chunk(chunk, chunk), at.rows(dv)],
        [at.of_keys(dk), at.of_keys(dk), at.rows(dv),
         at.per_chunk(2, chunk)],
        [jax.ShapeDtypeStruct((b, l, width), jnp.float32)
         for width in (steps * of_keys, steps * of_keys, h * dv)]
        + [jax.ShapeDtypeStruct((b, h, l // chunk, 2, chunk), jnp.float32)],
        pltpu.VMEM((at.heads, dk, dv), jnp.float32), interpret)(
        _flat(q), _flat(k), _flat(v), _gates(g, beta, chunk), states,
        inverses, _flat(d_out))
    # a key head's grid steps, where it has several: their sums added up
    d_q, d_k = (t.reshape(b, l, hk, -1, dk).sum(3) for t in (d_q, d_k))
    # [B, H, N, C] -> [B, L, H]; g entered by its sum since the chunk's
    # start, so its gradient is the sum until the chunk's end
    d_gc, d_beta = (jnp.transpose(d_gates[:, :, :, i], (0, 2, 3, 1))
                    for i in (0, 1))
    d_g = jnp.flip(jnp.cumsum(jnp.flip(d_gc, axis=2), axis=2), axis=2)
    return (d_q, d_k, d_v.reshape(v.shape), d_g.reshape(g.shape),
            d_beta.reshape(beta.shape))


def _bwd(chunk, interpret, res, d_out):
    return tuple(d.astype(t.dtype) for d, t in
                 zip(_backward(chunk, interpret, res, d_out), res))


gated_delta_rule_pallas.defvjp(_fwd, _bwd)


# The layer around the rule, from the projection's output to the output
# product's input, as fused passes: see `gated_delta_chain_pallas`.

#: rows a block of the chain's passes takes, and heads side by side in
#: its columns (the largest of these that divide what there is)
CHAIN_ROWS = (512, 256, 128)
CHAIN_HEADS = (4, 2, 1)

_UNIT_EPS = 1e-6            # under the root of a q or k head's length


def _halo(taps: int) -> int:
    """Rows of the block before (or after) a pass reads beside its own:
    the convolution's reach, in whole sublane tiles that divide a block
    of rows."""
    rows = 8
    while rows < taps - 1:
        rows *= 2
    if rows > CHAIN_ROWS[-1]:
        raise ValueError(f"a convolution of {taps} taps reaches over a "
                         f"block of {CHAIN_ROWS[-1]} rows")
    return rows


def _parts(heads):
    """The projection's columns [q | k | v | z] as (first column, heads,
    a head's width, the width the front brings to unit length (None:
    left as it is), the scale behind that) each."""
    hk, hv, dk, dv = heads
    return ((0, hk, dk, dk, dk ** -0.5), (hk * dk, hk, dk, dk, 1.0),
            (2 * hk * dk, hv, dv, None, None),
            (2 * hk * dk + hv * dv, hv, dv, None, None))


def _blocks(l, first, heads, width):
    """Rows and columns of a block over [.., L, heads x width] that
    starts at column `first` of the projection's output: whole heads,
    and `first` a whole number of blocks."""
    rows = next(x for x in CHAIN_ROWS if l % x == 0)
    cols = width * next(x for x in CHAIN_HEADS
                        if heads % x == 0 and first % (width * x) == 0)
    return rows, cols


def _taps_sum(ext_ref, w_ref, start, n):
    """sum_j w[j] ext[start + j : start + j + n]: the convolution on n
    rows, or its transpose."""
    return functools.reduce(operator.add, (
        w_ref[j:j + 1, :] * ext_ref[pl.ds(start + j, n), :]
        for j in range(w_ref.shape[0])))


def _heads_of(cols, width):
    return [slice(c, c + width) for c in range(0, cols, width)]


def _row_sums(t):
    return t.sum(axis=0, keepdims=True)


def _front_fwd_kernel(x_ref, before_ref, w_ref, o_ref, ext, *, width, scale):
    """SiLU of the causal convolution of a block of columns, a head's
    `width` columns brought to unit length and scaled (no `width`: left
    as they are). `ext`: the `halo` rows before the block, then its
    own."""
    rows, halo, taps = x_ref.shape[1], before_ref.shape[1], w_ref.shape[0]
    ext[0:halo] = jnp.where(pl.program_id(1) > 0, before_ref[0], 0.0)
    ext[halo:] = x_ref[0]
    a = _taps_sum(ext, w_ref, halo - (taps - 1), rows)
    y = a * jax.nn.sigmoid(a)
    if width is None:
        o_ref[0] = y
        return
    for at in _heads_of(y.shape[1], width):
        t = y[:, at]
        o_ref[0, :, at] = t * jax.lax.rsqrt(
            (t * t).sum(axis=1, keepdims=True) + _UNIT_EPS) * scale


def _front_bwd_kernel(x_ref, before_ref, after_ref, w_ref, dn_ref,
                      dn_after_ref, *refs, width, scale):
    """The gradient of `_front_fwd_kernel`'s block into the projection's
    gradient, and the taps' gradient summed over the block's rows. The
    convolution's transpose reads the pre-activation's gradient `halo`
    rows past the block, so that is made on the block's rows and the
    `halo` after them. `ext`: the rows before, the block's, the rows
    after; `da`: the block's, the rows after."""
    dx_ref, dw_ref, ext, da = refs[-4:]         # (an aliased buffer first)
    rows, halo, taps = x_ref.shape[1], before_ref.shape[1], w_ref.shape[0]
    i, last = pl.program_id(1), pl.num_programs(1) - 1
    ext[0:halo] = jnp.where(i > 0, before_ref[0], 0.0)
    ext[halo:halo + rows] = x_ref[0]
    ext[halo + rows:] = jnp.where(i < last, after_ref[0], 0.0)
    lead = halo - (taps - 1)
    for lo, n, dn in ((0, rows, dn_ref[0]),
                      (rows, halo, jnp.where(i < last, dn_after_ref[0], 0.0))):
        a = _taps_sum(ext, w_ref, lead + lo, n)
        s = jax.nn.sigmoid(a)
        silu_grad = s * (1.0 + a * (1.0 - s))
        if width is None:
            da[lo:lo + n] = dn * silu_grad
            continue
        y = a * s
        for at in _heads_of(y.shape[1], width):
            t, d = y[:, at], dn[:, at]
            r = jax.lax.rsqrt((t * t).sum(axis=1, keepdims=True) + _UNIT_EPS)
            da[lo:lo + n, at] = (d - t * (r * r * (d * t).sum(
                axis=1, keepdims=True))) * (r * scale) * silu_grad[:, at]
    dx_ref[0] = functools.reduce(operator.add, (
        w_ref[j:j + 1, :] * da[pl.ds(taps - 1 - j, rows), :]
        for j in range(taps)))
    for j in range(taps):
        dw_ref[0, 0, j:j + 1, :] = _row_sums(
            da[0:rows] * ext[pl.ds(lead + j, rows), :])


def _back_fwd_kernel(o_ref, z_ref, scale_ref, out_ref, *, eps):
    """A head of o normed (RMS, `scale`) times SiLU(z)."""
    width = scale_ref.shape[1]
    for at in _heads_of(o_ref.shape[2], width):
        o, z = o_ref[0, :, at], z_ref[0, :, at]
        r = jax.lax.rsqrt((o * o).mean(axis=1, keepdims=True) + eps)
        out_ref[0, :, at] = o * r * scale_ref[...] * (z * jax.nn.sigmoid(z))


def _back_bwd_kernel(o_ref, z_ref, scale_ref, d_ref, *refs, eps):
    """`_back_fwd_kernel`'s gradients: o's, z's into the projection's
    gradient, the scale's summed over the block's rows (a head's
    columns each)."""
    dz_ref, do_ref, ds_ref = refs[-3:]          # (an aliased buffer first)
    width = scale_ref.shape[1]
    for at in _heads_of(o_ref.shape[2], width):
        o, z, d = o_ref[0, :, at], z_ref[0, :, at], d_ref[0, :, at]
        r = jax.lax.rsqrt((o * o).mean(axis=1, keepdims=True) + eps)
        s = jax.nn.sigmoid(z)
        unit, gate = o * r, z * s
        ds_ref[0, 0, :, at] = _row_sums(d * gate * unit)
        du = d * gate * scale_ref[...]
        do_ref[0, :, at] = r * (
            du - unit * (du * unit).mean(axis=1, keepdims=True))
        dz_ref[0, :, at] = d * unit * scale_ref[...] \
            * (s * (1.0 + z * (1.0 - s)))


def _chain_call(kernel, name, grid, in_specs, out_specs, out_shape, scratch,
                into, interpret):
    """A pass over (batch row, row block, column block), to be called
    with the operands of `in_specs`. `into`: the array the first output
    is a block of columns of, written in place (None: a new one, only
    these columns of it written)."""
    aliases, last = {}, ()
    if into is not None:
        in_specs = [*in_specs, pl.BlockSpec(memory_space=pl.ANY)]
        aliases, last = {len(in_specs) - 1: 0}, (into,)
    call = pl.pallas_call(
        kernel, name=name, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3,
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret)
    return lambda *operands: call(*operands, *last)


def _block(rows, cols, first=0):
    """Row block i of column block `first` + c."""
    return pl.BlockSpec((1, rows, cols), lambda b, i, c: (b, i, first + c))


def _halo_block(halo, rows, cols, first, after, l):
    """The `halo` rows that end where row block i starts, or (`after`)
    start where it ends; the array's first or last where there are none
    (the kernel reads zeros then)."""
    per = rows // halo

    def index(b, i, c):
        at = (i + 1) * per if after else i * per - 1
        return b, jnp.clip(at, 0, l // halo - 1), first + c

    return pl.BlockSpec((1, halo, cols), index)


def _front(qkvz, taps, heads, interpret):
    """The projection's q, k, v columns [B, L, ...] where they lie ->
    q, k [B, L, Hk x dk] (convolved, SiLU, unit length, q scaled), v
    [B, L, Hv x dv] (convolved, SiLU): three calls of one kernel."""
    b, l, _ = qkvz.shape
    halo = _halo(taps.shape[0])
    outs = []
    for first, n, width, unit, scale in _parts(heads)[:3]:
        rows, cols = _blocks(l, first, n, width)
        outs.append(_chain_call(
            functools.partial(_front_fwd_kernel, width=unit, scale=scale),
            "gdn_chain_front_fwd", (b, l // rows, n * width // cols),
            [_block(rows, cols, first // cols),
             _halo_block(halo, rows, cols, first // cols, False, l),
             pl.BlockSpec((taps.shape[0], cols),
                          lambda b, i, c, at=first // cols: (0, at + c))],
            _block(rows, cols),
            jax.ShapeDtypeStruct((b, l, n * width), jnp.float32),
            [pltpu.VMEM((halo + rows, cols), jnp.float32)], None,
            interpret)(qkvz, qkvz, taps))
    return outs


def _front_backward(qkvz, taps, grads, into, heads, interpret):
    """dq, dk, dv [B, L, ...] -> the projection's gradient [B, L, ...]
    with its q, k, v columns written into `into`, and the taps'
    gradient."""
    b, l, total = qkvz.shape
    halo, n_taps = _halo(taps.shape[0]), taps.shape[0]
    d_taps = []
    for (first, n, width, unit, scale), dn in zip(_parts(heads), grads):
        rows, cols = _blocks(l, first, n, width)
        at = first // cols
        into, dw = _chain_call(
            functools.partial(_front_bwd_kernel, width=unit, scale=scale),
            "gdn_chain_front_bwd", (b, l // rows, n * width // cols),
            [_block(rows, cols, at),
             _halo_block(halo, rows, cols, at, False, l),
             _halo_block(halo, rows, cols, at, True, l),
             pl.BlockSpec((n_taps, cols),
                          lambda b, i, c, at=at: (0, at + c)),
             _block(rows, cols), _halo_block(halo, rows, cols, 0, True, l)],
            [_block(rows, cols, at),
             pl.BlockSpec((1, 1, n_taps, cols),
                          lambda b, i, c: (b, i, 0, c))],
            [jax.ShapeDtypeStruct((b, l, total), jnp.float32),
             jax.ShapeDtypeStruct((b, l // rows, n_taps, n * width),
                                  jnp.float32)],
            [pltpu.VMEM((2 * halo + rows, cols), jnp.float32),
             pltpu.VMEM((rows + halo, cols), jnp.float32)], into,
            interpret)(qkvz, qkvz, qkvz, taps, dn, dn)
        d_taps.append(dw.sum((0, 1)))
    return into, jnp.concatenate(d_taps, axis=1)


def _back(o, qkvz, scale, heads, eps, interpret):
    """o [B, L, Hv x dv], the projection's z columns where they lie ->
    norm(o) scale SiLU(z) [B, L, Hv x dv]."""
    b, l, _ = o.shape
    first, n, width, _, _ = _parts(heads)[3]
    rows, cols = _blocks(l, first, n, width)
    return _chain_call(
        functools.partial(_back_fwd_kernel, eps=eps), "gdn_chain_back_fwd",
        (b, l // rows, n * width // cols),
        [_block(rows, cols), _block(rows, cols, first // cols),
         pl.BlockSpec((1, width), lambda b, i, c: (0, 0))],
        _block(rows, cols), jax.ShapeDtypeStruct(o.shape, jnp.float32), [],
        None, interpret)(o, qkvz, scale.reshape(1, width))


def _back_backward(o, qkvz, scale, d_out, into, heads, eps, interpret):
    """-> the projection's gradient with its z columns written into
    `into`, do [B, L, Hv x dv], the scale's gradient [dv]."""
    b, l, total = qkvz.shape
    first, n, width, _, _ = _parts(heads)[3]
    rows, cols = _blocks(l, first, n, width)
    into, d_o, d_scale = _chain_call(
        functools.partial(_back_bwd_kernel, eps=eps), "gdn_chain_back_bwd",
        (b, l // rows, n * width // cols),
        [_block(rows, cols), _block(rows, cols, first // cols),
         pl.BlockSpec((1, width), lambda b, i, c: (0, 0)),
         _block(rows, cols)],
        [_block(rows, cols, first // cols), _block(rows, cols),
         pl.BlockSpec((1, 1, 1, cols), lambda b, i, c: (b, i, 0, c))],
        [jax.ShapeDtypeStruct((b, l, total), jnp.float32),
         jax.ShapeDtypeStruct(o.shape, jnp.float32),
         jax.ShapeDtypeStruct((b, l // rows, 1, n * width), jnp.float32)],
        [], into, interpret)(o, qkvz, scale.reshape(1, width), d_out)
    return into, d_o, d_scale.reshape(-1, n, width).sum((0, 1))


def _whole(t, l):
    """[B, .., ...] float32, filled up with zeros to l positions."""
    t = t.astype(jnp.float32)
    return jnp.pad(t, ((0, 0), (0, l - t.shape[1]))
                   + ((0, 0),) * (t.ndim - 2)) if l > t.shape[1] else t


def _chain_forward(qkvz, taps, g, beta, scale, heads, eps, chunk, interpret,
                   save_states):
    hk, hv, dk, dv = heads
    b, l, _ = qkvz.shape
    q, k, v = _front(qkvz, taps, heads, interpret)
    o, kept = _forward(q.reshape(b, l, hk, dk), k.reshape(b, l, hk, dk),
                       v.reshape(b, l, hv, dv), g, beta, chunk, interpret,
                       save_states)
    o = o.reshape(b, l, -1)
    return _back(o, qkvz, scale, heads, eps, interpret), (q, k, v, o, *kept)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _chain(qkvz, taps, g, beta, scale, heads, eps, chunk, interpret):
    """`gated_delta_chain_pallas` on float32 operands of whole grid steps
    and row blocks."""
    return _chain_forward(qkvz, taps, g, beta, scale, heads, eps, chunk,
                          interpret, save_states=False)[0]


def _chain_fwd(qkvz, taps, g, beta, scale, heads, eps, chunk, interpret):
    out, kept = _chain_forward(qkvz, taps, g, beta, scale, heads, eps, chunk,
                               interpret, save_states=True)
    return out, (qkvz, taps, g, beta, scale, *kept)


def _chain_bwd(heads, eps, chunk, interpret, res, d_out):
    qkvz, taps, g, beta, scale, q, k, v, o, states, inverses = res
    hk, hv, dk, dv = heads
    b, l, _ = qkvz.shape
    d_qkvz, d_o, d_scale = _back_backward(
        o, qkvz, scale, d_out, None, heads, eps, interpret)
    d_q, d_k, d_v, d_g, d_beta = _backward(
        chunk, interpret,
        (q.reshape(b, l, hk, dk), k.reshape(b, l, hk, dk),
         v.reshape(b, l, hv, dv), g, beta, states, inverses),
        d_o.reshape(b, l, hv, dv))
    d_qkvz, d_taps = _front_backward(
        qkvz, taps, [t.reshape(b, l, -1) for t in (d_q, d_k, d_v)], d_qkvz,
        heads, interpret)
    return d_qkvz, d_taps, d_g, d_beta, d_scale


_chain.defvjp(_chain_fwd, _chain_bwd)


def gated_delta_chain_pallas(qkvz, taps, g, beta, scale, heads, eps: float,
                             chunk: int, interpret: bool = False):
    """A linear-attention layer between its two projections: qkvz
    [B, L, ...] (the input projection's output, columns [q | k | v | z]:
    Hk x dk, Hk x dk, Hv x dv, Hv x dv), taps [K, q, k and v's columns]
    (a causal depthwise convolution), g, beta [B, L, Hv], scale [dv],
    heads (Hk, Hv, dk, dv) -> [B, L, Hv x dv] float32, the output
    projection's input: the gated delta rule on SiLU(conv(q, k, v)), q
    and k of unit length a head and q scaled by dk ** -0.5, its output
    normed a head (RMS, `eps`, `scale`) times SiLU(z). Any length and
    any floating type: the passes (one `jax.custom_vjp`) see float32 and
    whole grid steps of the rule's kernels and whole row blocks, and the
    casts' and the filling's gradients are JAX's own.

    Every array crosses HBM once each way a pass, at the width it has:
    the front (`gdn_chain_front_fwd`: three calls over the projection's
    columns where they lie, a block's earlier rows read from the block
    before) writes q and k at the key heads and v in the layout the
    rule's kernels read; the back (`gdn_chain_back_fwd`) reads o and z
    and writes the gated output. The backward pass keeps the
    projection's output, q, k, v, o and the chunks' states and inverses
    and runs no forward pass again: `gdn_chain_back_bwd`, the rule's
    backward kernel and `gdn_chain_front_bwd` write the projection's
    gradient into ONE array, a block of columns each, and the taps' and
    the scale's gradients as per-block sums."""
    l = qkvz.shape[1]
    lp = l + -l % math.lcm(chunk * CHUNKS[-1], CHAIN_ROWS[-1])
    qkvz, g, beta = (_whole(t, lp) for t in (qkvz, g, beta))
    return _chain(qkvz, taps.astype(jnp.float32), g, beta,
                  scale.astype(jnp.float32), tuple(heads), eps, chunk,
                  interpret)[:, :l]
