"""The chunked gated delta rule as Pallas TPU kernels, forward and
backward.

The same mathematics as the scan of ``ops/linear_attention._scan`` (the
WY form: a unit lower triangular system within a chunk, one state update
between chunks), with a head's state S [dk, dv] held in VMEM from its
first chunk to its last and everything a chunk computes on the way (the
decay matrix, the system and its inverse, `u`, `w`, the chunk's scores)
made in VMEM from the chunk's rows of q, k, v and never written to HBM:

    v_new = u - w S        o = q S + qk v_new        S <- decay S + k^T v_new

Two kernels, each a grid over (batch row, heads, chunks), the chunks
innermost and sequential, a few heads and chunks a grid step; q, k, v, o
and their gradients are read and written where they lie, [B, L, H x d]:

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
"""

from __future__ import annotations

import functools
import types

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
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

#: `jax.ad_checkpoint.checkpoint_name`s of what the forward pass hands
#: the backward pass besides the rule's inputs: the output, the chunks'
#: states and their systems' inverses. A caller that recomputes around
#: the rule (`models/seqrec._linear_attention` under `remat`) keeps
#: these and so runs no kernel a third time.
KEPT = ("gated_delta_rule_out", "gated_delta_rule_states",
        "gated_delta_rule_inverses")

_VMEM_LIMIT = 64 * 1024 * 1024

_NT = (((1,), (1,)), ((), ()))     # a @ b.T
_TN = (((0,), (0,)), ((), ()))     # a.T @ b
_BF16 = jnp.bfloat16
_HIGHEST = jax.lax.Precision.HIGHEST


def tiles(dk: int, dv: int) -> bool:
    """Whether the kernels lay these widths out: whole lane tiles, and a
    head's state within what was compiled (tests/test_tpu_compile.py)."""
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


def _rows_of(refs, j, h, c, widths):
    """Chunk j of head h of the grid step's blocks [1, chunks C, heads d]."""
    return [ref[0, j * c:(j + 1) * c, h * d:(h + 1) * d]
            for ref, d in zip(refs, widths)]


def _fwd_kernel(q_ref, k_ref, v_ref, gates_ref, o_ref, *refs, heads, chunks,
                save_states):
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
            rows = [_rows_of((q_ref, k_ref, v_ref), j, h, c, (dk, dk, dv))
                    for j in (pair, pair + 1)]
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
                dq_ref, dk_ref, dv_ref, dgates_ref, ds_scr, *, heads, chunks):
    c = gates_ref.shape[-1]
    _, dk, dv = ds_scr.shape

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    def row_sums(t):
        return t.sum(axis=1, keepdims=True)

    d_states = [ds_scr[h] for h in range(heads)]
    for j in reversed(range(chunks)):
        for h in range(heads):
            q, k, v, do = _rows_of((q_ref, k_ref, v_ref, do_ref), j, h, c,
                                   (dk, dk, dv, dv))
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
            dq_ref[0, j * c:(j + 1) * c, h * dk:(h + 1) * dk] = \
                _dot(d_qk, k) + d_q_since * x.since
            dk_ref[0, j * c:(j + 1) * c, h * dk:(h + 1) * dk] = \
                _dot32(d_kk, x.k_beta, _TN) + _dot(d_qk, q, _TN) \
                + d_k_until * x.until + d_k_beta * x.beta
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


def _layout(b, l, h, chunk, backward):
    """The grid, and block specs by what a block holds: rows of q, k, v,
    o [B, L, H x width]; a (head, chunk)'s gates [B, H, N, 2, C], state
    [B, H, N, dk, dv] or inverse [B, H, N, C, C]. The backward pass takes
    the chunks last to first."""
    n = l // chunk
    if l % chunk or n % CHUNKS[-1]:
        raise ValueError(f"{l} positions are no whole grid steps of "
                         f"{CHUNKS[-1]} chunks of {chunk}")
    heads = next(x for x in HEADS if h % x == 0)
    chunks = next(x for x in CHUNKS if n % x == 0)
    last = n // chunks - 1
    at = (lambda i: last - i) if backward else (lambda i: i)

    def rows(width):
        return pl.BlockSpec((1, chunks * chunk, heads * width),
                            lambda b, h, i: (b, at(i), h))

    def per_chunk(*shape):
        return pl.BlockSpec((1, heads, chunks, *shape),
                            lambda b, h, i: (b, h, at(i), 0, 0))

    return heads, chunks, (b, h // heads, n // chunks), rows, per_chunk


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
    b, l, h, dk = q.shape
    dv = v.shape[-1]
    heads, chunks, grid, rows, per_chunk = _layout(b, l, h, chunk, False)
    out_specs = [rows(dv)]
    out_shape = [jax.ShapeDtypeStruct((b, l, h * dv), jnp.float32)]
    if save_states:
        for shape in ((dk, dv), (chunk, chunk)):
            out_specs.append(per_chunk(*shape))
            out_shape.append(jax.ShapeDtypeStruct(
                (b, h, l // chunk, *shape), jnp.float32))
    o, *kept = _call(
        functools.partial(_fwd_kernel, heads=heads, chunks=chunks,
                          save_states=save_states),
        "gated_delta_rule_pallas_fwd", grid,
        [rows(dk), rows(dk), rows(dv), per_chunk(2, chunk)], out_specs,
        out_shape, pltpu.VMEM((heads, dk, dv), jnp.float32), interpret)(
        _flat(q), _flat(k), _flat(v), _gates(g, beta, chunk))
    return o.reshape(b, l, h, dv), tuple(kept)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def gated_delta_rule_pallas(q, k, v, g, beta, chunk: int,
                            interpret: bool = False):
    """q, k [B, L, H, dk], v [B, L, H, dv], g, beta [B, L, H], L whole
    grid steps (`CHUNKS[-1]` chunks) -> o [B, L, H, dv] float32, from a
    state of 0. The backward pass keeps the inputs and, of the chunked
    form, the state each chunk started from and its system's inverse
    (`KEPT` names them and the output). `interpret` runs the kernels in
    the Pallas interpreter (the CPU tests)."""
    return _forward(q, k, v, g, beta, chunk, interpret, save_states=False)[0]


def _fwd(q, k, v, g, beta, chunk, interpret):
    o, kept = _forward(q, k, v, g, beta, chunk, interpret, save_states=True)
    o, *kept = (checkpoint_name(t, name) for t, name in zip((o, *kept), KEPT))
    return o, (q, k, v, g, beta, *kept)


def _bwd(chunk, interpret, res, d_out):
    q, k, v, g, beta, states, inverses = res
    b, l, h, dk = q.shape
    dv = v.shape[-1]
    heads, chunks, grid, rows, per_chunk = _layout(b, l, h, chunk, True)
    d_q, d_k, d_v, d_gates = _call(
        functools.partial(_bwd_kernel, heads=heads, chunks=chunks),
        "gated_delta_rule_pallas_bwd", grid,
        [rows(dk), rows(dk), rows(dv), per_chunk(2, chunk),
         per_chunk(dk, dv), per_chunk(chunk, chunk), rows(dv)],
        [rows(dk), rows(dk), rows(dv), per_chunk(2, chunk)],
        [jax.ShapeDtypeStruct((b, l, h * width), jnp.float32)
         for width in (dk, dk, dv)]
        + [jax.ShapeDtypeStruct((b, h, l // chunk, 2, chunk), jnp.float32)],
        pltpu.VMEM((heads, dk, dv), jnp.float32), interpret)(
        _flat(q), _flat(k), _flat(v), _gates(g, beta, chunk), states,
        inverses, _flat(d_out))
    # [B, H, N, C] -> [B, L, H]; g entered by its sum since the chunk's
    # start, so its gradient is the sum until the chunk's end
    d_gc, d_beta = (jnp.transpose(d_gates[:, :, :, i], (0, 2, 3, 1))
                    for i in (0, 1))
    d_g = jnp.flip(jnp.cumsum(jnp.flip(d_gc, axis=2), axis=2), axis=2)
    return (d_q.reshape(q.shape).astype(q.dtype),
            d_k.reshape(k.shape).astype(k.dtype),
            d_v.reshape(v.shape).astype(v.dtype),
            d_g.reshape(g.shape).astype(g.dtype),
            d_beta.reshape(beta.shape).astype(beta.dtype))


gated_delta_rule_pallas.defvjp(_fwd, _bwd)
