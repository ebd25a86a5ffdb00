"""A gated short convolution between its two projections as Pallas TPU
passes: `gated_short_conv_pallas`, the kernels' route of
``ops/linear_attention.gated_short_conv``.

The input projection writes bcu [B, L, 3D], columns [b | c | u]; the
output projection reads y = c * conv(b * u), the convolution depthwise
and causal over K taps, no activation, no bias. Float32 in, float32
arithmetic, float32 out. Two passes under one `jax.custom_vjp`, each a
grid of `linear_attention_pallas._chain_call` whose blocks are whole
(8, 128) tiles of the projection's output where it lies: nothing is
split, padded or concatenated on the way.

* ``short_conv_chain_fwd``, a grid over (batch row, block of rows, block
  of columns): a block of b, of c and of u (three blocks of the ONE
  array, at column offsets 0, D, 2D) and the `halo` rows of b and u
  before it (zeros in a session's first block); `b * u`, the taps' sum
  and the gate `c` in VMEM; writes y. Reads 3D and writes D columns a
  position.
* ``short_conv_chain_bwd``, a grid over (batch row, block of rows), a
  block all 3D columns wide so that the three gradients land in the
  three column ranges of ONE [B, L, 3D] array, a chunk of columns at a
  time inside the kernel: reads bcu and dy (and the `halo` rows of b and
  u before the block, of c and dy after it: the convolution's transpose
  reaches past the block), makes `b * u` and the taps' sum again in
  VMEM, writes d_c = dy * mixed, d_b = u * conv^T(dy * c), d_u = b *
  conv^T(dy * c), and the taps' gradient summed over the block's rows
  ([B, blocks, K, D], added up outside). Reads 3D + D and writes 3D
  columns a position.

The backward pass keeps bcu (the projection's output, which the
projection's own backward products need anyway) and the taps, nothing
else; under a caller's `jax.checkpoint` the forward pass runs twice a
step and the backward pass once (PERF.md section 6, PR 42).
"""

from __future__ import annotations

import functools
import operator

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from predictionio_tpu.ops.linear_attention_pallas import (
    CHAIN_ROWS, _block, _chain_call, _halo, _halo_block, _row_sums,
)

#: columns a forward block, and a chunk of the backward kernel, takes
#: (the largest of these that divide D)
COLS = (512, 256, 128)
#: rows a backward block takes: all 3D columns wide, in and out, twice
#: each for the pipeline, 28 MiB at 256 rows of 3 x 2048 under
#: `linear_attention_pallas._VMEM_LIMIT`
BWD_ROWS = (256, 128)
#: rows a step of a kernel's inner loop takes (a divisor of a block's):
#: an array of 64 x 512 float32 is 32 registers of the core's 64
SUB = 64


def tiles(l: int, d: int, taps: int) -> bool:
    """Whether the passes lay a session of `l` positions, `d` columns a
    part and `taps` taps out: whole row blocks, whole lane tiles, and a
    convolution that reaches no further than one halo."""
    return (l % CHAIN_ROWS[-1] == 0 and d % COLS[-1] == 0
            and 1 <= taps <= CHAIN_ROWS[-1] + 1)


def _largest(sizes, n: int) -> int:
    return next(x for x in sizes if n % x == 0)


def _window_sum(window, w_ref, at, starts, n):
    """sum_j w[j, at] window[starts[j] : starts[j] + n]: the convolution
    on n rows (`starts` rising) or its transpose (falling), on the
    columns `at` of the taps."""
    return functools.reduce(operator.add, (
        w_ref[j:j + 1, at] * window[start:start + n]
        for j, start in enumerate(starts)))


def _by_rows(rows, step, carry=None):
    """`step(rows of a sub-block, the scratch window that starts `halo`
    rows before it, carry)` over a block's sub-blocks of `SUB` rows, in a
    loop the compiler does not unroll: a block's code is a sub-block's."""
    sub = min(SUB, rows)

    def body(r, carry):
        first = pl.multiple_of(r * sub, sub)
        return step(pl.ds(first, sub), lambda halo: pl.ds(first, sub + halo),
                    carry)

    return jax.lax.fori_loop(0, rows // sub, body, carry)


def _fwd_kernel(b_ref, c_ref, u_ref, b_before, u_before, w_ref, y_ref, ext):
    """c * conv(b * u) on a block, a sub-block of rows at a time. `ext`:
    b * u on the `halo` rows before the block, then on its own (a
    sub-block's are written before its window is read: the convolution
    looks back only)."""
    rows, halo, taps = b_ref.shape[1], b_before.shape[1], w_ref.shape[0]
    ext[0:halo] = jnp.where(pl.program_id(1) > 0,
                            b_before[0] * u_before[0], 0.0)

    def step(at, window, _):
        ext[pl.ds(at.start + halo, at.size)] = b_ref[0, at] * u_ref[0, at]
        y_ref[0, at] = c_ref[0, at] * _window_sum(
            ext[window(halo)], w_ref, slice(None),
            range(halo - (taps - 1), halo + 1), at.size)

    _by_rows(rows, step)


def _bwd_kernel(bcu_ref, b_before, u_before, c_after, w_ref, dy_ref,
                dy_after, d_ref, dw_ref, ext, g, *, cols):
    """`_fwd_kernel`'s gradients on a block of rows, all columns, `cols`
    at a time and of those a sub-block of rows at a time: into the three
    column ranges of the projection's gradient, and the taps' summed
    over the block's rows. `ext`: b * u on the rows before the block and
    its own; `g`: dy * c on the block's rows and the `halo` after them,
    which the convolution's transpose reads."""
    rows, halo, taps = dy_ref.shape[1], b_before.shape[1], w_ref.shape[0]
    d = dy_ref.shape[2]
    i, last = pl.program_id(1), pl.num_programs(1) - 1
    lead = halo - (taps - 1)
    for lo in range(0, d, cols):
        at_b, at_c, at_u = (slice(part * d + lo, part * d + lo + cols)
                            for part in range(3))
        ext[0:halo] = jnp.where(i > 0, b_before[0, :, at_b]
                                * u_before[0, :, at_b], 0.0)
        g[rows:] = jnp.where(i < last, dy_after[0, :, at_b]
                             * c_after[0, :, at_b], 0.0)

        def fill(at, window, _):
            ext[pl.ds(at.start + halo, at.size)] = \
                bcu_ref[0, at, at_b] * bcu_ref[0, at, at_u]
            g[at] = dy_ref[0, at, at_b] * bcu_ref[0, at, at_c]

        def step(at, window, d_taps):
            bu, dyc = ext[window(halo)], g[window(halo)]
            d_bu = _window_sum(dyc, w_ref, at_b, range(taps - 1, -1, -1),
                               at.size)
            for cut, grad in (
                    (at_b, d_bu * bcu_ref[0, at, at_u]),
                    (at_c, dy_ref[0, at, at_b] * _window_sum(
                        bu, w_ref, at_b, range(lead, lead + taps), at.size)),
                    (at_u, d_bu * bcu_ref[0, at, at_b])):
                d_ref[0, at, cut] = grad.astype(d_ref.dtype)
            return tuple(
                d_tap + _row_sums(dyc[0:at.size]
                                  * bu[lead + n:lead + n + at.size])
                for n, d_tap in enumerate(d_taps))

        _by_rows(rows, fill)
        d_taps = _by_rows(rows, step, tuple(
            jnp.zeros((1, cols), jnp.float32) for _ in range(taps)))
        for n, d_tap in enumerate(d_taps):
            dw_ref[0, 0, n:n + 1, at_b] = d_tap


def _forward(bcu, taps, interpret):
    b, l, d = bcu.shape[0], bcu.shape[1], bcu.shape[2] // 3
    halo, rows, cols = _halo(taps.shape[0]), _largest(CHAIN_ROWS, l), \
        _largest(COLS, d)
    n = d // cols
    return _chain_call(
        _fwd_kernel, "short_conv_chain_fwd", (b, l // rows, n),
        [_block(rows, cols), _block(rows, cols, n), _block(rows, cols, 2 * n),
         _halo_block(halo, rows, cols, 0, False, l),
         _halo_block(halo, rows, cols, 2 * n, False, l),
         pl.BlockSpec((taps.shape[0], cols), lambda b, i, c: (0, c))],
        _block(rows, cols), jax.ShapeDtypeStruct((b, l, d), jnp.float32),
        [pltpu.VMEM((halo + rows, cols), jnp.float32)], None, interpret)(
        bcu, bcu, bcu, bcu, bcu, taps)


def _backward(bcu, taps, dy, interpret, grad_dtype):
    """-> the projection's gradient [B, L, 3D] in `grad_dtype`, the taps'
    [K, D]."""
    b, l, d = dy.shape
    k, halo = taps.shape[0], _halo(taps.shape[0])
    rows, cols = _largest(BWD_ROWS, l), _largest(COLS, d)
    d_bcu, dw = _chain_call(
        functools.partial(_bwd_kernel, cols=cols), "short_conv_chain_bwd",
        (b, l // rows, 1),
        [_block(rows, 3 * d), _halo_block(halo, rows, d, 0, False, l),
         _halo_block(halo, rows, d, 2, False, l),
         _halo_block(halo, rows, d, 1, True, l),
         pl.BlockSpec((k, d), lambda b, i, c: (0, 0)), _block(rows, d),
         _halo_block(halo, rows, d, 0, True, l)],
        [_block(rows, 3 * d),
         pl.BlockSpec((1, 1, k, d), lambda b, i, c: (b, i, 0, 0))],
        [jax.ShapeDtypeStruct(bcu.shape, grad_dtype),
         jax.ShapeDtypeStruct((b, l // rows, k, d), jnp.float32)],
        [pltpu.VMEM((halo + rows, cols), jnp.float32),
         pltpu.VMEM((rows + halo, cols), jnp.float32)], None, interpret)(
        bcu, bcu, bcu, bcu, taps, dy, dy)
    return d_bcu, dw.sum((0, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _chain(bcu, taps, interpret, grad_dtype):
    return _forward(bcu, taps, interpret)


def _chain_fwd(bcu, taps, interpret, grad_dtype):
    return _forward(bcu, taps, interpret), (bcu, taps)


def _chain_bwd(interpret, grad_dtype, res, dy):
    d_bcu, d_taps = _backward(*res, dy, interpret, grad_dtype or jnp.float32)
    return d_bcu.astype(jnp.float32), d_taps


_chain.defvjp(_chain_fwd, _chain_bwd)


def gated_short_conv_pallas(bcu, taps, interpret: bool = False,
                            grad_dtype=None):
    """bcu [B, L, 3D] (the input projection's output, columns
    [b | c | u]), taps [K, D] -> c * conv(b * u) [B, L, D] in bcu's
    type, the output projection's input; sizes that `tiles` passes. Any
    floating type: the passes see float32, and the casts' gradients are
    JAX's own. `interpret` runs the kernels in the Pallas interpreter
    (the CPU tests). bcu's gradient is computed in float32 and comes
    back float32 unless the caller names a `grad_dtype`: then the
    backward pass writes it rounded once to that type (what the
    projection's two backward products would round it to themselves)."""
    return _chain(bcu.astype(jnp.float32), taps.astype(jnp.float32),
                  interpret, grad_dtype).astype(bcu.dtype)
