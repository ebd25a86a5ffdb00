"""The expert layer's grouped products as Pallas TPU kernels.

A pass of ``ops/moe._grouped_experts`` holds rows sorted by expert, the
rows of expert g being [offsets[g], offsets[g + 1]), and multiplies each
group by its expert's matrix. Three products, one kernel each, every one
a grid over the row tiles the groups cover and no others (a row tile
that two groups share is visited once for each; rows past the last group
are no grid step at all), the grid made once a pass (``schedule``):

* ``rows_by_matrix`` (``grouped_product_pallas_rows``): x [M, K] by
  w [G, K, N] -> [M, N], group by group;
* ``rows_by_matrix_t`` (``grouped_product_pallas_rows_t``): dy [M, N]
  by w [G, K, N] transposed -> [M, K];
* ``rows_t_by_rows`` (``grouped_product_pallas_groups``): x [M, K]
  transposed by dy [M, N] -> [G, K, N], a group's rows only.

A block is a row tile by the whole of the other two dimensions: a row is
read once, a result is written once, and an expert's matrix, whose block
index does not change between the row tiles of its group, is read once a
group (XLA's own ``ragged-dot`` kernel takes column tiles of 128 where
512 does not divide the width and reads the rows once a column tile).
The row-tile schedule is megablox's
(``jax.experimental.pallas.ops.tpu.megablox.gmm.make_group_metadata``);
its kernels are not used: they multiply float32 operands in float32
passes, tile the contraction (so the matrix is read once a row tile),
carry no name and take no VMEM limit, so the blocks above do not fit.

Precision: every product takes its operands in bfloat16 (what the TPU's
default does to float32 operands), rounded once in VMEM (operands handed
over in bfloat16 are taken as they are; a matrix's rounded copy is kept
in VMEM across its group's row tiles), and accumulates in float32; every
result is float32.

What the rows outside every group hold in a result is not defined (the
caller masks them, as it does for ``lax.ragged_dot``); what they hold on
the way in never reaches a result, NaN included.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

_VMEM_LIMIT = 100 * 1024 * 1024

_NN = (((1,), (0,)), ((), ()))     # a @ b
_NT = (((1,), (1,)), ((), ()))     # a @ b.T
_TN = (((0,), (0,)), ((), ()))     # a.T @ b
_BF16 = jnp.bfloat16
_LANES = 128

#: the row tiles `tiles` chooses among, largest first. 128 at most, from
#: chip runs of each kernel alone at the sequence cells' passes (PERF.md
#: section 6, PR 37), ms a call at row tiles of 128 / 256 / 512 / 1024:
#: 16,384 x 2048 x 1408 x 8 with 12,288 rows routed: x W 0.596 / 0.602 /
#: 0.623 / 0.689, h W_down 0.615 / 0.615 / 0.643 / 0.697, dy W^T 0.610 /
#: 0.606 / 0.623 / 0.702, x^T dy 0.623 / 0.631 / 0.629 / 0.706; 32,768 x
#: 2048 x 1536 x 8 with 16,384 routed: 0.794-0.826 / 0.793-0.805 /
#: 0.801-0.816 / 0.884-0.892; 16,384 x 2048 x 512 x 32 with 10,240
#: routed: 0.420-0.480 / 0.417-0.512 / 0.422-0.537. A larger tile is no
#: faster, and it is a larger kernel of which a step's executable holds
#: sixty instances: an expert layer's forward and backward compiled for
#: a v5e serialise to 19.3 MB at 128, 22.1 at 256, 29.2 at 512 (15.4 on
#: `ragged_dot`), and a process's first train reads all of it.
ROW_TILES = (128, 64, 32, 16, 8)


def tiles(rows: int, d: int, w: int, groups: int) -> Optional[int]:
    """The row tile of a pass of `rows` rows over `groups` experts of
    d x w matrices, or None where the kernels lay these sizes out in no
    tile: widths in whole lane tiles, a matrix block (whole, float32, in
    two buffers, beside its rounded copy) within the VMEM limit, and a
    row tile that divides the rows. The tile is the largest of ROW_TILES
    within a quarter of the rows a group holds when every row is routed
    and the groups are even (rows / groups): a group then fills most of
    the tiles it touches, and the steps at its two ends, where it shares
    a tile with its neighbours, are a small part of its work (128 in the
    three sequence cells; their smallest groups hold some 320 rows)."""
    if d % 128 or w % 128 or d * w * 10 > _VMEM_LIMIT // 2:
        return None
    for tile in ROW_TILES:
        if rows % tile == 0 and (4 * tile * groups <= rows
                                 or tile == ROW_TILES[-1]):
            return tile
    return None


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=("offsets", "group_ids", "tile_ids", "steps"),
    meta_fields=("tile",))
@dataclasses.dataclass(frozen=True)
class Schedule:
    """The grid of a pass's products: one step a (group, row tile) in
    which the group has rows, and one for a group without rows (its
    gradient is written as zeros)."""
    #: [G + 1] the row each group starts at; the last group's end
    offsets: jax.Array
    #: [steps'] the group and the row tile of each grid step
    group_ids: jax.Array
    tile_ids: jax.Array
    #: [] the number of grid steps
    steps: jax.Array
    #: rows a tile
    tile: int


# `schedule` and the three products are jitted on their own: a step
# calls them some sixty times at six signatures, and a call after a
# signature's first costs the caller's trace a lookup, not the kernel's
# body again (tracing it anew every time took 2.5 s of a cell's set-up)
@functools.partial(jax.jit, static_argnames=("rows", "tile"))
def schedule(sizes: jax.Array, rows: int, tile: int) -> Schedule:
    """The grid of a pass of `rows` rows of which the first sum(sizes)
    are the groups', in row tiles of `tile`, which divides `rows`: made
    once a pass, for all its products."""
    (offsets, group_ids, tile_ids), steps = make_group_metadata(
        group_sizes=sizes.astype(jnp.int32), m=rows, tm=tile,
        start_group=jnp.zeros((), jnp.int32),
        num_nonzero_groups=sizes.shape[0], visit_empty_groups=True)
    return Schedule(offsets, group_ids, tile_ids, steps, tile)


def _step(offsets, group_ids, tile_ids, tile):
    """Of this grid step: whether it is its group's first, and which of
    the tile's rows [tile, 1] are the group's."""
    i = pl.program_id(0)
    group = group_ids[i]
    first = (i == 0) | (group != group_ids[jnp.maximum(i - 1, 0)])
    row = tile_ids[i] * tile + jax.lax.broadcasted_iota(
        jnp.int32, (tile, 1), 0)
    return first, (row >= offsets[group]) & (row < offsets[group + 1])


def _by_rows(n, body):
    """`body` on each lane tile of rows [_LANES] of n, as a loop the
    compiler does not unroll: a matrix is 16 such tiles and more, and a
    step's sixty kernel instances are all in its executable."""
    def one(i, _):
        body(pl.ds(pl.multiple_of(i * _LANES, _LANES), _LANES))

    jax.lax.fori_loop(0, n // _LANES, one, None)


def _rows_kernel(offsets, group_ids, tile_ids, x, w, out, w_rounded, *,
                 tile, dims):
    first, mine = _step(offsets, group_ids, tile_ids, tile)

    @pl.when(first)
    def _():
        def rounded(rows):
            w_rounded[rows, :] = w[rows, :].astype(w_rounded.dtype)

        _by_rows(w.shape[0], rounded)

    product = jax.lax.dot_general(
        x[...].astype(w_rounded.dtype), w_rounded[...], dims,
        preferred_element_type=jnp.float32)
    # a tile two groups share is two consecutive steps on one block
    out[...] = jnp.where(mine, product, out[...])


def _groups_kernel(offsets, group_ids, tile_ids, x, dy, out, *, tile,
                   operand):
    first, mine = _step(offsets, group_ids, tile_ids, tile)

    @pl.when(first)
    def _():
        def zero(rows):
            out[rows, :] = jnp.zeros((_LANES, out.shape[1]), out.dtype)

        _by_rows(out.shape[0], zero)

    # an empty group's one step finds none of its rows; both operands
    # masked: 0 x NaN is NaN
    out[...] += jax.lax.dot_general(
        jnp.where(mine, x[...], 0).astype(operand),
        jnp.where(mine, dy[...], 0).astype(operand), _TN,
        preferred_element_type=jnp.float32)


def _call(kernel, name, plan, in_specs, out_spec, out_shape, scratch_shapes,
          interpret):
    return functools.partial(
        pl.pallas_call(
            functools.partial(kernel, tile=plan.tile), name=name,
            out_shape=out_shape,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=(plan.steps,), in_specs=in_specs,
                out_specs=out_spec, scratch_shapes=scratch_shapes),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret),
        plan.offsets, plan.group_ids, plan.tile_ids)


def _by_tile(tile, width):
    return pl.BlockSpec((tile, width), lambda i, o, g, t: (t[i], 0))


def _by_group(k, n):
    return pl.BlockSpec((None, k, n), lambda i, o, g, t: (g[i], 0, 0))


@functools.partial(jax.jit,
                   static_argnames=("name", "dims", "operand", "interpret"))
def _rows(x, w, plan, name, dims, operand, interpret):
    rows, width = x.shape
    _, k, n = w.shape
    out_width = n if dims == _NN else k
    return _call(
        functools.partial(_rows_kernel, dims=dims), name, plan,
        [_by_tile(plan.tile, width), _by_group(k, n)],
        _by_tile(plan.tile, out_width),
        jax.ShapeDtypeStruct((rows, out_width), jnp.float32),
        [pltpu.VMEM((k, n), operand)], interpret)(x, w)


@functools.partial(jax.jit, static_argnames=("operand", "interpret"))
def _groups(x, dy, plan, operand, interpret):
    k, n = x.shape[1], dy.shape[1]
    return _call(
        functools.partial(_groups_kernel, operand=operand),
        "grouped_product_pallas_groups", plan,
        [_by_tile(plan.tile, k), _by_tile(plan.tile, n)], _by_group(k, n),
        jax.ShapeDtypeStruct((plan.offsets.shape[0] - 1, k, n), jnp.float32),
        [], interpret)(x, dy)


def rows_by_matrix(x: jax.Array, w: jax.Array, plan: Schedule,
                   interpret: bool = False) -> jax.Array:
    """x [M, K], w [G, K, N] -> [M, N] float32: the rows of group g,
    [offsets[g], offsets[g + 1]), are x's by w[g]. `plan` is
    `schedule`'s for M rows. `interpret` runs the kernel in the Pallas
    interpreter (the CPU tests)."""
    return _rows(x, w, plan, "grouped_product_pallas_rows", _NN, _BF16,
                 interpret)


def rows_by_matrix_t(dy: jax.Array, w: jax.Array, plan: Schedule,
                     interpret: bool = False) -> jax.Array:
    """dy [M, N], w [G, K, N] -> [M, K] float32: a group's rows of dy by
    its matrix transposed."""
    return _rows(dy, w, plan, "grouped_product_pallas_rows_t", _NT, _BF16,
                 interpret)


def rows_t_by_rows(x: jax.Array, dy: jax.Array, plan: Schedule,
                   interpret: bool = False) -> jax.Array:
    """x [M, K], dy [M, N] -> [G, K, N] float32: for each group its rows
    of x, transposed, by its rows of dy (zeros for a group without
    rows)."""
    return _groups(x, dy, plan, _BF16, interpret)
