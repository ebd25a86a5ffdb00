"""Sparse experts: a router over all of a layer's experts, and an expert
layer that is told which of them it holds.

Under expert parallelism each chip holds a contiguous range of a layer's
routed experts and every chip routes every token over all of them (the
router is small and replicated). `held_experts` computes what the experts
held here add to the layer's output for the tokens routed to them; what
the absent experts would have added is left out, and the sum over all
the shares (plus whatever every chip computes alike, counted once) is the
whole layer. On one chip the layer runs without its exchange: nothing
here stands in for the other chips.

No token is dropped. The tokens routed to the held experts are sorted by
expert and multiplied group by group (a grouped matrix product: each
expert's rows against that expert's matrices). The
number of such rows varies from step to step between 0 and
tokens x min(k, held); shapes are static, so the rows are taken
`pass_rows` at a time in a loop that runs as many passes as there are
rows: the work follows the load, and a router that sends every token to
one expert costs more passes, not tokens. The loop's length is not known
when the program is traced, so the layer brings its own backward pass
(`custom_vjp`), which walks the same passes again, keeps one pass's
intermediates at a time and writes its six gradient products out
(four where an expert is two matrices around a squared ReLU).

Which kernel multiplies a pass is `grouped_product_route`'s to say, from
the device's kind, the sizes and the devices the program is traced for:
"pallas", the kernels of ops/moe_pallas.py (blocks of a row tile by a
whole matrix, `moe_pallas.tiles` rows a tile: a row, a matrix of a group
and a result cross HBM once), on a v5e in a program for one device at
widths in whole lane tiles; "xla", `jax.lax.ragged_dot` (XLA's own
kernel, tiles of 512 where 512 divides a dimension and 128 where not),
everywhere else: the CPU, a mesh, other TPUs. Either way a product takes
its operands in one bfloat16 pass (the TPU's default), accumulates in
float32 and returns float32, the gradient products too.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
from typing import Callable, Iterator, NamedTuple, Optional, Set, Tuple

import jax
import jax.numpy as jnp

from predictionio_tpu.ops import attention_pallas, moe_pallas


class Routing(NamedTuple):
    #: [T, k] the experts chosen for each token, out of all n_routed
    experts: jax.Array
    #: [T, k] the weight of each chosen expert in the token's output
    gates: jax.Array
    #: [T, n_routed] the affinities (sigmoids, or the softmax's
    #: probabilities), for the balance terms
    scores: jax.Array


def route(x: jax.Array, w_router: jax.Array, bias: jax.Array, top_k: int,
          scaling: float = 1.0, norm_topk: bool = True,
          scoring: str = "sigmoid", norm_eps: float = 1e-20) -> Routing:
    """Router with bias-steered selection (auxiliary-loss-free
    balancing): the affinities are each output's sigmoid (`scoring`
    "sigmoid") or the softmax over all outputs ("softmax"); the top-k of
    score + bias are chosen, the gates are the chosen scores themselves
    (normalised when `norm_topk`: over their sum + `norm_eps`, which a
    model family fixes; then scaled), so the bias
    moves which experts are picked and never the output's weights, and
    takes no gradient. The affinities are computed at the highest matmul
    precision: a rounding that flips the k-th and (k+1)-th expert of a
    token changes which weights it trains."""
    logits = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.softmax(logits, axis=-1) if scoring == "softmax" \
        else jax.nn.sigmoid(logits)
    _, experts = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), top_k)
    gates = jnp.take_along_axis(scores, experts, axis=-1)
    if norm_topk:
        gates = gates / (gates.sum(axis=-1, keepdims=True) + norm_eps)
    return Routing(experts, gates * scaling, scores)


def expert_load(experts: jax.Array, n_routed: int) -> jax.Array:
    """[n_routed] tokens routed to each expert (every chosen slot
    counts once)."""
    return jnp.bincount(experts.reshape(-1), length=n_routed)


def sequence_balance_loss(routing: Routing, n_sequences: int) -> jax.Array:
    """Sequence-wise balance loss (DeepSeek-V3 eq. 17-20), without its
    coefficient: for each sequence sum_e f_e P_e, with f_e the share of
    the sequence's k T slots that chose expert e times n_routed, and P_e
    the sequence's mean of the affinities normalised over the experts;
    averaged over the sequences. Tokens are sequence-major."""
    t, k = routing.experts.shape
    e = routing.scores.shape[-1]
    per_seq = t // n_sequences
    chosen = jax.nn.one_hot(routing.experts, e, dtype=jnp.float32).sum(1)
    f = chosen.reshape(n_sequences, per_seq, e).sum(1) * (e / (k * per_seq))
    norm = routing.scores / routing.scores.sum(axis=-1, keepdims=True)
    p = norm.reshape(n_sequences, per_seq, e).mean(axis=1)
    return (jax.lax.stop_gradient(f) * p).sum(axis=-1).mean()


def bias_update(bias: jax.Array, load: jax.Array, rate: float) -> jax.Array:
    """After a step: an overloaded expert's bias falls by `rate`, an
    underloaded one's rises (b += rate * sign(mean load - load))."""
    load = load.astype(jnp.float32)
    return bias + rate * jnp.sign(load.mean() - load)


#: what an expert computes: three matrices around a SiLU gate, or two
#: around a squared ReLU
EXPERT_KINDS = ("swiglu", "relu2")


def grouped_product_route(device_kind: str, rows: int, d: int, w: int,
                          groups: int, devices: int = 1) -> str:
    """Which kernel multiplies a pass of `rows` sorted rows by `groups`
    experts' d x w matrices on a device of this kind
    (`jax.Device.device_kind`), in a program traced for `devices`
    devices: "pallas", the kernels of ops/moe_pallas.py, on the TPUs the
    attention kernels are listed for (`attention_pallas.KINDS`), for
    sizes they tile (`moe_pallas.tiles`), in a program for one device
    (the compiler partitions no Mosaic kernel); "xla",
    `jax.lax.ragged_dot`, everywhere else."""
    if (device_kind in attention_pallas.KINDS and devices == 1
            and moe_pallas.tiles(rows, d, w, groups) is not None):
        return "pallas"
    return "xla"


def _device_kind() -> str:
    return jax.devices()[0].device_kind


_ROUTES: contextvars.ContextVar[Optional[Set[str]]] = contextvars.ContextVar(
    "grouped_product_routes", default=None)


@contextlib.contextmanager
def routes_into(routes: Set[str]) -> Iterator[None]:
    """While the block runs (a trace), every `held_experts` call adds the
    route its grouped products took to `routes`."""
    token = _ROUTES.set(routes)
    try:
        yield
    finally:
        _ROUTES.reset(token)


# dy [M, N] by w [G, K, N] transposed, and x [M, K] transposed by
# dy [M, N] a group: what `ragged_dot`'s own transposes are
_ROWS_T = jax.lax.RaggedDotDimensionNumbers(
    (((1,), (2,)), ((), ())), [0], [0])
_GROUPS = jax.lax.RaggedDotDimensionNumbers(
    (((0,), (0,)), ((), ())), [0], [])


class _Products(NamedTuple):
    """The three grouped products of a pass over its groups' rows, each
    (a, b) -> float32, and the row tile of the Pallas kernels that make
    them (None: `ragged_dot` does)."""
    rows_by_matrix: Callable        # x [M, K], w [G, K, N] -> [M, N]
    rows_by_matrix_t: Callable      # dy [M, N], w [G, K, N] -> [M, K]
    rows_t_by_rows: Callable        # x [M, K], dy [M, N] -> [G, K, N]
    tile: Optional[int]


def _products(sizes, rows: int, tile: Optional[int]) -> _Products:
    if tile is None:
        return _Products(
            lambda x, w: jax.lax.ragged_dot(x, w, sizes),
            lambda dy, w: jax.lax.ragged_dot_general(dy, w, sizes, _ROWS_T),
            lambda x, dy: jax.lax.ragged_dot_general(x, dy, sizes, _GROUPS),
            None)
    plan = moe_pallas.schedule(sizes, rows, tile)
    return _Products(*(
        functools.partial(f, plan=plan) for f in (
            moe_pallas.rows_by_matrix, moe_pallas.rows_by_matrix_t,
            moe_pallas.rows_t_by_rows)), tile)


def _operand(rows, tile):
    """The kernels round a product's operands to bfloat16; rows made for
    them are written rounded (half the bytes, the same product)."""
    return rows if tile is None else rows.astype(jnp.bfloat16)


def _pass_rows(lo, plan, k, pass_rows):
    """Of the sorted slots [lo, lo + pass_rows): (slot, its token, the
    rows of each held expert among them, which rows are slots at all)."""
    order, starts, ends, n_rows = plan
    slot = jax.lax.dynamic_slice_in_dim(order, lo, pass_rows)
    sizes = jnp.clip(ends, lo, lo + pass_rows) \
        - jnp.clip(starts, lo, lo + pass_rows)
    valid = (lo + jnp.arange(pass_rows) < n_rows)[:, None]
    return slot, slot // k, sizes, valid


def _pass_forward(xs, w_gate, w_up, w_down, valid, products):
    """A pass's rows through their experts, before the gates: (the rows
    as the products took them, gate and up products, the hidden rows,
    the output). Without `w_gate` the experts are two matrices around a
    squared ReLU and there is no gate product (None)."""
    # rows past the last group are no expert's: what a grouped product
    # leaves there is masked on the way in and on the way out
    xs = _operand(jnp.where(valid, xs, 0.0), products.tile)
    g = None if w_gate is None else products.rows_by_matrix(xs, w_gate)
    u = products.rows_by_matrix(xs, w_up)
    h = _operand(jnp.square(jax.nn.relu(u)) if g is None
                 else jax.nn.silu(g) * u, products.tile)
    out = jnp.where(valid, products.rows_by_matrix(h, w_down), 0.0)
    return xs, g, u, h, out


def _pass_grads(xs, gate, w_gate, w_up, w_down, valid, d_rows, products):
    """The gradients of a pass's gated output (`_pass_forward`'s by
    `gate`) for its rows' gradient `d_rows`: (d_xs, d_gate, d_w_gate,
    d_w_up, d_w_down), the pass computed again and the six gradient
    products written out, each float32; of two-matrix experts (no
    `w_gate`) four products and no d_w_gate."""
    xs, g, u, h, out = _pass_forward(xs, w_gate, w_up, w_down, valid,
                                     products)
    d_rows = jnp.where(valid, d_rows, 0.0)
    d_gate = (out * d_rows).sum(-1)
    d_out = _operand(d_rows * gate[:, None], products.tile)
    d_h = products.rows_by_matrix_t(d_out, w_down)
    if w_gate is None:
        d_u = _operand(d_h * 2.0 * jax.nn.relu(u), products.tile)
        return (jnp.where(valid, products.rows_by_matrix_t(d_u, w_up), 0.0),
                d_gate, products.rows_t_by_rows(xs, d_u),
                products.rows_t_by_rows(h, d_out))
    sig = jax.nn.sigmoid(g)
    d_g = _operand(d_h * u * sig * (1.0 + g * (1.0 - sig)), products.tile)
    d_u = _operand(d_h * g * sig, products.tile)
    d_xs = products.rows_by_matrix_t(d_g, w_gate) \
        + products.rows_by_matrix_t(d_u, w_up)
    return (jnp.where(valid, d_xs, 0.0), d_gate,
            products.rows_t_by_rows(xs, d_g),
            products.rows_t_by_rows(xs, d_u),
            products.rows_t_by_rows(h, d_out))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _grouped_experts(x, gates, w_gate, w_up, w_down, plan, k, pass_rows,
                     tile):
    return _grouped_experts_fwd(x, gates, w_gate, w_up, w_down, plan, k,
                                pass_rows, tile)[0]


def _grouped_experts_fwd(x, gates, w_gate, w_up, w_down, plan, k, pass_rows,
                         tile):
    n_rows = plan[3]

    def one_pass(carry):
        lo, y = carry
        slot, token, sizes, valid = _pass_rows(lo, plan, k, pass_rows)
        out = _pass_forward(
            x[token], w_gate, w_up, w_down, valid,
            _products(sizes, pass_rows, tile))[-1] * gates[slot][:, None]
        return lo + pass_rows, y.at[token].add(out.astype(y.dtype))

    _, y = jax.lax.while_loop(
        lambda c: c[0] < n_rows, one_pass,
        (jnp.zeros((), n_rows.dtype), jnp.zeros_like(x)))
    return y, (x, gates, w_gate, w_up, w_down, plan)


def _grouped_experts_bwd(k, pass_rows, tile, res, d_y):
    x, gates, w_gate, w_up, w_down, plan = res
    n_rows = plan[3]

    def one_pass(carry):
        lo, d_x, d_gates, d_w = carry
        slot, token, sizes, valid = _pass_rows(lo, plan, k, pass_rows)
        d_xs, d_gate, *d_w_pass = _pass_grads(
            x[token], gates[slot], w_gate, w_up, w_down, valid, d_y[token],
            _products(sizes, pass_rows, tile))
        return (lo + pass_rows, d_x.at[token].add(d_xs.astype(d_x.dtype)),
                d_gates.at[slot].add(d_gate.astype(d_gates.dtype)),
                tuple(a + b.astype(a.dtype) for a, b in zip(d_w, d_w_pass)))

    _, d_x, d_gates, d_w = jax.lax.while_loop(
        lambda c: c[0] < n_rows, one_pass,
        (jnp.zeros((), n_rows.dtype), jnp.zeros_like(x),
         jnp.zeros_like(gates),
         tuple(jnp.zeros_like(w) for w in (w_gate, w_up, w_down)
               if w is not None)))
    return (d_x, d_gates, *((None,) if w_gate is None else ()), *d_w, None)


_grouped_experts.defvjp(_grouped_experts_fwd, _grouped_experts_bwd)


def held_experts(x: jax.Array, w_gate: Optional[jax.Array], w_up: jax.Array,
                 w_down: jax.Array, routing: Routing, first_held: int,
                 pass_rows: int, devices: int = 1
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """What the experts [first_held, first_held + n_held) add to the
    layer's output. x: [T, d]; w_gate, w_up: [n_held, d, w]; w_down:
    [n_held, w, d]. With `w_gate` the experts are "swiglu": silu(x
    W_gate) (x W_up) W_down; with None "relu2": relu(x W_up)^2 W_down,
    two matrices an expert, four gradient products for six; the sort,
    the passes and the route are the same. Returns (y [T, d], tokens
    each held expert received [n_held], tokens dropped: those routed to a held
    expert whose row of y is all zeros, read from the output and not from
    the loop's own count; a token whose input is all zeros would read
    the same). The routed slots are multiplied `pass_rows` at a time, by
    the kernel `grouped_product_route` names for the device's kind, the
    sizes and `devices` (how many devices the calling program is traced
    for: a mesh's size)."""
    t, k = routing.experts.shape
    n_held, d, w = w_up.shape
    local = routing.experts.reshape(-1) - first_held
    here = (local >= 0) & (local < n_held)
    # absent experts sort last; the held experts' slots come first, by expert
    key = jnp.where(here, local, n_held)
    order = jnp.argsort(key, stable=True)
    counts = jnp.bincount(key, length=n_held + 1)[:n_held]
    ends = jnp.cumsum(counts)
    pass_rows = min(pass_rows, t * k)
    route = grouped_product_route(_device_kind(), pass_rows, d, w, n_held,
                                  devices)
    heard = _ROUTES.get()
    if heard is not None:
        heard.add(route)
    tile = moe_pallas.tiles(pass_rows, d, w, n_held) \
        if route == "pallas" else None
    plan = (jnp.pad(order, (0, pass_rows)), ends - counts, ends,
            counts.sum())
    y = _grouped_experts(x, routing.gates.reshape(-1), w_gate, w_up, w_down,
                         plan, k, pass_rows, tile)
    dropped = (here.reshape(t, k).any(-1) & ~(y != 0).any(-1)).sum()
    # counted here and now: left to the scheduler, the count is taken at
    # the end of the step and every layer's y is kept until then
    y, dropped = jax.lax.optimization_barrier((y, dropped))
    return y, counts, dropped
