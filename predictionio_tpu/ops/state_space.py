"""A state-space layer's scan (Mamba-2's state-space duality,
arXiv:2405.21060), beside the gated delta rule of ops/linear_attention.py.

Each head keeps a state S [P, N] (head width by state size). Position t
lets the state decay by a scalar a head, writes the outer product of its
input and its B, and reads with its C:

    a_t = exp(-rate dt_t)
    S_t = a_t S_(t-1) + dt_t x_t B_t^T
    y_t = S_t C_t + D x_t

It is gated linear attention WITHOUT the delta rule's correction (nothing
already in the state is taken back), so there is no triangular system to
solve and `linear_attention.gated_delta_rule` cannot be told to do it.
B and C belong to a group of heads (G groups, H / G heads each read one
B/C pair): they are read at their group, never repeated to the heads.

`scan` computes this `chunk` positions at a time in XLA operations:
within a chunk the scores C_i . B_j of a group, once for its heads, times
each head's decay from j to i under the causal mask (a [chunk, chunk]
matrix a head and chunk, a batched product with the chunk's inputs); a
chunk's own contribution to the state; then a `lax.scan` over the chunks
that carries S, and the read of each chunk's starting state. The
backward pass is autodiff's through the same operations (the scan over
the chunks runs in reverse); the caller bounds what is kept
(`models/seqrec` recomputes a layer in its backward pass under `remat`).
There is no Pallas kernel: this is the baseline one would start from.

Precision: the decays' logarithms, their cumulative sums within a chunk
and their exponentials are float32 elementwise (a decay compounds over a
session); the products take the backend's default (on the TPU one
bfloat16 pass, float32 accumulation).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: positions a chunk where the caller names none (the published
#: `chunk_size` of the family this was written for)
CHUNK = 128


def scan(x: jax.Array, dt: jax.Array, rate: jax.Array, b: jax.Array,
         c: jax.Array, skip: jax.Array, chunk: int = CHUNK) -> jax.Array:
    """x [B, L, H, P] (a head's input), dt [B, L, H] (the step, >= 0: at
    0 a position neither decays the state nor writes into it, which is
    what padding is given), rate [H] (> 0: exp(A_log)), b, c [B, L, G, N]
    (G divides H; head h reads group h // (H / G)), skip [H] (D) -> y
    [B, L, H, P] float32, from a state of 0 at position 0 of every row.
    Any length: it is filled up to whole chunks with positions of dt 0."""
    bsz, l, h, p = x.shape
    g, n = b.shape[2:]
    r = h // g
    pad = -l % chunk
    if pad:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, b, c))
    nc = (l + pad) // chunk
    f32 = jnp.float32

    def heads(t):       # [B, L, H, ...] -> [B, nc, G, R, chunk, ...]
        t = t.astype(f32).reshape(bsz, nc, chunk, g, r, *t.shape[3:])
        return jnp.moveaxis(t, 2, 4)

    def groups(t):      # [B, L, G, N] -> [B, nc, G, chunk, N]
        return jnp.moveaxis(t.astype(f32).reshape(bsz, nc, chunk, g, n), 2, 3)

    xs, dts, bs, cs = heads(x), heads(dt), groups(b), groups(c)
    # the decay's logarithm since the chunk's start, this position's in
    cum = jnp.cumsum(-rate.astype(f32).reshape(g, r, 1) * dts, axis=-1)
    at = jnp.arange(chunk)
    lower = at[:, None] >= at[None, :]
    # exp(cum_i - cum_j) for j <= i, 0 above the diagonal (whose
    # exponents are positive and may overflow)
    decay = jnp.where(lower, jnp.exp(jnp.where(
        lower, cum[..., :, None] - cum[..., None, :], 0.0)), 0.0)
    scores = jnp.einsum("zcgin,zcgjn->zcgij", cs, bs)       # a group's, once
    y = jnp.einsum("zcgrij,zcgrjp->zcgrip",
                   scores[:, :, :, None] * decay * dts[..., None, :], xs)
    # what a chunk adds to the state by its end, and the state's own decay
    last = cum[..., -1:]
    added = jnp.einsum("zcgrjp,zcgjn->zcgrpn",
                       xs * (jnp.exp(last - cum) * dts)[..., None], bs)
    keep = jnp.exp(last)[..., None]                         # [..., 1, 1]

    def one_chunk(s, chunk_of):
        added_c, keep_c = chunk_of
        return s * keep_c + added_c, s

    _, starts = jax.lax.scan(
        one_chunk, jnp.zeros((bsz, g, r, p, n), f32),
        (jnp.moveaxis(added, 1, 0), jnp.moveaxis(keep, 1, 0)))
    y = y + jnp.einsum("czgrpn,zcgin->zcgrip", starts, cs) \
        * jnp.exp(cum)[..., None]
    y = y + skip.astype(f32).reshape(g, r, 1, 1) * xs
    return jnp.moveaxis(y, 4, 2).reshape(bsz, l + pad, h, p)[:, :l]


def recurrence(x: jax.Array, dt: jax.Array, rate: jax.Array, b: jax.Array,
               c: jax.Array, skip: jax.Array) -> jax.Array:
    """The same, position by position as the equations are written (a
    `lax.scan` over L that carries every head's state): what `scan` is
    held to in the tests, and slow."""
    h, g = x.shape[2], b.shape[2]
    of = jnp.arange(h) // (h // g)          # a head's group

    def position(s, at):
        x_t, dt_t, b_t, c_t = at            # [B, H, P], [B, H], [B, G, N] x 2
        a_t = jnp.exp(-rate * dt_t)
        s = a_t[..., None, None] * s + (dt_t[..., None] * x_t)[..., None] \
            * b_t[:, of][:, :, None, :]
        return s, (s * c_t[:, of][:, :, None, :]).sum(-1) \
            + skip[:, None] * x_t

    s0 = jnp.zeros((x.shape[0], h, x.shape[3], b.shape[3]), jnp.float32)
    _, y = jax.lax.scan(position, s0, tuple(
        jnp.moveaxis(t.astype(jnp.float32), 1, 0) for t in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)
