"""Linear attention by the gated delta rule (Gated DeltaNet,
arXiv:2412.06464), and the short causal convolution in front of it
(`causal_conv`), which a gated short-convolution mixer calls too: what
that mixer runs between its two projections is `gated_short_conv`, on a
route of its own (`gated_short_conv_route`: the fused passes of
ops/short_conv_pallas.py, or `causal_conv` between the two gates).

Each head keeps a state S [dk, dv] instead of keys and values. Position
t first lets the state decay, then replaces what the state holds under
its key by a share beta_t of its value, then reads with its query:

    S' = exp(g_t) S_{t-1}
    S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T
    o_t = S_t^T q_t

`gated_delta_rule` computes this `CHUNK` positions at a time (the WY
form of the paper's section 3.3): inside a chunk the positions' updates
are coupled by a unit lower triangular system over the chunk's keys;
between chunks one state update. Two implementations of the same
mathematics, picked by `gated_delta_rule_route` from the device's kind,
the widths and the devices the program is traced for:

* the Pallas kernels of ops/linear_attention_pallas.py (a v5e, a
  program for one device, widths in whole lane tiles): a head's state
  stays in VMEM across its chunks and a chunk's system, inverse and
  scores never reach HBM; a backward kernel of their own, which keeps
  the rule's inputs and each chunk's starting state and inverse. On
  this route a layer runs everything between its two projections as
  fused passes around the kernels (`gated_delta_chain`, whose route is
  the rule's);
* `_scan`, XLA operations everywhere else: the systems solved for all
  chunks at once, then a `lax.scan` over the chunks that carries S. Its
  backward pass is the scan's own, with the scan's body under
  `jax.checkpoint`, so that a chunk keeps the state it started from
  (B x H x dk x dv a chunk: 512 MiB a layer at 32 heads of 128 x 128 and
  256 chunks) and recomputes its four products; the caller bounds what
  else is kept (`gated_delta_chain` takes a layer's heads `group` key
  heads at a time, which `models/seqrec` asks for under `remat`).
  PERF.md section 6, PRs 31 and 32, has the readings behind the choices.

Precision: the system's matrix (beta k_i . k_j, decayed), its inverse
and the two products with the inverse are computed in float32 at the
highest matmul precision: each entry of the inverse is a sum over paths
through the chunk, and a bfloat16 rounding of its operands compounds
along a path. Every other product takes the backend's default (on the
TPU one bfloat16 pass, float32 accumulation); decays and their
exponentials are float32 elementwise.
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import Callable, Iterator, Optional, Set, Tuple

import jax
import jax.numpy as jnp

from predictionio_tpu.ops import (
    attention_pallas, linear_attention_pallas, short_conv_pallas,
)

#: positions a chunk, on either route. A constant, from the paper's
#: kernels (64) and chip runs of the scan at 1 x 32 heads x 16,384 x 128
#: (PERF.md section 6, PR 31): the rule alone took 24.8 ms forward and
#: 81.6 forward + backward at 64, 27.6 and 90.4 at 128 (half the scan's
#: steps, four times the triangular system); the cell's step 1.053 s
#: against 1.078.
CHUNK = 64

_HIGHEST = jax.lax.Precision.HIGHEST


def causal_conv(x: jax.Array, w: jax.Array,
                activation: Optional[Callable] = jax.nn.silu) -> jax.Array:
    """Depthwise causal convolution: x [B, L, C], w [K, C] ->
    activation(sum_j w[j] x[t - (K - 1) + j]) [B, L, C], zeros before
    the sequence, no bias. `activation`: SiLU in front of the delta
    rule, None (the taps' sum as it is) in a gated short convolution."""
    taps, l = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    out = sum(w[j] * padded[:, j:j + l] for j in range(taps))
    return out if activation is None else activation(out)


def gated_short_conv_route(device_kind: str, l: int, d: int, taps: int,
                           devices: int = 1) -> str:
    """Which implementation `gated_short_conv` runs for a session of `l`
    positions, `d` columns a part and `taps` taps on a device of this
    kind, in a program traced for `devices` devices: "pallas", the
    passes of ops/short_conv_pallas.py, on the TPUs the attention
    kernels are listed for, in a program for one device, where the
    passes tile the sizes (`short_conv_pallas.tiles`: whole lane tiles,
    whole row blocks, the taps within a halo); "xla" everywhere else."""
    if (device_kind in attention_pallas.KINDS and devices == 1
            and short_conv_pallas.tiles(l, d, taps)):
        return "pallas"
    return "xla"


def gated_short_conv(bcu: jax.Array, taps: jax.Array, devices: int = 1,
                     grad_dtype=None) -> jax.Array:
    """A gated short convolution between its two projections: bcu
    [B, L, 3D] (the input projection's output, columns [b | c | u]),
    taps [K, D] -> c * conv(b * u) [B, L, D], the output projection's
    input; the convolution is `causal_conv`'s without activation. The
    route is `gated_short_conv_route`'s, and whoever listens hears it
    (`routes_into`'s second set): "pallas", two fused passes over the
    projection's columns where they lie with a backward pass of their
    own; "xla", the plain chain, XLA's to fuse. `grad_dtype`: the type
    the passes round bcu's gradient to where they write it (None:
    float32; XLA's chain decides for itself what its consumers read)."""
    route = gated_short_conv_route(_device_kind(), bcu.shape[1],
                                   bcu.shape[2] // 3, taps.shape[0], devices)
    _heard(route, short_conv=True)
    if route == "pallas":
        return short_conv_pallas.gated_short_conv_pallas(
            bcu, taps, grad_dtype=grad_dtype)
    b, c, u = jnp.split(bcu, 3, axis=-1)
    return c * causal_conv(b * u, taps, activation=None)


def _unit_lower_inverse(a: jax.Array) -> jax.Array:
    """(I + a)^-1 for strictly lower triangular a [..., C, C], C a power
    of two: a is nilpotent, so the inverse is sum_m (-a)^m =
    (I - a)(I + a^2)(I + a^4)..., log2(C) - 1 squarings."""
    c = a.shape[-1]
    x = -a
    t = jnp.eye(c, dtype=a.dtype) + x
    for _ in range(c.bit_length() - 2):
        x = jnp.matmul(x, x, precision=_HIGHEST)
        t = t + jnp.matmul(t, x, precision=_HIGHEST)
    return t


def _scan(q, k, v, g, beta):
    """The rule over whole chunks [B, L, H, ...] -> o [B, L, H, dv]
    float32: the chunks' systems solved for all chunks at once, then a
    `lax.scan` over the chunks that carries S, its body recomputed in
    the backward pass."""
    b, l, h, _ = q.shape
    n = l // CHUNK

    def chunks(t):          # [B, L, H, ...] -> [N, B, H, C, ...]
        t = t.astype(jnp.float32).reshape(b, n, CHUNK, h, *t.shape[3:])
        return jnp.moveaxis(t, (1, 3), (0, 2))

    q, k, v, g, beta = map(chunks, (q, k, v, g, beta))
    gc = jnp.cumsum(g, axis=-1)                  # decay since chunk start
    at = jnp.arange(CHUNK)
    lower = at[:, None] >= at[None, :]
    # exp(gc_i - gc_j) for j <= i, 0 above the diagonal (whose
    # exponents are positive and may overflow)
    decay = jnp.where(lower, jnp.exp(jnp.where(
        lower, gc[..., :, None] - gc[..., None, :], 0.0)), 0.0)
    k_beta = k * beta[..., None]
    a = jnp.einsum("...id,...jd->...ij", k_beta, k, precision=_HIGHEST) \
        * jnp.where(at[:, None] > at[None, :], decay, 0.0)
    t = _unit_lower_inverse(a)
    u = jnp.matmul(t, v * beta[..., None], precision=_HIGHEST)
    w = jnp.matmul(t, k_beta * jnp.exp(gc)[..., None], precision=_HIGHEST)
    qk = jnp.einsum("...id,...jd->...ij", q, k) * decay
    last = gc[..., -1:]
    xs = (u, w, qk, q * jnp.exp(gc)[..., None],
          k * jnp.exp(last - gc)[..., None], jnp.exp(last)[..., None])

    def chunk(s, x):
        u_i, w_i, qk_i, q_i, k_i, decay_i = x
        v_new = u_i - w_i @ s                    # [B, H, C, dv]
        o_i = q_i @ s + qk_i @ v_new
        return s * decay_i + jnp.swapaxes(k_i, -1, -2) @ v_new, o_i

    _, o = jax.lax.scan(
        jax.checkpoint(chunk),
        jnp.zeros((b, h, k.shape[-1], v.shape[-1]), jnp.float32), xs)
    return jnp.moveaxis(o, (0, 2), (1, 3)).reshape(b, l, h, -1)


def gated_delta_rule_route(device_kind: str, dk: int, dv: int,
                           devices: int = 1) -> str:
    """Which implementation `gated_delta_rule` runs for these sizes on a
    device of this kind (`jax.Device.device_kind`), in a program traced
    for `devices` devices: "pallas", the kernels of
    ops/linear_attention_pallas.py, on the TPUs the attention kernels
    are listed for (`attention_pallas.KINDS`), for widths they tile (any
    length: `gated_delta_rule` pads it to whole chunks), in a program
    for one device (the compiler partitions no Mosaic kernel); "xla",
    the scan over the chunks, everywhere else."""
    if (device_kind in attention_pallas.KINDS and devices == 1
            and linear_attention_pallas.tiles(dk, dv)):
        return "pallas"
    return "xla"


def _device_kind() -> str:
    return jax.devices()[0].device_kind


def route_here(dk: int, dv: int, devices: int = 1) -> str:
    """`gated_delta_rule_route` on this process's device."""
    return gated_delta_rule_route(_device_kind(), dk, dv, devices)


_ROUTES: contextvars.ContextVar[
    Optional[Tuple[Set[str], Optional[Set[str]]]]] = contextvars.ContextVar(
    "gated_delta_rule_routes", default=None)


@contextlib.contextmanager
def routes_into(routes: Set[str],
                short_conv: Optional[Set[str]] = None) -> Iterator[None]:
    """While the block runs (a trace), every `gated_delta_rule` and
    `gated_delta_chain` call adds the route it took to `routes`, every
    `gated_short_conv` call its own to `short_conv`."""
    token = _ROUTES.set((routes, short_conv))
    try:
        yield
    finally:
        _ROUTES.reset(token)


def _heard(route: str, short_conv: bool = False) -> None:
    heard = _ROUTES.get()
    if heard is not None and heard[short_conv] is not None:
        heard[short_conv].add(route)


def gated_delta_chain(qkvz: jax.Array, taps: jax.Array, g: jax.Array,
                      beta: jax.Array, scale: jax.Array, heads, eps: float,
                      devices: int = 1,
                      group: Optional[int] = None) -> jax.Array:
    """A linear-attention layer between its two projections: qkvz
    [B, L, ...] (the input projection's output, columns [q | k | v | z]:
    Hk x dk, Hk x dk, Hv x dv, Hv x dv), taps [K, q, k and v's columns]
    (`causal_conv`'s), g, beta [B, L, Hv], scale [dv], heads (Hk, Hv, dk,
    dv) -> [B, L, Hv x dv], the output projection's input: the gated
    delta rule on SiLU(conv(q, k, v)), q and k of unit length a head and
    q scaled by dk ** -0.5, its output normed a head (RMS, `eps`,
    `scale`) times SiLU(z). The route is the rule's
    (`gated_delta_rule_route`), and whoever listens hears it:

    * "pallas": `linear_attention_pallas.gated_delta_chain_pallas`, all
      heads at once as fused passes around the rule's kernels with a
      backward pass of their own, which keeps what the forward pass it
      follows made and recomputes nothing (`group` is not read);
    * "xla": the plain chain, XLA's to fuse, around `gated_delta_rule`'s
      scan. `group` key heads (a divisor of Hk) and the value heads they
      serve are taken at a time, each group's internals recomputed in the
      backward pass; None: all at once, nothing recomputed here."""
    hk, hv, dk, dv = heads
    route = route_here(dk, dv, devices)
    _heard(route)
    if route == "pallas":
        return linear_attention_pallas.gated_delta_chain_pallas(
            qkvz, taps, g, beta, scale, tuple(heads), eps, CHUNK)
    b, l, _ = qkvz.shape
    cuts = [hk * dk, 2 * hk * dk, 2 * hk * dk + hv * dv]
    q, k, v, z = jnp.split(qkvz, cuts, axis=-1)
    conv_q, conv_k, conv_v = jnp.split(taps, cuts[:2], axis=-1)
    n = hk // group if group else 1

    def groups(t):          # [..., heads x width] -> [n, ..., heads / n x width]
        return jnp.moveaxis(t.reshape(*t.shape[:-1], n, -1), -2, 0)

    def unit(t):            # [B, L, heads x dk] -> unit length, a key head
        t = t.reshape(b, l, -1, dk)
        return t * jax.lax.rsqrt((t * t).sum(-1, keepdims=True) + 1e-6)

    def one(args):
        q, k, v, z, conv_q, conv_k, conv_v, g, beta = args
        q, k, v = (causal_conv(t, w) for t, w in
                   ((q, conv_q), (k, conv_k), (v, conv_v)))
        o = gated_delta_rule(unit(q) * dk ** -0.5, unit(k),
                             v.reshape(b, l, -1, dv), g, beta, devices=devices)
        o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + eps) * scale
        return (o * jax.nn.silu(z.reshape(b, l, -1, dv))).reshape(b, l, -1)

    args = tuple(map(groups, (q, k, v, z, conv_q, conv_k, conv_v, g, beta)))
    if n > 1:
        o = jax.lax.map(jax.checkpoint(one), args)
    else:
        o = one(tuple(t[0] for t in args))[None]
    return jnp.moveaxis(o, 0, 2).reshape(b, l, -1)


def gated_delta_rule(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                     beta: jax.Array, devices: int = 1) -> jax.Array:
    """q, k [B, L, Hk, dk] (normalised and scaled by the caller; Hk key
    heads, each serving H / Hk value heads in a row), v [B, L, H, dv], g
    [B, L, H] (the decay's logarithm, <= 0), beta [B, L, H] -> o
    [B, L, H, dv], from a state of 0. Any length: it is filled up with
    positions that write nothing (k = 0). The kernels read a key head
    where it lies; the scan takes q and k repeated to the value heads.
    `gated_delta_rule_route` says from the device's kind, the sizes and
    `devices` (how many devices the calling program is traced for: a
    mesh's size) whether the chunks are taken by Pallas kernels or by
    XLA operations and a scan."""
    l = q.shape[1]
    route = route_here(q.shape[-1], v.shape[-1], devices)
    _heard(route)
    # whole chunks; for the kernels, whole grid steps
    pad = -l % (CHUNK * (linear_attention_pallas.CHUNKS[-1]
                         if route == "pallas" else 1))
    if pad:
        q, k, v, g, beta = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, g, beta))
    if route == "pallas":
        o = linear_attention_pallas.gated_delta_rule_pallas(q, k, v, g, beta,
                                                            CHUNK)
    else:
        group = v.shape[2] // q.shape[2]
        o = _scan(*(jnp.repeat(t, group, axis=2) if group > 1 else t
                    for t in (q, k)), v, g, beta)
    return o[:, :l].astype(v.dtype)
