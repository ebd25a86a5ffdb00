"""Batched dense linear algebra for the MXU/VPU.

Batched positive-definite solves: the per-segment normal equations of ALS
([S, K, K] @ x = [S, K]) — the direct-solve step MLlib ALS performs per
user/item block inside `ALS.run` (invoked by the reference templates at
examples/.../ALSAlgorithm.scala:85). K is small (the factor rank, 10-128)
and S is huge (one system per user/item), a shape XLA's LAPACK-style
`cho_factor` handles poorly on TPU: it loops over K with batched
dynamic-slice updates that round-trip HBM every step.

Three implementations, fastest selected automatically:

- ``cholesky_solve_xla``    — jax.scipy cho_factor/cho_solve (reference).
- ``cholesky_solve_vec``    — K-step right-looking Cholesky hand-vectorized
  over the batch: every step is one fused VPU pass over [S, K, K]. ~27x
  faster than cho_solve at ML-20M shape (S=140k, K=10) on v5e.
- ``cholesky_solve_pallas`` — Pallas TPU kernel; each batch tile of 128
  systems lives in VMEM for the whole factorization in a batch-in-lanes
  [K, K, T] layout (batch dim = vector lanes), so the K-step recurrence
  never touches HBM. The layout is not expressible through XLA's batched
  linalg, which is the point of hand-writing it.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.utils.device import on_tpu

#: ranks up to this use the Pallas kernel on TPU ([K,K,128] tiles stay
#: well under VMEM and the unrolled program stays small)
_PALLAS_MAX_K = 64
_PALLAS_TILE = 128


# ---------------------------------------------------------------------------
# XLA reference path
# ---------------------------------------------------------------------------

@jax.jit
def cholesky_solve_xla(A: jax.Array, b: jax.Array) -> jax.Array:
    """Solve SPD A[s] x = b[s] via jax.scipy (the XLA-library path)."""
    chol, lower = jax.scipy.linalg.cho_factor(A)
    return jax.scipy.linalg.cho_solve((chol, lower), b)


# ---------------------------------------------------------------------------
# Batch-vectorized path (pure JAX)
# ---------------------------------------------------------------------------

def _vec_cholesky(A: jax.Array) -> jax.Array:
    """Right-looking Cholesky, one fused batch-wide update per column."""
    k = A.shape[-1]
    rows = jnp.arange(k)

    def body(j, L):
        d = jax.lax.rsqrt(jnp.maximum(L[:, j, j], 1e-30))       # [S]
        col = L[:, :, j] * d[:, None]                           # [S, K]
        col = jnp.where((rows >= j)[None, :], col, 0.0)
        upd = col[:, :, None] * col[:, None, :]                 # [S, K, K]
        L = L - jnp.where((rows > j)[None, None, :], upd, 0.0)
        return L.at[:, :, j].set(col)

    return jax.lax.fori_loop(0, k, body, A)


def _vec_solve_tri(L: jax.Array, b: jax.Array) -> jax.Array:
    """x = (L L^T)^{-1} b by forward+backward substitution over columns."""
    k = b.shape[-1]

    def fwd(j, y):
        yj = (b[:, j] - jnp.einsum("sk,sk->s", L[:, j, :], y)) / L[:, j, j]
        return y.at[:, j].set(yj)

    y = jax.lax.fori_loop(0, k, fwd, jnp.zeros_like(b))

    def bwd(i, x):
        j = k - 1 - i
        xj = (y[:, j] - jnp.einsum("sk,sk->s", L[:, :, j], x)) / L[:, j, j]
        return x.at[:, j].set(xj)

    return jax.lax.fori_loop(0, k, bwd, jnp.zeros_like(b))


@jax.jit
def cholesky_solve_vec(A: jax.Array, b: jax.Array) -> jax.Array:
    """Solve SPD A[s] x = b[s], vectorized over the batch dimension."""
    L = _vec_cholesky(A)
    return _vec_solve_tri(L, b)


# ---------------------------------------------------------------------------
# Pallas TPU kernel
# ---------------------------------------------------------------------------

def _spd_solve_kernel(a_ref, b_ref, x_ref):
    """One batch tile: factorize + solve T systems entirely in VMEM.

    Layout: [K, K, T] / [K, T] — the batch dim maps to vector lanes, so
    every step of the K-recurrence is a full-width VPU op and no lane sits
    idle on the K x K structure.
    """
    k = a_ref.shape[1]
    A = jnp.transpose(a_ref[...], (1, 2, 0))      # [K, K, T]
    rhs = jnp.transpose(b_ref[...], (1, 0))       # [K, T]
    row1 = jax.lax.broadcasted_iota(jnp.int32, (k, 1), 0)       # [K, 1]
    row3 = jax.lax.broadcasted_iota(jnp.int32, (k, 1, 1), 0)    # [K, 1, 1]
    col3 = jax.lax.broadcasted_iota(jnp.int32, (1, k, 1), 1)    # [1, K, 1]

    # unrolled right-looking Cholesky; j is static so the masks are iota
    # compares. All column extraction is done as masked full-array
    # reductions — Mosaic has no scatter lowering and rejects sublane
    # reductions over offset-layout slices, so no A[:, j, :]-style slicing.
    for j in range(k):
        diag = jnp.sum(jnp.where((row3 == j) & (col3 == j), A, 0.0),
                       axis=(0, 1))                             # [T]
        d = jax.lax.rsqrt(jnp.maximum(diag, 1e-30))
        col = jnp.sum(jnp.where(col3 == j, A, 0.0), axis=1)     # [K, T]
        col = jnp.where(row1 >= j, col * d[None, :], 0.0)
        outer = col[:, None, :] * col[None, :, :]               # [K, K, T]
        A = jnp.where(col3 > j, A - outer, A)
        A = jnp.where(col3 == j, col[:, None, :], A)

    L = jnp.where(row3 >= col3, A, 0.0)
    Ld = jnp.sum(jnp.where(row3 == col3, A, 0.0), axis=1)       # [K, T] diag

    # forward substitution L y = rhs: each step recomputes every row's dot
    # product (full-width VPU op); only row j's result is committed, and
    # rows > j see zeros for the not-yet-solved entries.
    y = jnp.zeros_like(rhs)
    for j in range(k):
        acc = jnp.sum(L * y[None, :, :], axis=1)                # [K, T]
        y = jnp.where(row1 == j, (rhs - acc) / Ld, y)

    # backward substitution L^T x = y (row j of L^T = column j of L)
    x = jnp.zeros_like(rhs)
    for j in range(k - 1, -1, -1):
        acc = jnp.sum(L * x[:, None, :], axis=0)                # [K, T]
        x = jnp.where(row1 == j, (y - acc) / Ld, x)

    x_ref[...] = jnp.transpose(x, (1, 0))                       # [T, K]


@functools.partial(jax.jit, static_argnames=("interpret",))
def cholesky_solve_pallas(A: jax.Array, b: jax.Array,
                          interpret: bool = False) -> jax.Array:
    """Solve SPD A[s] x = b[s] with the VMEM-resident Pallas kernel."""
    from jax.experimental import pallas as pl

    s, k, _ = A.shape
    t = _PALLAS_TILE
    s_pad = max(t, ((s + t - 1) // t) * t)
    if s_pad != s:
        # pad with identity systems (x = 0 for b = 0)
        eye = jnp.broadcast_to(jnp.eye(k, dtype=A.dtype), (s_pad - s, k, k))
        A = jnp.concatenate([A, eye], axis=0)
        b = jnp.concatenate([b, jnp.zeros((s_pad - s, k), b.dtype)], axis=0)

    out = pl.pallas_call(
        _spd_solve_kernel,
        out_shape=jax.ShapeDtypeStruct((s_pad, k), A.dtype),
        grid=(s_pad // t,),
        in_specs=[
            pl.BlockSpec((t, k, k), lambda i: (i, 0, 0)),
            pl.BlockSpec((t, k), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((t, k), lambda i: (i, 0)),
        interpret=interpret,
    )(A, b)
    return out[:s]


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------

def batched_spd_solve(A: jax.Array, b: jax.Array,
                      jitter: float = 1e-6) -> jax.Array:
    """Solve A[s] x[s] = b[s] for SPD A, [S, K, K] x [S, K] -> [S, K].

    A small diagonal jitter keeps empty segments (A ~ 0) from producing
    NaNs; their rhs is 0 so the solution stays 0. Method selection:
    ``PIO_TPU_SOLVE`` env var (``pallas`` | ``vec`` | ``xla``) overrides
    (``pallas`` raises off-TPU); default is the Pallas kernel on TPU for
    K <= 64, else the vectorized JAX path. The choice is made from the
    backend and the shape, never by catching a compile error.
    """
    k = A.shape[-1]
    A = A + jitter * jnp.eye(k, dtype=A.dtype)
    method = os.environ.get("PIO_TPU_SOLVE", "auto").strip().lower()
    if method not in ("auto", "xla", "vec", "pallas"):
        raise ValueError(
            f"PIO_TPU_SOLVE={method!r}: expected auto|xla|vec|pallas")
    if method == "xla":
        return cholesky_solve_xla(A, b)
    if method == "vec":
        return cholesky_solve_vec(A, b)
    if method == "pallas":
        if not on_tpu():
            raise RuntimeError(
                "PIO_TPU_SOLVE=pallas needs a TPU backend, found "
                f"{jax.default_backend()!r} (the kernel's interpret mode "
                "is for tests, which call cholesky_solve_pallas directly)")
        return cholesky_solve_pallas(A, b)
    if k <= _PALLAS_MAX_K and on_tpu():
        return cholesky_solve_pallas(A, b)
    return cholesky_solve_vec(A, b)
