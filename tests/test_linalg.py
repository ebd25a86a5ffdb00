"""Batched SPD solve: all three implementations agree with numpy.

The solve is the per-segment normal-equation step of ALS (the direct solve
MLlib performs inside ALS.run, examples/.../ALSAlgorithm.scala:85); the
Pallas kernel runs in interpreter mode here (no TPU in CI).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from predictionio_tpu.ops.linalg import (
    batched_spd_solve,
    cholesky_solve_pallas,
    cholesky_solve_vec,
    cholesky_solve_xla,
)


def _spd_problem(s, k, seed=0):
    rng = np.random.default_rng(seed)
    m = rng.normal(size=(s, k, k)).astype(np.float32)
    A = m @ m.transpose(0, 2, 1) + 2.0 * k * np.eye(k, dtype=np.float32)
    b = rng.normal(size=(s, k)).astype(np.float32)
    x_ref = np.linalg.solve(A, b[..., None])[..., 0]
    return jnp.asarray(A), jnp.asarray(b), x_ref


@pytest.mark.parametrize("s,k", [(1, 3), (7, 10), (64, 10), (129, 16), (40, 32)])
def test_vec_matches_numpy(s, k):
    A, b, x_ref = _spd_problem(s, k)
    np.testing.assert_allclose(cholesky_solve_vec(A, b), x_ref,
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("s,k", [(7, 10), (64, 10)])
def test_xla_matches_numpy(s, k):
    A, b, x_ref = _spd_problem(s, k)
    np.testing.assert_allclose(cholesky_solve_xla(A, b), x_ref,
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("s,k", [(1, 4), (130, 10), (128, 16), (300, 32)])
def test_pallas_interpret_matches_numpy(s, k):
    """Pallas kernel (interpret mode) incl. non-tile-multiple batch sizes."""
    A, b, x_ref = _spd_problem(s, k, seed=1)
    out = cholesky_solve_pallas(A, b, interpret=True)
    assert out.shape == (s, k)
    np.testing.assert_allclose(out, x_ref, rtol=2e-4, atol=2e-4)


def test_dispatch_empty_segments_stay_zero():
    """Empty ALS segments (A ~ 0, b = 0) must solve to exactly-usable 0."""
    A = jnp.zeros((5, 8, 8), jnp.float32)
    b = jnp.zeros((5, 8), jnp.float32)
    out = np.asarray(batched_spd_solve(A, b))
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out, 0.0, atol=1e-5)


def test_dispatch_env_override(monkeypatch):
    A, b, x_ref = _spd_problem(33, 10, seed=2)
    for method in ("vec", "xla"):
        monkeypatch.setenv("PIO_TPU_SOLVE", method)
        np.testing.assert_allclose(batched_spd_solve(A, b), x_ref,
                                   rtol=2e-4, atol=2e-4)
    # the Pallas override never quietly runs the interpreter off-TPU
    monkeypatch.setenv("PIO_TPU_SOLVE", "pallas")
    with pytest.raises(RuntimeError, match="needs a TPU backend"):
        batched_spd_solve(A, b)
