"""The section 6.2 fractional-diffusion operator built on the device
(``fractional_diffusion_device``) against the host reference
``fractional_diffusion``, and the deployment it serves: compress, factor
at a loose eps, precondition PCG."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    CholOptions, TLROperator, fractional_diffusion,
    fractional_diffusion_device, grid_points, kd_tree_ordering, pcg,
)
from repro.core.generators import compensated_row_sum

F32 = np.finfo(np.float32).eps


def _points(n, tile):
    pts = grid_points(n, 3)
    return pts[kd_tree_ordering(pts, tile)]


@pytest.fixture(scope="module")
def grid512():
    return _points(512, 64)


def _margin(A, mass):
    """Each row's stored diagonal minus the exact sum of its stored
    off-diagonals' magnitudes (f64 over the f32 entries), and mass h^d."""
    n = A.shape[0]
    h = 1.0 / (n ** (1.0 / 3.0) - 1.0)
    A64 = np.asarray(A, np.float64)
    off = np.abs(A64).sum(axis=1) - np.abs(np.diagonal(A64))
    return np.diagonal(A64) - off, mass * h ** 3


def test_offdiagonals_match_the_host_reference(grid512):
    A = np.asarray(fractional_diffusion_device(grid512, dtype=jnp.float32,
                                               rows=64))
    ref = fractional_diffusion(grid512)
    assert A.dtype == np.float32
    off = ~np.eye(512, dtype=bool)
    # f32 coordinate differences and one f32 power: a few dozen roundings
    np.testing.assert_allclose(A[off], ref[off], rtol=32 * F32, atol=0)
    np.testing.assert_allclose(np.diagonal(A), np.diagonal(ref),
                               rtol=4 * F32)
    np.testing.assert_array_equal(A, A.T)


@pytest.mark.parametrize("normalize", [False, True])
def test_diagonal_keeps_the_spd_margin(grid512, normalize):
    mass = 1e-3
    A = np.asarray(fractional_diffusion_device(
        grid512, 0.75, mass, dtype=jnp.float32, rows=64,
        normalize=normalize))
    margin, mh = _margin(A, mass)
    if normalize:
        mh /= np.diagonal(fractional_diffusion(grid512, 0.75, mass)).max()
    assert margin.min() >= 0.9 * mh, (margin.min(), mh)
    assert np.linalg.eigvalsh(A.astype(np.float64)).min() > 0


def test_normalize_divides_by_the_largest_diagonal(grid512):
    A = np.asarray(fractional_diffusion_device(grid512, dtype=jnp.float32,
                                               rows=64))
    An = np.asarray(fractional_diffusion_device(grid512, dtype=jnp.float32,
                                                rows=64, normalize=True))
    assert np.diagonal(An).max() == 1.0
    np.testing.assert_allclose(An, A / np.diagonal(A).max(), rtol=4 * F32,
                               atol=0)


def test_compensated_row_sum_is_one_rounding():
    """32768 positive terms spread over eight decades, as a row of the
    N=32768 operator: the compensated sum lands within one rounding of
    the exact sum, where a plain f32 sum drifts further."""
    rng = np.random.default_rng(0)
    X = (10.0 ** rng.uniform(-10, -2, (4, 32768))).astype(np.float32)
    exact = X.astype(np.float64).sum(axis=1)
    with jax.enable_x64(False):
        hi, lo = jax.jit(compensated_row_sum)(jnp.asarray(X))
        total = np.asarray(hi + lo, np.float64)
    assert np.all(np.abs(total - exact) <= F32 * exact)
    assert np.max(np.abs(np.asarray(hi, np.float64) + np.asarray(lo)
                         - exact) / exact) < 1e-9
    plain = X.sum(axis=1, dtype=np.float32).astype(np.float64)
    assert np.max(np.abs(total - exact)) <= np.max(np.abs(plain - exact))


def test_generator_validates_its_arguments(grid512):
    with pytest.raises(ValueError):
        fractional_diffusion_device(grid512, s=1.5, rows=64)
    with pytest.raises(ValueError):
        fractional_diffusion_device(grid512, rows=100)


def test_factor_preconditions_pcg():
    """compress -> cholesky at eps 1e-2 -> pcg in f32, as the chip
    deployment runs it: a small backward error against the dense
    operator, in fewer iterations than unpreconditioned CG. (The tile
    ranks stay under r_max = tile / 2, as at the chip's size.)"""
    n, tile = 512, 64
    pts = _points(n, tile)
    with jax.enable_x64(False):
        A = fractional_diffusion_device(pts, dtype=jnp.float32, rows=tile,
                                        normalize=True)
        op = TLROperator.compress(A, tile, 32, 1e-4, method="ara", bs=16,
                                  key=jax.random.PRNGKey(2))
        fact = op.cholesky(CholOptions(eps=1e-2, bs=16, seed=3))
        y = jax.random.normal(jax.random.PRNGKey(5), (n,), jnp.float32)
        x, iters, hist = pcg(op, y, precond=fact, tol=1e-6, maxiter=300)
        _, plain_iters, _ = pcg(op, y, tol=1e-6, maxiter=300)
    assert hist.breakdown is None and hist[-1] < 1e-6
    assert iters < plain_iters, (iters, plain_iters)
    A64 = np.asarray(A, np.float64)
    x64, y64 = np.asarray(x, np.float64), np.asarray(y, np.float64)
    x_ref = np.linalg.solve(A64, y64)
    berr = np.linalg.norm(A64 @ x64 - y64) / (
        np.linalg.norm(A64, 2) * np.linalg.norm(x64) + np.linalg.norm(y64))
    assert berr < 1e-4, berr
    # the solution lies along the dense solve's (its near-null mode rules)
    cos = x64 @ x_ref / (np.linalg.norm(x64) * np.linalg.norm(x_ref))
    assert cos > 0.99, cos
