"""Problem generators matching the paper's experiments (section 6).

* Spatial-statistics covariance matrices: isotropic exponential kernel
  ``exp(-r / ell)`` with correlation lengths 0.1 (2D) and 0.2 (3D), points on
  a uniform grid or random in a ball.
* Fractional-diffusion-type operator: integral-equation discretization of a
  Riesz-potential kernel ``c / r^{d - 2s}`` (SPD for 0 < s < d/2), singular
  diagonal replaced by a self-interaction term scaled to the mesh width.
  Like the paper's matrix it is SPD but severely ill-conditioned, which is
  what exercises Schur compensation and the preconditioned-CG experiments.
"""

from __future__ import annotations

import numpy as np


# -- point clouds ------------------------------------------------------------


def grid_points(n: int, d: int) -> np.ndarray:
    """~n points on a uniform grid in [0,1]^d (exactly m^d for m=ceil(n^(1/d)))."""
    m = int(round(n ** (1.0 / d)))
    while m**d < n:
        m += 1
    axes = [np.linspace(0.0, 1.0, m) for _ in range(d)]
    pts = np.stack(np.meshgrid(*axes, indexing="ij"), axis=-1).reshape(-1, d)
    return pts[:n]


def ball_points(n: int, d: int, seed: int = 0) -> np.ndarray:
    """n points uniformly distributed in the unit d-ball."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, d))
    x /= np.linalg.norm(x, axis=1, keepdims=True)
    r = rng.random(n) ** (1.0 / d)
    return x * r[:, None]


# -- kernels -----------------------------------------------------------------


def pairwise_dist(points: np.ndarray) -> np.ndarray:
    g = points @ points.T
    sq = np.diag(g)
    d2 = np.maximum(sq[:, None] + sq[None, :] - 2 * g, 0.0)
    return np.sqrt(d2)


def exp_covariance(
    points: np.ndarray, ell: float, nugget: float = 1e-8
) -> np.ndarray:
    """Isotropic exponential covariance  K = exp(-r/ell) + nugget*I  (SPD)."""
    r = pairwise_dist(points)
    K = np.exp(-r / ell)
    K[np.diag_indices_from(K)] += nugget
    return K


def matern32_covariance(
    points: np.ndarray, ell: float, nugget: float = 1e-8
) -> np.ndarray:
    """Matern nu=3/2 covariance (smoother spectrum than exponential)."""
    r = pairwise_dist(points) * (np.sqrt(3.0) / ell)
    K = (1.0 + r) * np.exp(-r)
    K[np.diag_indices_from(K)] += nugget
    return K


def fractional_diffusion(
    points: np.ndarray, s: float = 0.75, mass: float = 1e-3
) -> np.ndarray:
    """SPD, ill-conditioned fractional-Laplacian collocation matrix.

    Singular-integral form of (-Delta)^s (the paper's [12] integral
    formulation):  (-Delta)^s u(x) = c \\int (u(x)-u(y)) / |x-y|^{d+2s} dy.
    Collocation with double quadrature weight h^{2d} gives the symmetric
    diagonally-dominant matrix

        A_ij = -h^{2d} / r_ij^{d+2s}   (i != j),
        A_ii =  sum_{j!=i} h^{2d}/r_ij^{d+2s} + mass * h^d,

    which is SPD (Gershgorin) with condition number ~ h^{-2s} / mass --
    severely ill-conditioned as n grows, matching the paper's kappa ~ 1e7
    regime for N = 2^17. Off-diagonal *tiles* inherit the low-rank structure
    of the smooth far-field kernel.
    """
    n, d = points.shape
    if not 0.0 < s < 1.0:
        raise ValueError(f"need 0 < s < 1, got s={s}")
    r = pairwise_dist(points)
    h = 1.0 / max(n ** (1.0 / d) - 1.0, 1.0)
    alpha = d + 2 * s
    with np.errstate(divide="ignore"):
        W = (h ** (2 * d)) / np.maximum(r, 1e-300) ** alpha
    np.fill_diagonal(W, 0.0)
    A = -W
    np.fill_diagonal(A, W.sum(axis=1) + mass * h**d)
    return 0.5 * (A + A.T)


# -- assembled problems ------------------------------------------------------


def covariance_points(n: int, d: int, tile_size: int, *,
                      geometry: str = "grid", seed: int = 0) -> np.ndarray:
    """The section 6.1 point cloud, KD-tree ordered into tiles."""
    from .ordering import kd_tree_ordering

    pts = grid_points(n, d) if geometry == "grid" else ball_points(n, d, seed)
    pts = pts[:n]
    return pts[kd_tree_ordering(pts, tile_size)]


def exp_covariance_device(points, ell: float, nugget: float = 1e-8, *,
                          dtype=None, rows: int = 512):
    """:func:`exp_covariance` evaluated on the default device, ``rows``
    rows at a time, so no host array or (n, n, d) intermediate is ever
    formed. Distances are taken from coordinate differences (exact zeros
    on the diagonal), not from the Gram identity, whose cancellation at
    f32 would perturb the diagonal by ~1e-3."""
    import jax
    import jax.numpy as jnp

    dtype = dtype or jnp.result_type(float)
    n, d = points.shape
    rows = min(rows, n)
    if n % rows:
        raise ValueError(f"n={n} must be a multiple of rows={rows}")

    @jax.jit
    def build(P):
        def block(i):
            Pi = jax.lax.dynamic_slice_in_dim(P, i * rows, rows)
            diff = Pi[:, None, :] - P[None, :, :]
            r = jnp.sqrt(jnp.sum(diff * diff, axis=-1))
            K = jnp.exp(-r / ell)
            eye = (jnp.arange(rows)[:, None] + i * rows
                   == jnp.arange(n)[None, :])
            return K + nugget * eye.astype(dtype)

        return jax.lax.map(block, jnp.arange(n // rows)).reshape(n, n)

    return build(jnp.asarray(points, dtype))


def _two_sum(a, b):
    """Knuth's error-free sum: ``s + e == a + b`` exactly, ``s = fl(a + b)``."""
    s = a + b
    bb = s - a
    return s, (a - (s - bb)) + (b - bb)


def compensated_row_sum(X):
    """Row sums of ``X`` (m, n) as ``(hi, lo)``, ``hi + lo`` within about
    one rounding of the exact sum: a pairwise tree whose every addition
    keeps its rounding error (:func:`_two_sum`), the errors summed
    alongside into ``lo``. A plain f32 sum of n terms errs by up to
    ~log2(n) roundings."""
    import jax.numpy as jnp

    w = 1 << max(0, (X.shape[1] - 1).bit_length())
    X = jnp.pad(X, ((0, 0), (0, w - X.shape[1])))
    err = jnp.zeros_like(X)
    while w > 1:
        w //= 2
        X, e = _two_sum(X[:, :w], X[:, w:])
        err = err[:, :w] + err[:, w:] + e
    return X[:, 0], err[:, 0]


def fractional_diffusion_device(points, s: float = 0.75, mass: float = 1e-3,
                                *, dtype=None, rows: int = 512,
                                normalize: bool = False):
    """:func:`fractional_diffusion` evaluated on the default device,
    ``rows`` rows at a time (no host array, no (n, n, d) intermediate).

    The off-diagonals ``-h^{2d} / r^{d+2s}`` come from coordinate
    differences in ``dtype``. The SPD margin ``mass h^d`` is a few f32
    roundings of the diagonal at N=32768 (5.5e-7 of it), and the constant
    vector is the near-null mode, so each diagonal entry is the
    compensated sum (:func:`compensated_row_sum`) of its row's *rounded*
    off-diagonals plus ``mass h^d``: Gershgorin then holds on the stored
    matrix, with that margin.

    ``normalize=True`` divides by the largest diagonal (a first pass
    computes the diagonal alone), so the diagonal is at most 1 and an
    absolute tolerance means what it means for a unit-diagonal
    covariance; the off-diagonals are rounded after the scaling and the
    diagonal summed from them, so the margin holds on the scaled matrix.
    """
    import jax
    import jax.numpy as jnp

    dtype = dtype or jnp.result_type(float)
    n, d = points.shape
    if not 0.0 < s < 1.0:
        raise ValueError(f"need 0 < s < 1, got s={s}")
    rows = min(rows, n)
    if n % rows:
        raise ValueError(f"n={n} must be a multiple of rows={rows}")
    h = 1.0 / max(n ** (1.0 / d) - 1.0, 1.0)
    coef, margin, alpha = h ** (2 * d), mass * h ** d, d + 2 * s

    def block(P, i, scale):
        """Rows ``i * rows ..`` of the (scaled) matrix: where their diagonal
        entries sit, their off-diagonal magnitudes and their diagonal."""
        Pi = jax.lax.dynamic_slice_in_dim(P, i * rows, rows)
        diff = Pi[:, None, :] - P[None, :, :]
        r2 = jnp.sum(diff * diff, axis=-1)
        eye = jnp.arange(rows)[:, None] + i * rows == jnp.arange(n)[None, :]
        W = jnp.where(eye, 0.0, (coef * scale)
                      * jnp.where(eye, 1.0, r2) ** (-alpha / 2))
        hi, lo = compensated_row_sum(W)
        return eye, W, hi + (lo + margin * scale)     # one rounding

    @jax.jit
    def largest_diagonal(P):
        one = jnp.ones((), dtype)
        diag = jax.lax.map(lambda i: block(P, i, one)[2],
                           jnp.arange(n // rows))
        return jnp.max(diag)

    @jax.jit
    def build(P, scale):
        def rows_of(i):
            eye, W, diag = block(P, i, scale)
            return jnp.where(eye, diag[:, None], -W)

        return jax.lax.map(rows_of, jnp.arange(n // rows)).reshape(n, n)

    P = jnp.asarray(points, dtype)
    scale = (1.0 / largest_diagonal(P) if normalize
             else jnp.ones((), dtype)).astype(dtype)
    return build(P, scale)


def covariance_problem(
    n: int,
    d: int,
    tile_size: int,
    *,
    geometry: str = "grid",
    seed: int = 0,
    kernel: str = "exp",
):
    """Points (KD-tree ordered) + covariance matrix, paper's section 6.1 setup."""
    ell = 0.1 if d == 2 else 0.2
    pts = covariance_points(n, d, tile_size, geometry=geometry, seed=seed)
    if kernel == "exp":
        K = exp_covariance(pts, ell)
    elif kernel == "matern32":
        K = matern32_covariance(pts, ell)
    else:
        raise ValueError(kernel)
    return pts, K


def fractional_diffusion_problem(
    n: int, tile_size: int, *, s: float = 0.75, seed: int = 0
):
    """3D fractional-diffusion-type matrix, KD-tree ordered (section 6.2)."""
    from .ordering import kd_tree_ordering

    pts = grid_points(n, 3)[:n]
    perm = kd_tree_ordering(pts, tile_size)
    pts = pts[perm]
    return pts, fractional_diffusion(pts, s=s)
