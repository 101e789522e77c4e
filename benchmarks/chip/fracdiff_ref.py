"""The benchmark's own plain reference for the fractional-diffusion cells,
kept apart from the program under test (it imports nothing of it).

* :func:`points` -- the uniform grid in ``[0, 1]^3`` of the paper's
  section 6.2 problem, KD-tree ordered (``problem.kd_tree_order``).
* :func:`dense_operator` -- the fractional-Laplacian collocation matrix
  ``A_ij = -h^{2d} / r_ij^{d+2s}``, ``A_ii = sum_j |A_ij| + mass h^d``,
  optionally divided by its largest diagonal, built on the device in row
  blocks. Each diagonal entry is a Neumaier-compensated sum of its row's
  rounded off-diagonals, so the f32 matrix keeps its SPD margin
  ``mass h^d`` (a few roundings of the diagonal at N=32768).
* :func:`norm2` -- ``||A||_2`` by power iteration.
* :func:`lower_t_apply` -- ``L^T Z`` for a lower-triangular TLR factor,
  straight from its tiles (``problem.tlr_apply(lower=True)`` gives ``L Z``).
"""

from __future__ import annotations

import math

import numpy as np

import problem


def points(cfg: dict) -> np.ndarray:
    """The configuration's ``side^3`` grid points, KD-tree ordered into
    tiles. The grid does not depend on the seed."""
    side = round(cfg["n"] ** (1.0 / 3.0))
    if side ** 3 != cfg["n"]:
        raise ValueError(f"n={cfg['n']} is not a cube")
    axis = np.linspace(0.0, 1.0, side)
    pts = np.stack(np.meshgrid(axis, axis, axis, indexing="ij"),
                   axis=-1).reshape(-1, 3)
    return pts[problem.kd_tree_order(pts, cfg["tile"])]


def _neumaier_rows(W, lanes: int = 128):
    """Row sums of ``W`` (m, n) as ``(sum, compensation)``: Neumaier's
    compensated summation, run along the row in ``lanes`` interleaved
    streams that are then summed the same way."""
    import jax
    import jax.numpy as jnp

    m, n = W.shape
    W = jnp.pad(W, ((0, 0), (0, -n % lanes))).reshape(m, -1, lanes)

    def add(s, c, x):
        t = s + x
        c = c + jnp.where(jnp.abs(s) >= jnp.abs(x), (s - t) + x, (x - t) + s)
        return t, c

    def stream(k, carry):
        return add(*carry, jax.lax.dynamic_index_in_dim(W, k, 1, False))

    zero = jnp.zeros((m, lanes), W.dtype)
    s, c = jax.lax.fori_loop(0, W.shape[1], stream, (zero, zero))

    def lane(j, carry):
        t, cc = add(*carry, s[:, j])
        return t, cc + c[:, j]

    return jax.lax.fori_loop(0, lanes, lane,
                             (jnp.zeros((m,), W.dtype),) * 2)


def dense_operator(pts: np.ndarray, cfg: dict, rows: int = 512):
    """The configuration's operator (``s``, ``mass``, ``normalize``) in f32
    on the default device, ``rows`` rows per step. Distances come from
    coordinate differences; ``h = 1 / (n^{1/3} - 1)``, the grid spacing."""
    import jax
    import jax.numpy as jnp

    n, d = pts.shape
    rows = math.gcd(n, rows)
    s, mass = float(cfg["s"]), float(cfg["mass"])
    h = 1.0 / (n ** (1.0 / d) - 1.0)
    coef, margin, alpha = h ** (2 * d), mass * h ** d, d + 2.0 * s

    def rows_of(P, i, scale):
        """Rows ``i * rows ..``: where their diagonal sits, their
        off-diagonal magnitudes and their diagonal."""
        Pi = jax.lax.dynamic_slice_in_dim(P, i * rows, rows)
        diff = Pi[:, None, :] - P[None, :, :]
        r2 = jnp.sum(diff * diff, axis=-1)
        eye = jnp.arange(rows)[:, None] + i * rows == jnp.arange(n)[None, :]
        safe = jnp.where(eye, 1.0, r2)
        W = jnp.where(eye, 0.0, (coef * scale) / safe ** (alpha / 2))
        t, c = _neumaier_rows(W)
        return eye, W, t + (c + margin * scale)

    @jax.jit
    def largest_diagonal(P):
        one = jnp.ones((), jnp.float32)
        return jnp.max(jax.lax.map(lambda i: rows_of(P, i, one)[2],
                                   jnp.arange(n // rows)))

    @jax.jit
    def build(P, scale):
        def block(i):
            eye, W, diag = rows_of(P, i, scale)
            return jnp.where(eye, diag[:, None], -W)

        return jax.lax.map(block, jnp.arange(n // rows)).reshape(n, n)

    P = jnp.asarray(pts, jnp.float32)
    scale = (1.0 / largest_diagonal(P) if cfg.get("normalize")
             else jnp.ones((), jnp.float32))
    return build(P, scale)


def norm2(K, iters: int = 30) -> float:
    """``||K||_2`` of a symmetric matrix by power iteration from a fixed
    Gaussian vector (not the constant vector, this operator's near-null
    mode), f32 at HIGHEST."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def power(K):
        def step(_, x):
            y = K @ x
            return y / jnp.linalg.norm(y)

        x0 = jax.random.normal(jax.random.PRNGKey(0), (K.shape[0],), K.dtype)
        with jax.default_matmul_precision("highest"):
            x = jax.lax.fori_loop(0, iters, step, x0)
            return jnp.linalg.norm(K @ x)

    return float(power(K))


def lower_t_apply(D, U, V, ranks, Z):
    """``L^T Z`` for the lower-triangular TLR factor with diagonal tiles
    ``tril(D)`` and lower tiles ``U_t[:, :r_t] V_t[:, :r_t]^T`` (packed
    order of ``problem.tril_pairs``); f32 at HIGHEST."""
    import jax
    import jax.numpy as jnp

    nb, b = D.shape[0], D.shape[1]
    m = Z.shape[1]
    ii, jj = (jnp.asarray(a) for a in problem.tril_pairs(nb))
    keep = (jnp.arange(U.shape[2])[None, :]
            < jnp.asarray(ranks)[:, None]).astype(U.dtype)
    Um, Vm = U * keep[:, None, :], V * keep[:, None, :]
    Zt = Z.reshape(nb, b, m)
    with jax.default_matmul_precision("highest"):
        Y = jnp.einsum("icb,icm->ibm", jnp.tril(D), Zt)
        W = jnp.einsum("tbr,tbm->trm", Um, Zt[ii])
        Y = Y.at[jj].add(jnp.einsum("tbr,trm->tbm", Vm, W))
    return Y.reshape(nb * b, m)
