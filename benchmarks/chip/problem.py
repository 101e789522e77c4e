"""The benchmark's own inputs and plain references, kept apart from the
program under test so that no change to the program can move them.

* :func:`points` -- the point cloud of the paper's section 6.1 problem,
  uniform in the unit ball (disk in 2D) from the seed, KD-tree ordered so
  that tiles hold clusters.
* :func:`dense_covariance` -- the exponential covariance
  ``exp(-r / ell) + nugget I``, built on the device in row blocks.
* :func:`dense_reference` -- logdet from a blocked dense Cholesky, a
  power-iteration ``||A||_2`` and ``tr(A)``, all in f32 at HIGHEST.
* :func:`tlr_apply` -- a TLR matrix (or its lower factor) times a block of
  vectors, straight from the tiles by the definition
  ``A = D + sum_t U_t V_t^T`` over the packed lower tiles.
* :func:`backward_error` -- the normwise backward error of solves.

Nothing here imports the program.
"""

from __future__ import annotations

import math

import numpy as np


# -- points ------------------------------------------------------------------


def ball_points(n: int, dim: int, seed: int) -> np.ndarray:
    """``n`` points uniform in the unit ``dim``-ball, from ``seed``."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, dim))
    x /= np.linalg.norm(x, axis=1, keepdims=True)
    r = rng.random(n) ** (1.0 / dim)
    return x * r[:, None]


def kd_tree_order(pts: np.ndarray, tile: int) -> np.ndarray:
    """Permutation into KD-tree leaves of ``tile`` points: each cluster is
    sorted along the widest side of its bounding box and split so that the
    left child holds the power-of-two multiple of ``tile`` nearest to half
    the cluster (the paper's ordering)."""
    out: list[np.ndarray] = []
    stack = [np.arange(pts.shape[0])]
    while stack:
        idx = stack.pop()
        m = idx.shape[0]
        if m <= tile:
            out.append(idx)
            continue
        cloud = pts[idx]
        axis = int(np.argmax(cloud.max(axis=0) - cloud.min(axis=0)))
        order = np.argsort(cloud[:, axis], kind="stable")
        p2 = 2 ** int(round(np.log2(max(1, m / (2 * tile)))))
        left = min(m - 1, max(1, p2 * tile))
        stack.append(idx[order[left:]])     # popped second
        stack.append(idx[order[:left]])     # popped first: left to right
    return np.concatenate(out)


def points(cfg: dict, seed: int) -> np.ndarray:
    """The configuration's point cloud for ``seed``, KD-tree ordered."""
    pts = ball_points(cfg["n"], cfg["dim"], seed)
    return pts[kd_tree_order(pts, cfg["tile"])]


# -- dense covariance ------------------------------------------------------------


def dense_covariance(pts: np.ndarray, cfg: dict, rows: int = 512):
    """``exp(-r / ell) + nugget I`` in f32 on the default device, ``rows``
    rows per step of one jitted loop. Distances come from coordinate
    differences, so the diagonal is exactly ``1 + nugget``."""
    import jax
    import jax.numpy as jnp

    n = pts.shape[0]
    rows = math.gcd(n, rows)
    ell, nugget = float(cfg["ell"]), float(cfg["nugget"])

    @jax.jit
    def build(P):
        def block(i):
            Pi = jax.lax.dynamic_slice_in_dim(P, i * rows, rows)
            diff = Pi[:, None, :] - P[None, :, :]
            K = jnp.exp(-jnp.sqrt(jnp.sum(diff * diff, axis=-1)) / ell)
            eye = (jnp.arange(rows)[:, None] + i * rows
                   == jnp.arange(n)[None, :])
            return K + nugget * eye.astype(K.dtype)

        return jax.lax.map(block, jnp.arange(n // rows)).reshape(n, n)

    return build(jnp.asarray(pts, jnp.float32))


# -- dense reference ---------------------------------------------------------------


def dense_reference(K, block: int = 1024) -> dict:
    """logdet of ``K`` from a blocked right-looking dense Cholesky (one
    compiled panel step over ``block``-row panels, each updating the whole
    trailing matrix through a column mask), ``||K||_2`` by 20 power
    iterations, and ``tr(K)``; f32 at HIGHEST."""
    import jax
    import jax.numpy as jnp

    n = K.shape[0]
    block = math.gcd(n, block)

    @jax.jit
    def ref(K):
        cols = jnp.arange(n)

        def panel(j, carry):
            A, logdet = carry
            P = jax.lax.dynamic_slice_in_dim(A, j * block, block, axis=0)
            Ljj = jnp.linalg.cholesky(
                jax.lax.dynamic_slice_in_dim(P, j * block, block, axis=1))
            logdet += 2.0 * jnp.sum(jnp.log(jnp.diagonal(Ljj)))
            W = jax.scipy.linalg.solve_triangular(Ljj, P, lower=True)
            W = jnp.where(cols[None, :] >= (j + 1) * block, W, 0.0)
            return A - W.T @ W, logdet

        def power(_, x):
            y = K @ x
            return y / jnp.linalg.norm(y)

        with jax.default_matmul_precision("highest"):
            _, logdet = jax.lax.fori_loop(0, n // block, panel,
                                          (K, jnp.zeros((), K.dtype)))
            x = jax.lax.fori_loop(0, 20, power, jnp.ones((n,), K.dtype))
            norm2 = jnp.linalg.norm(K @ x)
        return logdet, norm2, jnp.trace(K)

    logdet, norm2, tr = ref(K)
    return {"logdet": float(logdet), "norm2": float(norm2), "trace": float(tr)}


def matmul_dense(K, X):
    """``K @ X`` at HIGHEST, for the checks."""
    import jax

    with jax.default_matmul_precision("highest"):
        return K @ X


def backward_error(K, norm2: float, X, Y) -> np.ndarray:
    """Per column ``||K x - y|| / (||K||_2 ||x|| + ||y||)``."""
    import jax.numpy as jnp

    R = matmul_dense(K, X) - Y
    nx = jnp.linalg.norm(X, axis=0)
    return np.asarray(jnp.linalg.norm(R, axis=0)
                      / (norm2 * nx + jnp.linalg.norm(Y, axis=0)))


def relative_residual(K, X, Y) -> np.ndarray:
    """Per column ``||K x - y|| / ||y||``."""
    import jax.numpy as jnp

    R = matmul_dense(K, X) - Y
    return np.asarray(jnp.linalg.norm(R, axis=0) / jnp.linalg.norm(Y, axis=0))


# -- TLR matrices from their tiles -----------------------------------------------------


def tril_pairs(nb: int) -> tuple[np.ndarray, np.ndarray]:
    """Row and column tile index of every strictly lower tile, in packed
    order: tile (i, j), i > j, sits at ``i (i - 1) / 2 + j``."""
    ii, jj = np.tril_indices(nb, -1)
    order = np.argsort(ii * (ii - 1) // 2 + jj)
    return ii[order], jj[order]


def tlr_apply(D, U, V, ranks, Z, *, lower: bool = False):
    """``A Z`` for the symmetric TLR matrix ``A`` with diagonal tiles ``D``
    and lower tiles ``U_t[:, :r_t] V_t[:, :r_t]^T``; with ``lower=True``,
    ``L Z`` for the lower-triangular factor with those tiles (the strict
    upper part of each diagonal tile is not part of ``L``). ``Z`` is
    ``(n, m)``; f32 at HIGHEST, all tiles in one batched pass."""
    import jax
    import jax.numpy as jnp

    nb, b = D.shape[0], D.shape[1]
    m = Z.shape[1]
    ii, jj = tril_pairs(nb)
    ii, jj = jnp.asarray(ii), jnp.asarray(jj)
    keep = (jnp.arange(U.shape[2])[None, :]
            < jnp.asarray(ranks)[:, None]).astype(U.dtype)
    Um, Vm = U * keep[:, None, :], V * keep[:, None, :]
    Zt = Z.reshape(nb, b, m)
    with jax.default_matmul_precision("highest"):
        Dl = jnp.tril(D) if lower else D
        Y = jnp.einsum("ibc,icm->ibm", Dl, Zt)
        W = jnp.einsum("tbr,tbm->trm", Vm, Zt[jj])
        Y = Y.at[ii].add(jnp.einsum("tbr,trm->tbm", Um, W))
        if not lower:
            W = jnp.einsum("tbr,tbm->trm", Um, Zt[ii])
            Y = Y.at[jj].add(jnp.einsum("tbr,trm->tbm", Vm, W))
    return Y.reshape(nb * b, m)
