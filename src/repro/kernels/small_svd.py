"""Pallas TPU kernel: batched small SVD by one-sided Jacobi rotations.

The rounding pass (``core/algebra.py``) needs the SVD of the small core
matrix ``R_u R_v^T`` (r x r, r <= b) for every tile in a batch. XLA's SVD
does not exist inside Pallas; one-sided Jacobi does: it only ever rotates
pairs of columns by the angle that zeroes their inner product, and at the
end the column norms are the singular values and the normalized columns
are U:

    M = U diag(s) V^T        (V, not V^H -- the op contract of ops.small_svd)

Parallel ordering. Each step rotates n/2 disjoint column pairs at once:
the round-robin (circle) schedule pairs every column with every other once
per sweep in n - 1 rounds. A round is three small matmuls -- the Gram
matrix ``A^T A`` (all the inner products and norms the angles need) and
the products of A and of the accumulated V with the round's pairing
permutation -- plus elementwise work, all at ``Precision.HIGHEST`` so the
permutation products are exact. No dynamic lane slice, no scatter, no
trigonometric function (the angle is the smaller root of
``t^2 + 2 zeta t - 1 = 0``, Demmel-Veselic). Cyclic sweeps converge
quadratically; the loop stops after ``sweeps`` sweeps or once a sweep
finds every pair orthogonal to machine precision. Odd n is padded with one
zero column, which no rotation touches.

Values come out unsorted; the dispatch wrapper in ``ops.py`` sorts
descending, which the truncation logic of the rounding pass relies on.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .lr_sample import HIGHEST


def _dot(a, b, ca: int, cb: int):
    return jax.lax.dot_general(a, b, (((ca,), (cb,)), ((), ())),
                               precision=HIGHEST,
                               preferred_element_type=a.dtype)


def _jacobi_svd_kernel(a_ref, u_ref, s_ref, v_ref, *, sweeps: int):
    A = a_ref[0]                                   # (m, n), n even, n <= m
    n = A.shape[1]
    dtype = A.dtype
    tiny = jnp.finfo(dtype).tiny
    h = n - 1                                      # column h sits still
    row = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    eye = (row == col).astype(dtype)
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1).astype(dtype)

    def circle(x, r):
        # round r pairs circle positions x, y < h with x + y = r (mod h);
        # the one x with 2x = r (mod h) meets the still column h instead
        t = r - x + h
        t = jnp.where(t >= h, t - h, t)
        return jnp.where(t >= h, t - h, t)

    def one_round(r, carry):
        A, V, off = carry
        tr, tc = circle(row, r), circle(col, r)
        P = (((row < h) & (col < h) & (row == tc) & (tc != col))
             | ((col == h) & (row < h) & (tr == row))
             | ((row == h) & (col < h) & (tc == col))).astype(dtype)
        G = _dot(A, A, 0, 0)                                    # A^T A
        d_row = jnp.sum(G * eye, axis=0, keepdims=True)         # ||a_j||^2
        d_col = jnp.sum(G * eye, axis=1, keepdims=True)
        gamma = jnp.sum(G * P, axis=0, keepdims=True)           # <a_j, a_pj>
        d_par = jnp.sum(P * d_col, axis=0, keepdims=True)       # ||a_pj||^2
        first = idx < jnp.sum(P * row.astype(dtype), axis=0, keepdims=True)
        alpha = jnp.where(first, d_row, d_par)                  # pair (p, q),
        beta = jnp.where(first, d_par, d_row)                   # p < q
        do = jnp.abs(gamma) > tiny
        zeta = (beta - alpha) / (2.0 * jnp.where(do, gamma, 1.0))
        sgn = jnp.where(zeta >= 0.0, 1.0, -1.0)
        t = sgn / (jnp.abs(zeta) + jnp.sqrt(1.0 + zeta * zeta))
        c = jnp.where(do, jax.lax.rsqrt(1.0 + t * t), 1.0)
        s = jnp.where(do, c * t, 0.0) * jnp.where(first, -1.0, 1.0)
        # a_p <- c a_p - s a_q,  a_q <- s a_p + c a_q
        A = c * A + s * _dot(A, P, 1, 0)
        V = c * V + s * _dot(V, P, 1, 0)
        rel = jnp.abs(gamma) / jnp.maximum(jnp.sqrt(alpha * beta), tiny)
        return A, V, jnp.maximum(off, jnp.max(rel, axis=1, keepdims=True))

    def sweep(carry):
        k, A, V, _ = carry
        A, V, off = jax.lax.fori_loop(0, h, one_round,
                                      (A, V, jnp.zeros((1, 1), dtype)))
        return k + 1, A, V, off

    def unconverged(carry):
        k, _, _, off = carry
        return (k < sweeps) & (off[0, 0] > jnp.finfo(dtype).eps)

    _, A, V, _ = jax.lax.while_loop(
        unconverged, sweep, (0, A, eye, jnp.full((1, 1), jnp.inf, dtype)))
    s = jnp.sqrt(jnp.sum(A * A, axis=0, keepdims=True))   # (1, n) col norms
    U = A / jnp.maximum(s, tiny)
    u_ref[0] = jnp.where(s > tiny, U, 0.0)
    s_ref[0] = s
    v_ref[0] = V


@functools.partial(jax.jit, static_argnames=("sweeps", "interpret"))
def small_svd_pallas(M, *, sweeps: int = 8, interpret: bool = True):
    """Batched SVD of small cores: M (T, m, n), n <= m.

    Returns (U (T, m, n), s (T, n), V (T, n, n)) with M[t] ~= U s V^T,
    *unsorted* -- ``ops.small_svd`` sorts descending.
    """
    T, m, n = M.shape
    if n > m:
        raise ValueError(f"small_svd needs n <= m, got m={m}, n={n}; "
                         "transpose the core first")
    n2 = n + n % 2
    if n2 != n:                                     # one inert zero column
        M = jnp.pad(M, ((0, 0), (0, max(0, n2 - m)), (0, 1)))
    m2 = M.shape[1]
    # the working set is a dozen (n, n) and (m, n) f32 arrays
    vmem = 4 * (16 * n2 * n2 + 8 * m2 * n2)
    U, s, V = pl.pallas_call(
        functools.partial(_jacobi_svd_kernel, sweeps=sweeps),
        grid=(T,),
        in_specs=[pl.BlockSpec((1, m2, n2), lambda t: (t, 0, 0))],
        out_specs=[
            pl.BlockSpec((1, m2, n2), lambda t: (t, 0, 0)),
            # (1, 1, n): the last two block dims equal the array's, which
            # is what Mosaic's (8, 128) block rule accepts for a row
            pl.BlockSpec((1, 1, n2), lambda t: (t, 0, 0)),
            pl.BlockSpec((1, n2, n2), lambda t: (t, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, m2, n2), M.dtype),
            jax.ShapeDtypeStruct((T, 1, n2), M.dtype),
            jax.ShapeDtypeStruct((T, n2, n2), M.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=min(max(vmem, 32 << 20), 100 << 20)),
        interpret=interpret,
        name="small_svd_pallas",
    )(M)
    return U[:, :m, :n], s[:, 0, :n], V[:, :n, :n]
