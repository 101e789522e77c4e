"""Pallas TPU kernel: batched thin QR via modified Gram-Schmidt.

The rounding pass of the tile algebra (``core/algebra.py``) reduces every
low-rank sum to one batched QR of the stacked factors followed by a small
SVD of the core. LAPACK-style Householder panels do not map onto the MXU;
the kernel instead runs right-looking modified Gram-Schmidt: when column
``k`` is finalized it is projected out of every later column with one
rank-1 update (an outer product -- MXU work), so the whole factorization is
``r`` sequential steps of matvec + outer-product, all expressible with
``where`` / lane and sublane reductions / ``fori_loop`` (no scatter, no
dynamic lane slice, no linalg primitives).

Rank deficiency: a column whose residual norm falls below a relative drop
tolerance (1e-8 f64 / 1e-4 f32, the same cut ``core/ara.py`` uses) carries
no information and is zeroed -- zero columns are inert in every downstream
product, and the small-SVD truncation removes the matching zero rows of R.
Two MGS sweeps restore orthogonality on ill-conditioned panels (MGS2); R is
recovered as ``Q^T Y`` at the end, so ``Y ~= Q R`` holds to the drop
tolerance even for rank-deficient input.

Requires ``r <= b`` (tall panels): the economy factorization is
``Q (b, r), R (r, r)``, matching ``jnp.linalg.qr(..., mode="reduced")``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .lr_sample import HIGHEST


def _mgs_body(r: int, tol, Q):
    """One MGS sweep over the r columns of Q (b, r); returns orthonormal Q.

    Column ``k`` is read and written through an iota mask on the lane axis
    (a masked lane reduction and a select) -- Mosaic has no dynamic lane
    slice -- and the projection is a sublane reduction plus a broadcast
    outer product, so every step is plain VPU work."""
    col = jax.lax.broadcasted_iota(jnp.int32, (1, r), 1)

    def body(k, Q):
        sel = col == k
        qk = jnp.sum(jnp.where(sel, Q, 0.0), axis=1, keepdims=True)  # (b, 1)
        nrm = jnp.sqrt(jnp.sum(qk * qk, axis=0, keepdims=True))      # (1, 1)
        keep = nrm > tol
        qk = jnp.where(keep, qk / jnp.maximum(nrm, tol), 0.0)
        # project the finalized direction out of every *later* column
        proj = jnp.sum(qk * Q, axis=0, keepdims=True)                # (1, r)
        proj = jnp.where(col > k, proj, 0.0)
        Q = Q - qk * proj
        return jnp.where(sel, qk, Q)

    return jax.lax.fori_loop(0, r, body, Q)


def _mgs_qr_kernel(y_ref, q_ref, r_ref, *, sweeps: int):
    Y = y_ref[0]                                                 # (b, r)
    r = Y.shape[1]
    rel = 1e-8 if Y.dtype == jnp.float64 else 1e-4
    Q = Y
    for _ in range(sweeps):
        # Tolerance must track the *current* column scale: after sweep 1 the
        # surviving columns are unit vectors, so a tolerance derived from the
        # input norms (which can exceed 1/rel) would zero them all in sweep 2.
        col_norm = jnp.sqrt(jnp.sum(Q * Q, axis=0, keepdims=True))  # (1, r)
        tol = jnp.maximum(rel * jnp.max(col_norm, axis=1, keepdims=True),
                          jnp.finfo(Y.dtype).tiny)                  # (1, 1)
        Q = _mgs_body(r, tol, Q)
    q_ref[0] = Q
    # R = Q^T Y, contracting the sublane (row) axis of both operands
    r_ref[0] = jax.lax.dot_general(
        Q, Y, (((0,), (0,)), ((), ())), precision=HIGHEST,
        preferred_element_type=Q.dtype)


@functools.partial(jax.jit, static_argnames=("sweeps", "interpret"))
def batched_qr_pallas(Y, *, sweeps: int = 2, interpret: bool = True):
    """Batched economy QR: Y (T, b, r) -> Q (T, b, r), R (T, r, r), r <= b."""
    T, b, r = Y.shape
    if r > b:
        raise ValueError(
            f"batched_qr needs tall panels (r <= b), got b={b}, r={r}; "
            "densify the factor sum first (core/algebra.py does)")
    return pl.pallas_call(
        functools.partial(_mgs_qr_kernel, sweeps=sweeps),
        grid=(T,),
        in_specs=[pl.BlockSpec((1, b, r), lambda t: (t, 0, 0))],
        out_specs=[
            pl.BlockSpec((1, b, r), lambda t: (t, 0, 0)),
            pl.BlockSpec((1, r, r), lambda t: (t, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, b, r), Y.dtype),
            jax.ShapeDtypeStruct((T, r, r), Y.dtype),
        ],
        interpret=interpret,
        name="batched_qr_pallas",
    )(Y)
