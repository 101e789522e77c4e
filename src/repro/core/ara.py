"""Batched Adaptive Randomized Approximation (ARA), Algorithm 1 / [14].

The operator being compressed is only touched through black-box sampling
closures, which is what lets the TLR factorization compress the *matrix
expression* ``A(i,k) - sum_j L(i,j) L(k,j)^T`` without ever forming it:

  sample_fn(data, Omega) -> Y = Op @ Omega      (T, b, s)
  samplet_fn(data, Q)    -> B = Op^T @ Q        (T, m, R)

``data`` is an explicit pytree of operand arrays (tile gathers); it is an
argument rather than a closure capture, so one jitted step serves every
column of a row bucket.

TPU adaptation (see DESIGN.md section 2): the batch is *uniform* -- every
tile owns a zero-padded rank-``r_max`` basis buffer ``Q`` and a rank counter.
Zero padding makes the padded columns numerically inert (projections against
zero columns are zero), so no masking is needed in the orthogonalization.
Convergence is tracked per tile, and a converged tile stops appending where
Algorithm 5 would evict it. A batch runs as one ``lax.while_loop`` over
:func:`ara_iteration` until every tile converges: :func:`run_ara_fused`
here, and the TLR factorization's column loop (``core/cholesky.py``), which
reads its outcome once per column.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..precision import einsum


@dataclasses.dataclass(frozen=True)
class ARAParams:
    bs: int = 16          # number of sample vectors per block iteration
    r_max: int = 128      # basis buffer width (static rank bound)
    eps: float = 1e-6     # absolute threshold on the 2-norm residual estimate
    calib: float = 1.0    # estimator calibration constant
    gs_passes: int = 2    # block Gram-Schmidt passes against Q
    max_iters: int = 0    # 0 => r_max // bs
    qr: str = "householder"  # "householder" (robust) | "cholqr" (TPU-fast)

    @property
    def iters(self) -> int:
        return self.max_iters or max(1, self.r_max // self.bs)


class ARAState(NamedTuple):
    Q: jax.Array          # (T, b, r_max) zero-padded orthonormal bases
    rank: jax.Array       # (T,) int32
    converged: jax.Array  # (T,) bool
    err: jax.Array        # (T,) last residual-norm estimate
    it: jax.Array         # () int32


def init_state(T: int, b: int, p: ARAParams, dtype, valid=None) -> ARAState:
    """Fresh ARA state for a batch of T slots.

    ``valid``: optional (T,) bool mask marking which slots host real tiles.
    Invalid (padding) slots -- the tail of a column batch padded up to a
    bucket size (DESIGN.md section 2) -- start converged at rank 0 with zero
    error, so they never sample, never append, and never hold back the
    all-converged termination test.
    """
    if valid is None:
        converged = jnp.zeros((T,), bool)
        err = jnp.full((T,), jnp.inf, dtype)
    else:
        converged = ~valid
        err = jnp.where(valid, jnp.inf, 0.0).astype(dtype)
    return ARAState(
        Q=jnp.zeros((T, b, p.r_max), dtype),
        rank=jnp.zeros((T,), jnp.int32),
        converged=converged,
        err=err,
        it=jnp.zeros((), jnp.int32),
    )


def rank_overflow(ranks, err, p: ARAParams) -> np.ndarray:
    """Host-side mask of tiles that exhausted the rank budget unconverged.

    A tile overflows when it sits at the cap with a residual estimate
    still above ``p.eps`` (the ``~room`` forced-convergence path of
    :func:`ara_iteration`), or when its error estimate is non-finite;
    both take the same remedy ladder (eps-loosened re-pass, then densify;
    DESIGN.md section 13).
    """
    ranks = np.asarray(ranks)
    err = np.asarray(err)
    with np.errstate(invalid="ignore"):
        unconverged = ~(err <= p.eps)          # NaN err counts as overflow
    return ((ranks >= p.r_max) & unconverged) | ~np.isfinite(err)


def _orthonormalize(Y: jax.Array, method: str, drop_tol: float) -> jax.Array:
    """Orthonormalize the (T, b, s) panel; zero out numerically-dead columns.

    Columns whose norm (or orthogonalized residual, via the R diagonal) falls
    below ``drop_tol`` carry no information at the target accuracy and are
    zeroed -- zero columns are inert in all downstream projections. This is
    what keeps the panel QR stable when the sampled spectrum dies inside a
    block (rank-deficient panel).

    ``cholqr`` is the paper's mixed-precision CholeskyQR2 analogue (Gram +
    Cholesky, MXU-friendly); ``householder`` is the robust default used for
    CPU validation.
    """
    col_norm = jnp.linalg.norm(Y, axis=1)                      # (T, s)
    keep = col_norm > drop_tol
    # Relative cut: in a rank-deficient panel the dead directions are
    # normalized numerical noise whose R-diagonal can still exceed an
    # absolute tolerance; keeping one such column (it is NOT orthogonal to
    # the accumulated basis) poisons every later iteration.
    rel = 1e-8 if Y.dtype == jnp.float64 else 1e-4
    if method == "householder":
        Q, R = jnp.linalg.qr(Y)
        rdiag = jnp.abs(jnp.diagonal(R, axis1=-2, axis2=-1))   # (T, s)
        rmax = jnp.max(rdiag, axis=-1, keepdims=True)
        keep = keep & (rdiag > drop_tol) & (rdiag > rel * rmax)
        return Q * keep[:, None, :]

    # CholeskyQR2 on norm-equilibrated columns with trace-scaled jitter.
    cmax = jnp.max(col_norm, axis=-1, keepdims=True)
    keep = keep & (col_norm > rel * cmax)
    Yn = Y / jnp.maximum(col_norm, drop_tol)[:, None, :]
    Yn = Yn * keep[:, None, :]
    s = Y.shape[-1]
    eye = jnp.eye(s, dtype=Y.dtype)
    jit0 = 1e-12 if Y.dtype == jnp.float64 else 1e-5

    def one_pass(Yp):
        G = einsum("tbs,tbc->tsc", Yp, Yp)
        scale = jnp.maximum(jnp.trace(G, axis1=-2, axis2=-1), 1.0)
        R = jnp.linalg.cholesky(G + jit0 * scale[:, None, None] * eye)
        Yq = jax.scipy.linalg.solve_triangular(
            jnp.swapaxes(R, -1, -2), jnp.swapaxes(Yp, -1, -2), lower=False
        )
        return jnp.swapaxes(Yq, -1, -2)

    Q = one_pass(one_pass(Yn))
    return Q * keep[:, None, :]


def ara_iteration(
    sample_fn: Callable[[Any, jax.Array], jax.Array],
    data: Any,
    state: ARAState,
    key: jax.Array,
    p: ARAParams,
    *,
    share_omega: bool,
    T: int,
    b: int,
) -> ARAState:
    """One block iteration: sample, orthogonalize, estimate, append."""
    dtype = state.Q.dtype
    kit = jax.random.fold_in(key, state.it)
    shape = (b, p.bs) if share_omega else (T, b, p.bs)
    Omega = jax.random.normal(kit, shape, dtype)

    Y = sample_fn(data, Omega)  # (T, b, bs)
    # Two-pass block Gram-Schmidt against the accumulated basis. Padded
    # (zero) columns of Q contribute nothing, so no column masking needed.
    for _ in range(p.gs_passes):
        proj = einsum("tbr,tbs->trs", state.Q, Y)
        Y = Y - einsum("tbr,trs->tbs", state.Q, proj)

    # Residual 2-norm estimate from the projected-out samples: for a shared
    # Gaussian probe, max_j ||y_j|| concentrates around the residual norm.
    col_norms = jnp.linalg.norm(Y, axis=1)            # (T, bs)
    err = p.calib * jnp.max(col_norms, axis=1)        # (T,)

    newly = err <= p.eps
    active = ~state.converged & ~newly                # tiles that append
    room = state.rank + p.bs <= p.r_max
    active = active & room

    Qy = _orthonormalize(Y, p.qr, drop_tol=p.eps * 1e-3)
    Qy = jnp.where(active[:, None, None], Qy, jnp.zeros_like(Qy))

    # Append Qy into each tile's buffer at its own rank offset. The write is
    # masked per tile: for inactive tiles (converged or rank buffer full)
    # dynamic_update_slice would CLAMP the out-of-bounds offset and wipe the
    # final appended block with zeros.
    def put(Qi, Qyi, r):
        zero = jnp.zeros((), r.dtype)
        return jax.lax.dynamic_update_slice(Qi, Qyi, (zero, r))

    Q_cand = jax.vmap(put)(state.Q, Qy, state.rank)
    Q = jnp.where(active[:, None, None], Q_cand, state.Q)
    rank = state.rank + jnp.where(active, p.bs, 0)
    converged = state.converged | newly | (~room & ~state.converged)
    err = jnp.where(state.converged, state.err, err)
    return ARAState(Q=Q, rank=rank, converged=converged, err=err,
                    it=state.it + 1)


def run_ara_fused(
    sample_fn, samplet_fn, data, key, *, T: int, b: int, m: int,
    p: ARAParams, dtype, share_omega: bool = True, valid=None,
):
    """Single-jit ARA for a whole batch: while_loop until all tiles converge.

    ``valid`` marks real slots when the batch is zero-padded up to a bucket
    size (see ``init_state``); padding slots are inert.
    """
    state0 = init_state(T, b, p, dtype, valid=valid)

    def cond(state: ARAState):
        return (~jnp.all(state.converged)) & (state.it < p.iters)

    def body(state: ARAState):
        return ara_iteration(
            sample_fn, data, state, key, p, share_omega=share_omega, T=T, b=b
        )

    state = jax.lax.while_loop(cond, body, state0)
    B = samplet_fn(data, state.Q)  # (T, m, r_max); cols past rank are zero
    return state.Q, B, state.rank, state


# -- dense-operand convenience (used by Schur compensation & tests) ----------


def dense_batch_sampler(A: jax.Array):
    """Samplers for a batch of dense operators A: (T, b, m)."""

    def sample(data, Omega):
        if Omega.ndim == 2:
            return einsum("tbm,ms->tbs", data, Omega)
        return einsum("tbm,tms->tbs", data, Omega)

    def sample_t(data, Q):
        return einsum("tbm,tbq->tmq", data, Q)

    return sample, sample_t, A


def ara_compress_dense(
    A: jax.Array, key, p: ARAParams, *, share_omega: bool = True
):
    """Compress a batch of dense matrices (T, b, m) -> (Q, B, ranks)."""
    T, b, m = A.shape
    sample, sample_t, data = dense_batch_sampler(A)
    return run_ara_fused(
        sample, sample_t, data, key, T=T, b=b, m=m, p=p, dtype=A.dtype,
        share_omega=share_omega,
    )
