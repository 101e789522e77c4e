"""Pure-jnp oracles for every Pallas kernel (the allclose references)."""

from __future__ import annotations

import jax.numpy as jnp

from ..precision import einsum


def lr_sample_ref(Ui, Vi, W2):
    """Y[t] = sum_j U[t,j] @ (V[t,j]^T @ W2[j])."""
    T, k, b, _ = Ui.shape
    s = W2.shape[-1]
    if k == 0:
        return jnp.zeros((T, b, s), Ui.dtype)
    T3 = einsum("tjbr,jbs->tjrs", Vi, W2)
    return einsum("tjbr,tjrs->tbs", Ui, T3)


def batched_gemm_ref(A, B, ranks):
    """C[t] = A[t][:, :ranks[t]] @ B[t][:ranks[t], :] via masking."""
    k = A.shape[-1]
    mask = (jnp.arange(k)[None, :] < ranks[:, None]).astype(A.dtype)
    return einsum("tmk,tk,tkn->tmn", A, mask, B)


def tile_chain_ref(U, V, X):
    """out[t] = U[t] @ (V[t]^T @ X[t])."""
    return einsum("tbr,trs->tbs", U, einsum("tbr,tbs->trs", V, X))


def batched_qr_ref(Y):
    """Batched economy QR, (T, b, r) -> Q (T, b, r), R (T, r, r), r <= b.

    Householder (XLA's geqrf): for rank-deficient panels the dead Q columns
    are arbitrary orthonormal directions with ~zero R rows, while the MGS
    kernel zeroes them -- both satisfy the only contract the rounding pass
    needs (Y ~= Q R with orthonormal live columns).
    """
    return jnp.linalg.qr(Y, mode="reduced")


def small_svd_ref(M):
    """Batched SVD of small cores: (T, m, n) -> (U, s, V), M ~= U s V^T.

    Note V, not V^H, to match the rotation-accumulated V of the Jacobi
    kernel; singular values descending.
    """
    U, s, Vh = jnp.linalg.svd(M, full_matrices=False)
    return U, s, jnp.swapaxes(Vh, -1, -2)
