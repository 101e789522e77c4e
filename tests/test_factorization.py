"""Integration tests: TLR Cholesky / LDL^T vs dense oracles (paper sections 4-5)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    CholOptions, covariance_problem, fractional_diffusion_problem,
    from_dense, pcg, spectral_norm_est, tile_perm_to_element_perm,
    tlr_cholesky, tlr_ldlt, tlr_matvec,
    tlr_to_dense, tlr_tri_matvec, tlr_trsv, dense_ldlt_tile, robust_cholesky,
)


def _cov_tlr(n=512, d=3, b=64, eps=1e-7, r_max=64):
    _, K = covariance_problem(n, d, b)
    A = from_dense(jnp.asarray(K), b, r_max, eps)
    return K, A


def _factor_error(K, fact):
    """||P A P^T - L (D) L^T||_2 via dense reconstruction."""
    Ld = np.asarray(tlr_to_dense(fact.L.D, fact.L.U, fact.L.V,
                                 fact.L.nb, fact.L.b))
    # keep only the lower triangle (to_dense mirrors the off-diag tiles)
    Ld = np.tril(Ld)
    eperm = tile_perm_to_element_perm(fact.perm, fact.L.b)
    Ap = K[np.ix_(eperm, eperm)]
    if fact.d is not None:
        dd = np.asarray(fact.d).reshape(-1)
        R = Ld @ np.diag(dd) @ Ld.T
    else:
        R = Ld @ Ld.T
    return np.linalg.norm(Ap - R, 2)


@pytest.mark.parametrize("mode", ["fused", "dynamic"])
def test_cholesky_accuracy(mode):
    K, A = _cov_tlr()
    opts = CholOptions(eps=1e-6, bs=8, mode=mode, r_max_out=64)
    fact = tlr_cholesky(A, opts)
    err = _factor_error(K, fact)
    assert err < 1e-4, f"mode={mode}: ||A-LL^T|| = {err}"
    assert fact.stats["modified_chol"] == 0


@pytest.mark.slow
def test_cholesky_modes_agree():
    """Dynamic batching must not change the math, only the orchestration."""
    K, A = _cov_tlr(n=384, b=64)
    f1 = tlr_cholesky(A, CholOptions(eps=1e-6, bs=8, mode="fused"))
    f2 = tlr_cholesky(A, CholOptions(eps=1e-6, bs=8, mode="dynamic", bucket=3))
    e1, e2 = _factor_error(K, f1), _factor_error(K, f2)
    assert abs(e1 - e2) < 5e-5
    # Ranks agree to within one sample block: the math is identical, but a
    # refilled slot sees a different (equally fresh) Omega stream, which can
    # move a borderline tile by +-bs.
    r1, r2 = np.asarray(f1.L.ranks), np.asarray(f2.L.ranks)
    assert np.max(np.abs(r1 - r2)) <= 8


@pytest.mark.parametrize("share_omega", [True, False])
def test_share_omega_equivalent_accuracy(share_omega):
    K, A = _cov_tlr(n=384, b=64)
    opts = CholOptions(eps=1e-6, bs=8, share_omega=share_omega)
    err = _factor_error(K, tlr_cholesky(A, opts))
    assert err < 1e-4


@pytest.mark.parametrize("eps", [1e-2, 1e-4, 1e-6])
def test_accuracy_tracks_threshold(eps):
    """Factorization error scales with the compression threshold (Fig. 7 regime)."""
    K, A = _cov_tlr(n=512, b=64, eps=eps * 1e-2)
    fact = tlr_cholesky(A, CholOptions(eps=eps, bs=8))
    err = _factor_error(K, fact)
    assert err < 100 * eps


@pytest.mark.slow
def test_tighter_eps_higher_ranks():
    K, A = _cov_tlr(n=512, d=3, b=64, eps=1e-9, r_max=64)
    r_loose = np.asarray(
        tlr_cholesky(A, CholOptions(eps=1e-2, bs=4)).L.ranks).sum()
    r_tight = np.asarray(
        tlr_cholesky(A, CholOptions(eps=1e-6, bs=4)).L.ranks).sum()
    assert r_tight > r_loose


def test_trsv_and_solve():
    K, A = _cov_tlr()
    fact = tlr_cholesky(A, CholOptions(eps=1e-8, bs=8))
    rng = np.random.default_rng(0)
    x_true = rng.standard_normal(A.n)
    y = np.asarray(K) @ x_true
    x = np.asarray(fact.solve(jnp.asarray(y)))
    rel = np.linalg.norm(x - x_true) / np.linalg.norm(x_true)
    assert rel < 1e-3, f"solve relative error {rel}"


def test_tri_matvec_roundtrip():
    _, A = _cov_tlr(n=384, b=64)
    fact = tlr_cholesky(A, CholOptions(eps=1e-8, bs=8))
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal(A.n))
    y = tlr_tri_matvec(fact.L, x)
    x2 = tlr_trsv(fact.L, y)
    np.testing.assert_allclose(np.asarray(x2), np.asarray(x), rtol=1e-8,
                               atol=1e-8)
    yt = tlr_tri_matvec(fact.L, x, trans=True)
    x3 = tlr_trsv(fact.L, yt, trans=True)
    np.testing.assert_allclose(np.asarray(x3), np.asarray(x), rtol=1e-8,
                               atol=1e-8)


def test_logdet_and_mvn():
    K, A = _cov_tlr(n=384, b=64)
    fact = tlr_cholesky(A, CholOptions(eps=1e-8, bs=8))
    ld = float(fact.logdet())
    _, ld_ref = np.linalg.slogdet(K)
    assert abs(ld - ld_ref) / abs(ld_ref) < 1e-3
    # value parity with the per-tile host loop the batched jnp.diagonal
    # implementation replaced
    ld_loop = 2.0 * float(sum(
        np.sum(np.log(np.abs(np.diag(np.asarray(fact.L.D[k])))))
        for k in range(fact.L.nb)))
    np.testing.assert_allclose(ld, ld_loop, rtol=1e-12)
    s = fact.sample(jax.random.PRNGKey(0), num=4)
    assert s.shape == (A.n, 4) and np.isfinite(np.asarray(s)).all()


@pytest.mark.slow
def test_pcg_preconditioned_by_tlr():
    """Fractional-diffusion PCG: looser eps => more iterations (Fig. 9)."""
    _, Kfd = fractional_diffusion_problem(512, 64)
    A = from_dense(jnp.asarray(Kfd), 64, 64, 1e-10)
    rng = np.random.default_rng(0)
    rhs = jnp.asarray(rng.standard_normal(512))

    iters = {}
    for eps in (1e-2, 1e-6):
        Keps = Kfd + eps * np.eye(512)
        Aeps = from_dense(jnp.asarray(Keps), 64, 64, eps * 1e-3)
        fact = tlr_cholesky(Aeps, CholOptions(eps=eps, bs=8))
        x, it, hist = pcg(
            lambda v: tlr_matvec(A, v), rhs,
            precond=lambda r: fact.solve(r),
            tol=1e-6, maxiter=300,
        )
        iters[eps] = it
        assert hist[-1] < 1e-6 or it == 300
    assert iters[1e-6] <= iters[1e-2]
    assert iters[1e-6] < 50  # tight preconditioner converges fast


def test_unpreconditioned_cg_is_worse():
    _, Kfd = fractional_diffusion_problem(512, 64)
    A = from_dense(jnp.asarray(Kfd), 64, 64, 1e-10)
    rhs = jnp.asarray(np.random.default_rng(0).standard_normal(512))
    _, it_plain, _ = pcg(lambda v: tlr_matvec(A, v), rhs, tol=1e-6,
                         maxiter=300)
    fact = tlr_cholesky(A, CholOptions(eps=1e-6, bs=8))
    _, it_prec, _ = pcg(lambda v: tlr_matvec(A, v), rhs,
                        precond=lambda r: fact.solve(r),
                        tol=1e-6, maxiter=300)
    assert it_prec < it_plain


# -- robustness extensions (section 5) -----------------------------------------


@pytest.mark.slow
def test_schur_compensation_rescues_loose_eps():
    """At loose eps on an ill-conditioned matrix, compensation avoids breakdown."""
    _, Kfd = fractional_diffusion_problem(768, 64, s=0.9)
    A = from_dense(jnp.asarray(Kfd), 64, 64, 1e-10)
    f_comp = tlr_cholesky(A, CholOptions(eps=5e-3, bs=8, schur="diag",
                                         modified_chol=True))
    # factorization finished and L is finite
    assert np.isfinite(np.asarray(f_comp.L.D)).all()
    assert np.isfinite(np.asarray(f_comp.L.V)).all()


def test_modified_cholesky_fallback():
    # near-PSD tile: eigenvalue clamp keeps the factor finite
    rng = np.random.default_rng(0)
    Q, _ = np.linalg.qr(rng.standard_normal((32, 32)))
    w = np.linspace(1.0, -1e-8, 32)
    Aind = jnp.asarray((Q * w) @ Q.T)
    L, bad = robust_cholesky(Aind, delta=1e-6)
    assert bool(bad)
    assert np.isfinite(np.asarray(L)).all()
    resid = np.asarray(L @ L.T) - np.asarray(Aind)
    assert np.linalg.norm(resid, 2) < 1e-4


def test_dense_ldlt_tile():
    rng = np.random.default_rng(3)
    M = rng.standard_normal((48, 48))
    Aind = jnp.asarray(M + M.T)  # symmetric indefinite
    L, d = dense_ldlt_tile(Aind)
    R = np.asarray(L) @ np.diag(np.asarray(d)) @ np.asarray(L).T
    np.testing.assert_allclose(R, np.asarray(Aind), rtol=1e-6, atol=1e-8)
    assert (np.asarray(d) < 0).any(), "indefinite: some d must be negative"


def test_ldlt_factorization_spd():
    """LDL^T on an SPD matrix matches Cholesky accuracy (section 6.3)."""
    K, A = _cov_tlr(n=384, b=64)
    fact = tlr_ldlt(A, CholOptions(eps=1e-6, bs=8))
    err = _factor_error(K, fact)
    assert err < 1e-4
    assert (np.asarray(fact.d) > 0).all()


def test_ldlt_factorization_indefinite():
    """LDL^T factors a (mildly) indefinite TLR matrix."""
    K, _ = _cov_tlr(n=384, b=64)
    K = np.asarray(K) - 0.5 * np.eye(384)  # shift: indefinite but invertible
    A = from_dense(jnp.asarray(K), 64, 64, 1e-9)
    fact = tlr_ldlt(A, CholOptions(eps=1e-7, bs=8))
    err = _factor_error(K, fact)
    assert err < 1e-4
    assert (np.asarray(fact.d) < 0).any()
    # solve through the LDL^T factorization
    rng = np.random.default_rng(0)
    x_true = rng.standard_normal(384)
    y = K @ x_true
    x = np.asarray(fact.solve(jnp.asarray(y)))
    assert np.linalg.norm(x - x_true) / np.linalg.norm(x_true) < 1e-2


@pytest.mark.parametrize("pivot", ["frobenius", "power"])
def test_pivoted_cholesky(pivot):
    """Inter-tile pivoting (section 5.2): correct factorization of P A P^T."""
    K, A = _cov_tlr(n=384, b=64)
    fact = tlr_cholesky(A, CholOptions(eps=1e-6, bs=8, pivot=pivot))
    err = _factor_error(K, fact)
    assert err < 1e-4
    # the permutation should generally be non-trivial for covariance problems
    assert fact.perm.shape == (A.nb,)
    # solve must honor the permutation
    rng = np.random.default_rng(0)
    x_true = rng.standard_normal(384)
    y = K @ x_true
    x = np.asarray(fact.solve(jnp.asarray(y)))
    assert np.linalg.norm(x - x_true) / np.linalg.norm(x_true) < 1e-2


def test_power_norms_zero_tile_f32():
    """The power-iteration normalizer clamps at the dtype's smallest
    normal number: a zero f32 tile estimates 0, not NaN (a 1e-300 clamp
    is 0 in f32)."""
    from repro.core.cholesky import _power_norms

    tiles = jnp.zeros((2, 16, 16), jnp.float32)
    tiles = tiles.at[1].set(3.0 * jnp.eye(16, dtype=jnp.float32))
    norms = _power_norms(tiles, 5, jax.random.PRNGKey(0))
    assert norms.dtype == jnp.float32
    assert float(norms[0]) == 0.0
    np.testing.assert_allclose(float(norms[1]), 3.0, rtol=1e-6)
