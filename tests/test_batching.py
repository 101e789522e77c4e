"""Rank-bucketed dynamic batching (core/batching.py, DESIGN.md section 8).

Pins the tentpole contracts of the ``batching="ranked"`` dispatch layer:

* bucketed-vs-flat parity: ``tlr_round``, ``tlr_gemm``, ``tlr_syrk`` and
  both Cholesky drivers produce the same result (same truncation
  semantics; exact up to floating-point reduction order),
* the compile-count contract: ``batching_trace_count()`` stays at
  O(log2(r_max) * log2(nt)) bucket-core variants -- never one per rank
  distribution or per tile -- and a repeat call at the same shapes
  compiles nothing,
* rank-0 buckets skip the kernels entirely (no QR/SVD, no phantom rank-1
  regrowth -- the PR 4 rank-floor semantics extended to the bucketed
  path),
* the tile-mesh sharding hook is numerics-neutral with a single-device
  mesh and the no-mesh fallback is the identity.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    CholOptions, TLROperator, batching_trace_count, bucket_width,
    exp_covariance, kd_tree_ordering, plan_rank_buckets, rank_ladder,
    set_tile_mesh, tile_mesh, tlr_axpy, tlr_gemm, tlr_round, tlr_round_tiles,
    tlr_syrk, tlr_to_dense,
)


def _cov_operator(seed, nb, b, eps=1e-5, ell=0.1):
    """Covariance operator with a *heterogeneous* rank distribution (short
    correlation length + loose threshold spreads ranks well below b)."""
    rng = np.random.default_rng(seed)
    n = nb * b
    pts = rng.random((n, 3))
    K = exp_covariance(pts[kd_tree_ordering(pts, b)], ell)
    return np.asarray(K), TLROperator.compress(jnp.asarray(K), b, b, eps)


def _block_diag_op(nb=4, b=32, seed=0):
    rng = np.random.default_rng(seed)
    n = nb * b
    K = np.zeros((n, n))
    for s in range(0, n, b):
        M = rng.standard_normal((b, b))
        K[s:s + b, s:s + b] = M @ M.T + b * np.eye(b)
    return K, TLROperator.compress(jnp.asarray(K), b, b, 1e-10)


def _factor_error(K, fact):
    Ld = np.tril(np.asarray(tlr_to_dense(fact.L.D, fact.L.U, fact.L.V,
                                         fact.L.nb, fact.L.b)))
    if fact.d is not None:
        R = Ld @ np.diag(np.asarray(fact.d).reshape(-1)) @ Ld.T
    else:
        R = Ld @ Ld.T
    return np.linalg.norm(K - R, 2)


# -- planning units ------------------------------------------------------------


def test_rank_ladder_and_bucket_width():
    assert rank_ladder(8) == [1, 2, 4, 8]
    assert rank_ladder(12) == [1, 2, 4, 8, 12]
    assert bucket_width([3, 9], 64) == 16
    assert bucket_width([64], 64) == 64
    assert bucket_width([0, 0], 64) == 1    # floor: no 0-width batches
    assert bucket_width(np.zeros((0,)), 64) == 1
    assert bucket_width([5], 0) == 0


def test_plan_rank_buckets_groups_and_zero_bucket():
    ranks = np.asarray([0, 1, 2, 3, 4, 5, 8, 9, 0])
    plan = plan_rank_buckets(ranks, 16)
    widths = {bk.width: sorted(bk.idx.tolist()) for bk in plan.buckets}
    assert widths == {1: [1], 2: [2], 4: [3, 4], 8: [5, 6], 16: [7]}
    assert sorted(plan.zero_idx.tolist()) == [0, 8]
    assert plan.zero_count == 2
    # every tile lands in exactly one group
    covered = sorted(sum((bk.idx.tolist() for bk in plan.buckets),
                         plan.zero_idx.tolist()))
    assert covered == list(range(len(ranks)))
    # count padding rides the count ladder
    for bk in plan.buckets:
        assert bk.padded >= bk.count


def test_resolve_batching_validated():
    _, op = _block_diag_op()
    with pytest.raises(ValueError, match="batching"):
        op.cholesky(CholOptions(batching="bucketed"))
    with pytest.raises(ValueError, match="batching"):
        tlr_round(op.A, 1e-8, batching="bogus")


# -- bucketed-vs-flat parity ---------------------------------------------------


def test_round_ranked_matches_flat():
    _, op = _cov_operator(0, 6, 32)
    ranks = np.asarray(op.ranks)
    assert ranks.min() < ranks.max()  # heterogeneous, else the test is void
    Rf = tlr_round(op.A, 1e-6)
    Rr = tlr_round(op.A, 1e-6, batching="ranked")
    np.testing.assert_array_equal(np.asarray(Rf.ranks), np.asarray(Rr.ranks))
    np.testing.assert_allclose(np.asarray(Rr.to_dense()),
                               np.asarray(Rf.to_dense()), rtol=1e-12,
                               atol=1e-12)


def test_round_ranked_wide_concat_densify_bucket():
    """Accumulated concatenations (axpy width convention) whose per-tile
    width exceeds b must route through the densify bucket and still agree
    with the flat pass."""
    _, op = _cov_operator(1, 4, 32)
    S = tlr_axpy(1.0, op.A, tlr_axpy(1.0, op.A, op.A))  # widths up to 3b
    assert S.r_max > op.b
    Rf = tlr_round(S, 1e-8)
    Rr = tlr_round(S, 1e-8, batching="ranked")
    np.testing.assert_allclose(np.asarray(Rr.to_dense()),
                               np.asarray(Rf.to_dense()), rtol=1e-9,
                               atol=1e-9)


def test_round_tiles_ranked_needs_ranks():
    _, op = _cov_operator(2, 3, 16)
    with pytest.raises(ValueError, match="ranks"):
        tlr_round_tiles(op.A.U, op.A.V, 1e-8, batching="ranked")
    Uf, Vf, rf, ef = tlr_round_tiles(op.A.U, op.A.V, 1e-8)
    Ur, Vr, rr, er = tlr_round_tiles(op.A.U, op.A.V, 1e-8, ranks=op.A.ranks,
                                     batching="ranked")
    np.testing.assert_array_equal(np.asarray(rf), np.asarray(rr))
    np.testing.assert_allclose(np.asarray(ef), np.asarray(er), rtol=1e-12,
                               atol=1e-14)


def test_gemm_and_syrk_ranked_match_flat():
    _, opA = _cov_operator(3, 5, 32)
    _, opB = _cov_operator(4, 5, 32)
    Cf = tlr_gemm(opA.A, opB.A, 1e-8)
    Cr = tlr_gemm(opA.A, opB.A, 1e-8, batching="ranked")
    np.testing.assert_allclose(np.asarray(Cr.to_dense()),
                               np.asarray(Cf.to_dense()), rtol=1e-11,
                               atol=1e-11)
    fact = opB.cholesky(CholOptions(eps=1e-8, algo="right"))
    Sf = tlr_syrk(opA.A, fact.L, 1e-10)
    Sr = tlr_syrk(opA.A, fact.L, 1e-10, batching="ranked")
    np.testing.assert_allclose(np.asarray(Sr.to_dense()),
                               np.asarray(Sf.to_dense()), rtol=1e-10,
                               atol=1e-10)


@pytest.mark.parametrize("ldl", [False, True])
def test_right_driver_ranked_matches_flat(ldl):
    K, op = _cov_operator(5, 8, 32)
    make = op.ldlt if ldl else op.cholesky
    ff = make(CholOptions(eps=1e-6, algo="right"))
    fr = make(CholOptions(eps=1e-6, algo="right", batching="ranked"))
    assert fr.stats["batching"] == "ranked"
    ef, er = _factor_error(K, ff), _factor_error(K, fr)
    assert ef < 1e-4 and er < 1e-4
    assert er < 100 * max(ef, 1e-8)
    # both factorizations solve to the same answer
    rng = np.random.default_rng(0)
    x_true = rng.standard_normal(op.n)
    y = jnp.asarray(K @ x_true)
    xf, xr = np.asarray(ff.solve(y)), np.asarray(fr.solve(y))
    nrm = np.linalg.norm(x_true)
    assert np.linalg.norm(xf - x_true) / nrm < 1e-3
    assert np.linalg.norm(xr - x_true) / nrm < 1e-3
    # ranked appends run at the bucketed panel rank, never above r_max
    assert all(1 <= w <= op.r_max for w in fr.stats["append_widths"])


@pytest.mark.parametrize("ldl", [False, True], ids=["cholesky", "ldlt"])
def test_left_driver_ranked_matches_flat(ldl):
    K, op = _cov_operator(6, 8, 32)
    make = op.ldlt if ldl else op.cholesky
    ff = make(CholOptions(eps=1e-6, bs=8))
    fr = make(CholOptions(eps=1e-6, bs=8, batching="ranked"))
    ef, er = _factor_error(K, ff), _factor_error(K, fr)
    assert ef < 1e-4 and er < 1e-4
    # same sampling keys, exact (zero-column) slicing: the ranked run sees
    # the same operator samples, so the factors agree to rounding noise
    Lf = np.tril(np.asarray(tlr_to_dense(ff.L.D, ff.L.U, ff.L.V, 8, 32)))
    Lr = np.tril(np.asarray(tlr_to_dense(fr.L.D, fr.L.U, fr.L.V, 8, 32)))
    np.testing.assert_allclose(Lr, Lf, rtol=1e-7, atol=1e-7)
    # the ranked projection widths ride the rank ladder
    for ev in fr.stats["column_events"]:
        assert ev["wQ"] in rank_ladder(op.r_max)


# -- compile-count contract ----------------------------------------------------


def test_batching_trace_count_pinned():
    """Bucket cores compile O(log) variants per shape family, reuse across
    rank distributions sharing the ladder, and never retrace at steady
    state."""
    _, op = _cov_operator(7, 6, 16)
    tlr_round(op.A, 1e-6, batching="ranked")  # warm the family
    t0 = batching_trace_count()
    tlr_round(op.A, 1e-6, batching="ranked")
    tlr_round(op.A, 1e-4, batching="ranked")  # new eps: still no retrace
    assert batching_trace_count() == t0
    # a bigger grid of the same tile shape adds at most a ladder of count
    # variants (never one executable per tile)
    _, big = _cov_operator(8, 12, 16)
    t0 = batching_trace_count()
    tlr_round(big.A, 1e-6, batching="ranked")
    nt = big.A.U.shape[0]
    bound = (int(math.log2(big.r_max)) + 1) + (int(math.log2(nt)) + 1)
    assert batching_trace_count() - t0 <= bound
    t0 = batching_trace_count()
    tlr_round(big.A, 1e-6, batching="ranked")
    assert batching_trace_count() == t0


def test_right_ranked_compile_count_steady_state():
    """A repeat ranked factorization at the same shapes compiles no new
    bucket cores (process-wide cache), and the per-run TRSM variants stay
    ladder-bounded like every other column step."""
    _, op = _cov_operator(9, 8, 16)
    opts = CholOptions(eps=1e-6, algo="right", batching="ranked")
    op.cholesky(opts)
    t0 = batching_trace_count()
    fact = op.cholesky(opts)
    assert batching_trace_count() == t0
    assert fact.stats["column_traces"] <= int(math.log2(op.nb)) + 1


# -- rank-0 bucket skips the kernels (PR 4 rank-floor, bucketed) ---------------


def test_zero_rank_bucket_skips_kernels_and_keeps_floor():
    K, op = _block_diag_op()
    assert int(np.asarray(op.ranks).max()) == 0
    t0 = batching_trace_count()
    R = tlr_round(op.A, 1e-10, batching="ranked")
    # all tiles sit in the zero bucket: no bucket core compiles, no QR/SVD
    assert batching_trace_count() == t0
    assert int(np.asarray(R.ranks).max()) == 0
    np.testing.assert_allclose(np.asarray(R.to_dense()), K, rtol=0,
                               atol=1e-12)


def test_right_ranked_block_diagonal_no_phantom_ranks():
    """The ranked right-looking driver on a block-diagonal matrix: every
    panel is rank 0, so the trailing update is skipped outright and no
    off-diagonal rank is ever resurrected."""
    K, op = _block_diag_op()
    fact = op.cholesky(CholOptions(eps=1e-8, algo="right",
                                   batching="ranked"))
    assert int(np.asarray(fact.L.ranks).max()) == 0
    assert float(jnp.abs(fact.L.U).max()) == 0.0
    # rank-0 panels skip the trailing update entirely: nothing appended,
    # nothing accumulated, so no flush can ever trigger
    assert fact.stats["append_widths"] == [0] * (op.nb - 1)
    assert fact.stats["flushes"] == 0
    assert _factor_error(K, fact) < 1e-10 * np.linalg.norm(K, 2)
    x = jnp.asarray(np.random.default_rng(1).standard_normal(op.n))
    y = np.asarray(fact.solve(jnp.asarray(K @ np.asarray(x))))
    assert np.linalg.norm(y - np.asarray(x)) / np.linalg.norm(x) < 1e-8


# -- tile-mesh sharding hook ---------------------------------------------------


def test_tile_mesh_single_device_smoke():
    """Sharding the accumulation batch axis over a 1-device mesh is
    numerics-neutral for tlr_gemm and the ranked right driver; the hook
    restores cleanly and the no-mesh path is the identity."""
    from jax.sharding import Mesh

    K, op = _cov_operator(10, 4, 32)
    want = np.asarray(tlr_gemm(op.A, op.A, 1e-8).to_dense())
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    prev = set_tile_mesh(mesh)
    try:
        assert tile_mesh() is mesh
        got = np.asarray(tlr_gemm(op.A, op.A, 1e-8).to_dense())
        fact = op.cholesky(CholOptions(eps=1e-6, algo="right",
                                       batching="ranked"))
    finally:
        set_tile_mesh(prev)
    assert tile_mesh() is prev
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-12)
    assert _factor_error(K, fact) < 1e-4
