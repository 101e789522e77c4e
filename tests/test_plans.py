"""Rank-aware execution plans (DESIGN.md section 9): TilePlan memoization /
invalidation, ranked-vs-flat parity on every read path (TRSM, matvec,
tri_matvec, sample) on skewed rank distributions with rank-0 tiles, the
unified trace-registry compile pin, the auto policy's decision record, and
the pcg ``check_every`` history regression."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    CholOptions, PCGHistory, TLROperator, TilePlan, choose_batching,
    covariance_problem, pcg, plan_rank_buckets, resolve_batching,
    resolve_policy, tile_plan, tlr_matvec, tlr_tri_matvec, tlr_trsv,
    tlr_trsv_reference, trace_count, trace_counts, trace_counts_diff,
)
from repro.core.tlr import TLRMatrix, num_tiles, tril_pairs


# -- fixtures: skewed-rank synthetic factors -----------------------------------


def _skewed_lower(nb=8, b=16, r_max=16, seed=0):
    """Lower-triangular TLR L with a skewed rank distribution: most tiles
    rank 1-2, a few at r_max, some exactly rank 0 -- the regime the ranked
    read paths exist for. Factors honor the storage invariant (columns past
    each tile's rank exactly zero); diagonal blocks are well-conditioned
    lower-triangular."""
    rng = np.random.default_rng(seed)
    nt = num_tiles(nb)
    ranks = np.ones(nt, np.int32)
    ranks[rng.permutation(nt)[: max(1, nt // 4)]] = 2
    ranks[rng.permutation(nt)[: max(1, nt // 8)]] = r_max
    ranks[rng.permutation(nt)[: max(1, nt // 8)]] = 0
    D = np.tril(rng.standard_normal((nb, b, b)) * 0.1)
    D[:, np.arange(b), np.arange(b)] = 2.0 + rng.random((nb, b))
    U = np.zeros((nt, b, r_max))
    V = np.zeros((nt, b, r_max))
    for t, r in enumerate(ranks):
        U[t, :, :r] = rng.standard_normal((b, r)) * 0.1
        V[t, :, :r] = rng.standard_normal((b, r)) * 0.1
    return TLRMatrix(D=jnp.asarray(D), U=jnp.asarray(U), V=jnp.asarray(V),
                     ranks=jnp.asarray(ranks))


def _skewed_sym(nb=8, b=16, r_max=16, seed=1):
    """Symmetric TLR A with the same skewed distribution (diag symmetric)."""
    L = _skewed_lower(nb, b, r_max, seed)
    D = np.asarray(L.D)
    D = D + np.swapaxes(D, 1, 2)
    return TLRMatrix(D=jnp.asarray(D), U=L.U, V=L.V, ranks=L.ranks)


# -- TilePlan: structure, memoization, invalidation ----------------------------


def test_tile_plan_memoized_on_ranks_identity():
    L = _skewed_lower()
    p1 = tile_plan(L.ranks, L.r_max)
    p2 = tile_plan(L.ranks, L.r_max)
    assert p1 is p2                       # same ranks array -> cached plan
    assert isinstance(p1, TilePlan)
    # a new ranks array (every functional update makes one) -> new plan
    ranks2 = jnp.asarray(np.asarray(L.ranks).copy())
    p3 = tile_plan(ranks2, L.r_max)
    assert p3 is not p1
    np.testing.assert_array_equal(p3.widths, p1.widths)


def test_tile_plan_invalidated_on_host_mutation():
    """np.ndarray ranks (the right driver's in-place ``tile_w``) are
    fingerprinted: mutating the array in place invalidates its cache slot."""
    rk = np.array([0, 1, 2, 8, 8, 3], np.int64)
    p1 = tile_plan(rk, 8)
    assert tile_plan(rk, 8) is p1
    rk[0] = 5                             # in-place mutation
    p2 = tile_plan(rk, 8)
    assert p2 is not p1
    assert p2.widths[0] == 8              # 5 buckets up to 8


def test_tile_plan_widths_and_histogram():
    ranks = np.array([0, 1, 2, 3, 4, 5, 8, 9, 0], np.int64)
    plan = plan_rank_buckets(ranks, 16)
    np.testing.assert_array_equal(plan.widths,
                                  [0, 1, 2, 4, 4, 8, 8, 16, 0])
    assert plan.max_rank == 9
    assert plan.median_rank == pytest.approx(4.0)  # positive ranks only
    assert plan.rank_skew == pytest.approx(9 / 4.0)
    assert plan.useful_cols() == 32
    assert plan.flat_cols() == 9 * 16
    assert plan.padded_flop_ratio() > 1.0


def test_plan_flop_estimates_ordered():
    """flop_estimate-backed per-bucket costs: the ranked dispatch lowers
    strictly fewer FLOPs than the flat r_max-wide pass on a skewed plan."""
    L = _skewed_lower()
    plan = tile_plan(L.ranks, L.r_max)
    per_bucket = plan.bucket_flops(L.b, dtype=np.float64)
    flat = plan.flat_flops(L.b, dtype=np.float64)
    assert len(per_bucket) == len(plan.buckets)
    assert all(f > 0 for f in per_bucket)
    assert sum(per_bucket) < flat


# -- the auto policy -----------------------------------------------------------


def test_choose_batching_thresholds():
    skew = tile_plan(jnp.asarray(np.array([1, 1, 1, 16], np.int32)), 16)
    assert choose_batching(skew) == "ranked"          # skew 16 >= 4
    flat = tile_plan(jnp.asarray(np.array([8, 12, 16], np.int32)), 16)
    assert choose_batching(flat) == "flat"            # skew 2 < 4
    empty = tile_plan(jnp.asarray(np.zeros(0, np.int32)), 16)
    assert choose_batching(empty) == "flat"
    zeros = tile_plan(jnp.asarray(np.zeros(5, np.int32)), 16)
    assert choose_batching(zeros) == "flat"


def test_resolve_batching_auto_needs_ranks():
    with pytest.raises(ValueError, match="auto"):
        resolve_batching("auto")
    assert resolve_batching("flat") == "flat"
    assert resolve_batching(None) == "flat"
    L = _skewed_lower()
    assert resolve_batching("auto", L.ranks, L.r_max) in ("flat", "ranked")


def test_resolve_policy_record():
    L = _skewed_lower()
    plan = tile_plan(L.ranks, L.r_max)
    pol = resolve_policy("auto", plan, b=L.b)
    assert pol["requested"] == "auto"
    assert pol["batching"] == choose_batching(plan)
    assert pol["rank_skew"] == pytest.approx(plan.rank_skew)
    assert pol["padded_flop_ratio"] == pytest.approx(plan.padded_flop_ratio())
    assert pol["right_flush"] >= 1
    # explicit knobs pass through but keep the audit record
    pol2 = resolve_policy("flat", plan, b=L.b, right_flush=3)
    assert pol2["batching"] == "flat" and pol2["right_flush"] == 3
    with pytest.raises(ValueError):
        resolve_policy("bogus", plan, b=L.b)


@pytest.mark.parametrize("limit,want", [(None, 2), (17 * 2**30, 1),
                                        (2**50, 2)])
def test_auto_flush_fits_device_memory(monkeypatch, limit, want):
    """The auto flush cadence keeps the right driver's two accumulation
    buffers within a third of the device's memory: a one-chip v5e at
    N=32768, b=512, r_max=128 (2016 tiles) gets one column between
    flushes; a device that reports no limit (the CPU) is not capped, and
    an explicit ``right_flush`` is never overridden."""
    from repro.core import batching

    class Dev:
        def memory_stats(self):
            return None if limit is None else {"bytes_limit": limit}

    monkeypatch.setattr(batching.jax, "devices", lambda: [Dev()])
    plan = tile_plan(np.full(2016, 100, np.int32), 128)
    pol = resolve_policy("flat", plan, b=512, dtype=np.float32)
    assert pol["right_flush"] == want
    pol = resolve_policy("flat", plan, b=512, dtype=np.float32,
                         right_flush=3)
    assert pol["right_flush"] == 3


@pytest.mark.parametrize("count", [64, 3])
def test_bucketed_round_inplace_chunked_matches(monkeypatch, count):
    """In-place rounding (results written back into the donated stacks at
    their full width, zero past the new rank) in dispatches of ``count``
    tiles (one-tile dispatches for remainders of one) gives the
    out-of-place pass's factors; rank-0 tiles keep their content."""
    from repro.core import batching
    from repro.core.batching import bucketed_round_tiles

    monkeypatch.setattr(batching, "ROUND_COUNTS", (1, count))
    rng = np.random.default_rng(0)
    n, b, w = 20, 16, 24
    ranks = rng.integers(0, w + 1, n)
    ranks[:4] = 0
    U = rng.standard_normal((n, b, w)).astype(np.float32)
    V = rng.standard_normal((n, b, w)).astype(np.float32)
    cols = np.arange(w)[None, None, :]
    U = np.where(cols < ranks[:, None, None], U, 0)
    V = np.where(cols < ranks[:, None, None], V, 0)
    U[0, 0, 0] = 7.0   # rank-0 content an in-place pass must not touch
    Uo, Vo, ro, eo = bucketed_round_tiles(jnp.asarray(U), jnp.asarray(V),
                                          ranks, 1e-3, r_out=b)
    Ui, Vi, ri, ei = bucketed_round_tiles(
        jnp.asarray(U), jnp.asarray(V), ranks, 1e-3, r_out=b, inplace=True)
    assert Ui.shape == (n, b, w) and Uo.shape == (n, b, b)
    np.testing.assert_array_equal(np.asarray(ri), np.asarray(ro))
    np.testing.assert_allclose(np.asarray(ei), np.asarray(eo), atol=1e-5)
    live = ranks > 0
    for got, want in ((Ui, Uo), (Vi, Vo)):
        got = np.asarray(got)
        np.testing.assert_allclose(got[live, :, :b], np.asarray(want)[live],
                                   atol=1e-5)
        assert not got[live, :, b:].any()
    assert float(Ui[0, 0, 0]) == 7.0
    with pytest.raises(ValueError, match="r_out"):
        bucketed_round_tiles(jnp.asarray(U), jnp.asarray(V), ranks, 1e-3,
                             r_out=w + 1, inplace=True)


@pytest.mark.parametrize("count,want", [
    (1, [1]), (5, [8]), (8, [8]), (9, [64]), (64, [64]), (70, [64, 8]),
    (129, [64, 64, 1])])
def test_round_dispatches(count, want):
    """Rounding dispatch counts step by 8x: a bucket goes in chunks of 64
    tiles, each padded to the smallest of 1, 8, 64 that holds it."""
    from repro.core.batching import round_dispatches

    assert round_dispatches(count) == want


def test_round_bucket_flops_count_dispatches():
    """The round.bucket spans and TilePlan.bucket_flops count the FLOPs of
    the dispatches the rounding makes (a 3-tile bucket pays an 8-tile
    core, not the count ladder's 4 or a 64-tile chunk)."""
    from repro import obs
    from repro.core.batching import (_round_core_flops, bucketed_round_tiles,
                                     round_dispatches)

    n, b, w = 3, 8, 4
    U = jnp.asarray(np.random.default_rng(0).standard_normal((n, b, w)))
    ranks = np.full(n, w)
    plan = tile_plan(ranks, w)
    (bk,) = plan.buckets
    want = _round_core_flops(8, b, w, w, U.dtype, None)
    assert round_dispatches(bk.count) == [8]
    assert plan.bucket_flops(b, w, dtype=U.dtype) == [want]
    obs.enable()
    bucketed_round_tiles(U, U, ranks, 1e-10, r_out=w)
    tel = obs.disable()
    (span,) = [s for s in tel.spans if s.name == "round.bucket"]
    assert span.args["flops_padded"] == want
    assert span.args["padded"] == 8


def test_factorization_stats_record_policy():
    _, K = covariance_problem(256, 2, 32)
    K = np.asarray(K) + 1e-2 * np.eye(256)
    op = TLROperator.compress(jnp.asarray(K), 32, 32, 1e-6)
    for algo in ("left", "right"):
        fact = op.cholesky(CholOptions(eps=1e-6, bs=8, algo=algo))
        pol = fact.stats["policy"]
        assert pol["requested"] == "auto"
        assert pol["batching"] == fact.stats["batching"]
        assert "padded_flop_ratio" in pol and "rank_skew" in pol
        assert pol["flops_flat"] >= pol["flops_ranked"] > 0


# -- ranked-vs-flat parity on the read paths -----------------------------------


@pytest.mark.parametrize("trans", [False, True])
@pytest.mark.parametrize("nrhs", [None, 4])
def test_trsm_ranked_matches_flat_and_reference(trans, nrhs):
    L = _skewed_lower()
    rng = np.random.default_rng(2)
    y = rng.standard_normal(L.n) if nrhs is None else rng.standard_normal(
        (L.n, nrhs))
    yj = jnp.asarray(y)
    x_r = np.asarray(tlr_trsv(L, yj, trans=trans, batching="ranked"))
    x_f = np.asarray(tlr_trsv(L, yj, trans=trans, batching="flat"))
    x_ref = np.asarray(tlr_trsv_reference(L, yj, trans=trans))
    assert x_r.shape == y.shape
    np.testing.assert_allclose(x_r, x_ref, rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(x_f, x_ref, rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("nrhs", [None, 3])
def test_matvec_ranked_matches_flat(nrhs):
    A = _skewed_sym()
    rng = np.random.default_rng(3)
    x = rng.standard_normal(A.n) if nrhs is None else rng.standard_normal(
        (A.n, nrhs))
    xj = jnp.asarray(x)
    y_r = np.asarray(tlr_matvec(A, xj, batching="ranked"))
    y_f = np.asarray(tlr_matvec(A, xj, batching="flat"))
    assert y_r.shape == x.shape
    np.testing.assert_allclose(y_r, y_f, rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("trans", [False, True])
def test_tri_matvec_ranked_matches_flat(trans):
    L = _skewed_lower()
    x = jnp.asarray(np.random.default_rng(4).standard_normal((L.n, 2)))
    y_r = np.asarray(tlr_tri_matvec(L, x, trans=trans, batching="ranked"))
    y_f = np.asarray(tlr_tri_matvec(L, x, trans=trans, batching="flat"))
    np.testing.assert_allclose(y_r, y_f, rtol=1e-12, atol=1e-12)


def test_sample_runs_through_plan_dispatch():
    """fact.sample rides tri_matvec's plan dispatch; parity via the tri
    product itself (sampling is L z, a deterministic function of z)."""
    _, K = covariance_problem(256, 2, 32)
    K = np.asarray(K) + 1e-1 * np.eye(256)
    op = TLROperator.compress(jnp.asarray(K), 32, 32, 1e-8)
    fact = op.cholesky(CholOptions(eps=1e-8, bs=8))
    s = fact.sample(jax.random.PRNGKey(0), num=3)
    assert s.shape == (256, 3) and np.isfinite(np.asarray(s)).all()
    L = fact.L
    z = jnp.asarray(np.random.default_rng(5).standard_normal((256, 2)))
    np.testing.assert_allclose(
        np.asarray(tlr_tri_matvec(L, z, batching="ranked")),
        np.asarray(tlr_tri_matvec(L, z, batching="flat")),
        rtol=1e-12, atol=1e-12)


def test_zero_rank_reads_skip_plan_kernels():
    """An all-zero-rank operator's ranked matvec compiles no plan cores:
    the zero bucket never touches a kernel (it is diag-only)."""
    nb, b = 4, 8
    rng = np.random.default_rng(6)
    D = rng.standard_normal((nb, b, b))
    D = D + np.swapaxes(D, 1, 2)
    nt = num_tiles(nb)
    A = TLRMatrix(D=jnp.asarray(D), U=jnp.zeros((nt, b, b)),
                  V=jnp.zeros((nt, b, b)),
                  ranks=jnp.zeros(nt, jnp.int32))
    x = jnp.asarray(rng.standard_normal(A.n))
    snap = trace_counts()
    y = tlr_matvec(A, x, batching="ranked")
    assert trace_counts_diff(snap) == {}  # zero ranks touch no plan kernel
    want = np.zeros(A.n)
    for k in range(nb):
        want[k * b:(k + 1) * b] = D[k] @ np.asarray(x)[k * b:(k + 1) * b]
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-10, atol=1e-10)


# -- unified trace registry: the compile-count contract ------------------------


def test_unified_registry_keys_and_views():
    from repro.core import algebra, batching, solve

    counts = trace_counts()
    assert set(counts) <= {"trsm", "algebra", "batching", "plan"}
    assert trace_count() == sum(counts.values())
    assert solve.trsm_trace_count() == trace_count("trsm")
    assert algebra.algebra_trace_count() == trace_count("algebra")
    assert batching.batching_trace_count() == trace_count("batching")


def test_plan_core_compile_count_pinned():
    """Repeated ranked reads on one plan retrace nothing; a fresh run
    compiles at most (#buckets) sym-chain variants per rhs shape."""
    L = _skewed_lower(nb=8, b=16, seed=7)
    A = _skewed_sym(nb=8, b=16, seed=7)
    plan = tile_plan(A.ranks, A.r_max)
    x = jnp.asarray(np.random.default_rng(8).standard_normal(A.n))
    snap = trace_counts()
    tlr_matvec(A, x, batching="ranked")
    compiled = trace_counts_diff(snap).get("plan", 0)
    assert 0 < compiled <= len(plan.buckets)
    warm = trace_counts()
    tlr_matvec(A, x + 1.0, batching="ranked")
    tlr_matvec(A, 2.0 * x, batching="ranked")
    assert trace_counts_diff(warm) == {}   # steady state: zero retraces


def test_trsm_ranked_compile_count_additive():
    """Ranked TRSM keeps the flat path's jit-cache contract: at most one
    column-step variant per (row-bucket ladder entry, direction) -- the
    width ladder multiplies nothing."""
    L = _skewed_lower(nb=16, b=8, r_max=8, seed=9)
    ladder_len = int(math.log2(L.nb - 1)) + 2
    y = jnp.asarray(np.random.default_rng(10).standard_normal(L.n))
    snap = trace_counts()
    tlr_trsv(L, y, trans=False, batching="ranked")
    tlr_trsv(L, y, trans=True, batching="ranked")
    compiled = trace_counts_diff(snap).get("trsm", 0)
    assert 0 < compiled <= 2 * ladder_len
    warm = trace_counts()
    tlr_trsv(L, y + 1.0, trans=False, batching="ranked")
    assert trace_counts_diff(warm) == {}


# -- pcg check_every -----------------------------------------------------------


def _spd_problem(n=128, seed=11):
    rng = np.random.default_rng(seed)
    M = rng.standard_normal((n, n))
    A = M @ M.T + n * np.eye(n)
    b = rng.standard_normal(n)
    return jnp.asarray(A), jnp.asarray(b)


def test_pcg_check_every_identical_history():
    """The device op sequence per iteration is unchanged, so the iterate
    history is bit-for-bit identical for every ``check_every``."""
    A, b = _spd_problem()
    mv = lambda v: A @ v
    x1, it1, h1 = pcg(mv, b, tol=1e-10, maxiter=60, check_every=1)
    for ce in (2, 5, 16, 1000):
        xc, itc, hc = pcg(mv, b, tol=1e-10, maxiter=60, check_every=ce)
        assert itc == it1
        assert list(hc) == list(h1)        # bitwise-equal floats
        np.testing.assert_array_equal(np.asarray(xc), np.asarray(x1))
        assert hc.breakdown is None


def test_pcg_check_every_breakdown_parity():
    """Mid-window breakdowns replay to the exact per-iteration stopping
    point: same breakdown tag, same history, same final iterate."""
    n = 64
    rng = np.random.default_rng(12)
    M = rng.standard_normal((n, n))
    A = jnp.asarray(-(M @ M.T) - n * np.eye(n))    # negative definite
    b = jnp.asarray(rng.standard_normal(n))
    mv = lambda v: A @ v
    x1, it1, h1 = pcg(mv, b, tol=1e-12, maxiter=30, check_every=1)
    assert h1.breakdown == "indefinite_curvature"
    for ce in (3, 7, 30):
        xc, itc, hc = pcg(mv, b, tol=1e-12, maxiter=30, check_every=ce)
        assert hc.breakdown == h1.breakdown
        assert itc == it1 and list(hc) == list(h1)
        np.testing.assert_array_equal(np.asarray(xc), np.asarray(x1))


def test_pcg_check_every_converged_tail_not_overrun():
    """Convergence inside a window stops at the converged iterate: no
    history entries past the tolerance crossing."""
    A, b = _spd_problem(seed=13)
    mv = lambda v: A @ v
    _, it1, h1 = pcg(mv, b, tol=1e-8, maxiter=200, check_every=1)
    _, itc, hc = pcg(mv, b, tol=1e-8, maxiter=200, check_every=64)
    assert itc == it1 and len(hc) == len(h1)
    assert hc[-1] < 1e-8
    assert all(v >= 1e-8 for v in list(hc)[1:-1])


def test_pcg_zero_and_histories_are_pcghistory():
    A, b = _spd_problem(seed=14)
    x, it, h = pcg(lambda v: A @ v, jnp.zeros_like(b), check_every=8)
    assert it == 0 and isinstance(h, PCGHistory) and h == []


def test_pcg_scalar_maxiter_not_multiple_of_window():
    """maxiter that is not a multiple of check_every stops at exactly
    maxiter iterations (the window clamps to the remaining budget)."""
    A, b = _spd_problem(seed=15)
    mv = lambda v: A @ v
    x1, it1, h1 = pcg(mv, b, tol=1e-30, maxiter=10, check_every=1)
    assert it1 == 10 and len(h1) == 11
    for ce in (3, 4, 7, 64):
        xc, itc, hc = pcg(mv, b, tol=1e-30, maxiter=10, check_every=ce)
        assert itc == 10 and len(hc) == 11
        assert list(hc) == list(h1)
        np.testing.assert_array_equal(np.asarray(xc), np.asarray(x1))


# -- multi-RHS TRSM through the plan + batched-RHS pcg (PR 7) ------------------


def test_trsm_multirhs_ranked_compile_count_additive():
    """An (n, k) RHS rides the same plan bucket widths as the vector path:
    at most one column-step variant per (ladder entry, direction) for the
    new RHS shape, zero retraces steady-state, and no dependence on k
    beyond the one shape."""
    L = _skewed_lower(nb=16, b=8, r_max=8, seed=16)
    ladder_len = int(math.log2(L.nb - 1)) + 2
    Y = jnp.asarray(np.random.default_rng(17).standard_normal((L.n, 8)))
    snap = trace_counts()
    tlr_trsv(L, Y, trans=False, batching="ranked")
    tlr_trsv(L, Y, trans=True, batching="ranked")
    compiled = trace_counts_diff(snap).get("trsm", 0)
    assert 0 < compiled <= 2 * ladder_len
    warm = trace_counts()
    tlr_trsv(L, Y + 1.0, trans=False, batching="ranked")
    tlr_trsv(L, 2.0 * Y, trans=True, batching="ranked")
    assert trace_counts_diff(warm) == {}   # steady state: zero retraces
    # ranked multi-RHS parity against the reference sweep
    np.testing.assert_allclose(
        np.asarray(tlr_trsv(L, Y, trans=False, batching="ranked")),
        np.asarray(tlr_trsv_reference(L, Y, trans=False)),
        rtol=1e-12, atol=1e-12)


def test_pcg_batched_matches_scalar_per_column():
    """(n, k) right-hand sides run per-column CG: every column's iteration
    count and history match its own scalar pcg run (same recurrence, same
    stopping rules; reduction order differs so equality is to round-off)."""
    A, _ = _spd_problem(seed=18)
    mv = lambda v: A @ v
    rng = np.random.default_rng(19)
    B = jnp.asarray(rng.standard_normal((A.shape[0], 4)))
    X, iters, hists = pcg(mv, B, tol=1e-8, maxiter=200, check_every=8)
    assert X.shape == B.shape and iters.shape == (4,) and len(hists) == 4
    for j in range(4):
        xj, itj, hj = pcg(mv, B[:, j], tol=1e-8, maxiter=200, check_every=8)
        assert int(iters[j]) == itj
        assert hists[j].breakdown is None and hj.breakdown is None
        np.testing.assert_allclose(list(hists[j]), list(hj),
                                   rtol=1e-6, atol=1e-14)
        np.testing.assert_allclose(np.asarray(X[:, j]), np.asarray(xj),
                                   rtol=1e-8, atol=1e-12)


def test_pcg_batched_per_column_tolerance():
    """tol may be a (k,) array: each column stops at its own threshold --
    the loose column evicts early, the tight column keeps iterating (the
    serve path's per-request tolerance rides on this)."""
    A, _ = _spd_problem(seed=20)
    mv = lambda v: A @ v
    b = np.random.default_rng(21).standard_normal(A.shape[0])
    B = jnp.asarray(np.stack([b, b], axis=1))
    X, iters, hists = pcg(mv, B, tol=np.array([1e-2, 1e-10]), maxiter=200,
                          check_every=4)
    assert int(iters[0]) < int(iters[1])
    assert hists[0][-1] < 1e-2 and hists[1][-1] < 1e-10
    for j, tol in enumerate((1e-2, 1e-10)):
        _, itj, _ = pcg(mv, B[:, j], tol=tol, maxiter=200, check_every=4)
        assert int(iters[j]) == itj


def test_pcg_batched_per_column_breakdown():
    """A breakdown freezes only its own column: the healthy column keeps
    iterating to convergence while the indefinite one stops with the same
    tag its scalar run reports."""
    n = 64
    rng = np.random.default_rng(22)
    M = rng.standard_normal((n, n))
    Apos = jnp.asarray(M @ M.T + n * np.eye(n))
    Aneg = -Apos
    mv = lambda V: jnp.stack([Apos @ V[:, 0], Aneg @ V[:, 1]], axis=1)
    B = jnp.asarray(rng.standard_normal((n, 2)))
    X, iters, hists = pcg(mv, B, tol=1e-8, maxiter=50, check_every=4)
    assert hists[0].breakdown is None and hists[0][-1] < 1e-8
    assert hists[1].breakdown == "indefinite_curvature"
    x0, it0, h0 = pcg(lambda v: Apos @ v, B[:, 0], tol=1e-8, maxiter=50,
                      check_every=4)
    x1, it1, h1 = pcg(lambda v: Aneg @ v, B[:, 1], tol=1e-8, maxiter=50,
                      check_every=4)
    assert int(iters[0]) == it0 and int(iters[1]) == it1
    np.testing.assert_allclose(np.asarray(X[:, 0]), np.asarray(x0),
                               rtol=1e-10, atol=1e-12)
    np.testing.assert_array_equal(np.asarray(X[:, 1]), np.asarray(x1))


def test_pcg_batched_maxiter_window_guard():
    """Per-column budgets that are not multiples of the window stop at
    exactly maxiter iterations (stop_at + replay, never an overrun)."""
    A, _ = _spd_problem(seed=23)
    mv = lambda v: A @ v
    B = jnp.asarray(np.random.default_rng(24).standard_normal(
        (A.shape[0], 3)))
    X, iters, hists = pcg(mv, B, tol=1e-30, maxiter=10, check_every=4)
    np.testing.assert_array_equal(np.asarray(iters), [10, 10, 10])
    assert all(len(h) == 11 for h in hists)


def test_pcg_batched_zero_column():
    """A zero column completes instantly (x = 0, empty history) without
    touching the recurrence; live columns are unaffected."""
    A, b = _spd_problem(seed=25)
    mv = lambda v: A @ v
    B = jnp.stack([b, jnp.zeros_like(b)], axis=1)
    X, iters, hists = pcg(mv, B, tol=1e-8, maxiter=200, check_every=8)
    assert int(iters[1]) == 0 and hists[1] == []
    np.testing.assert_array_equal(np.asarray(X[:, 1]), 0.0)
    assert hists[0][-1] < 1e-8
