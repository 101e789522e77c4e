"""TLR benchmarks, one function per paper table/figure (section 6).

Runnable standalone with suite selection:

    PYTHONPATH=src python -m benchmarks.bench_tlr --suite solve

``--suite solve`` times the solve phase: the old host-loop TRSV against the
jitted bucketed TRSM that replaced it (PR 2), and the TilePlan-dispatched
ranked read paths against the flat r_max-wide ones (PR 6, also standalone
as ``--suite plans``).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    CholOptions, TLROperator, choose_batching, covariance_problem,
    fractional_diffusion_problem, pcg, tile_plan, tlr_axpy, tlr_gemm,
    tlr_matvec, tlr_newton_schulz, tlr_round, tlr_to_dense, tlr_trsv,
    tlr_trsv_reference,
)

from .common import emit, factorization_flop_model, scaled, timeit, write_json


def _build(n, d, b, build_eps=1e-9, r_max=None):
    _, K = covariance_problem(n, d, b)
    op = TLROperator.compress(jnp.asarray(K), b, r_max or b, build_eps)
    return K, op


def _factor_err(K, fact):
    Ld = np.tril(np.asarray(tlr_to_dense(fact.L.D, fact.L.U, fact.L.V,
                                         fact.L.nb, fact.L.b)))
    from repro.core import tile_perm_to_element_perm
    ep = tile_perm_to_element_perm(fact.perm, fact.L.b)
    if fact.d is not None:
        R = Ld @ np.diag(np.asarray(fact.d).reshape(-1)) @ Ld.T
    else:
        R = Ld @ Ld.T
    return np.linalg.norm(K[np.ix_(ep, ep)] - R, 2)


def bench_tile_size():
    """Table 1: tile size vs memory and factorization time (3D covariance)."""
    n = scaled(2048)
    for b in (64, 128, 256):
        K, op = _build(n, 3, b)
        dt, fact = timeit(
            lambda: op.cholesky(CholOptions(eps=1e-6, bs=8)), repeats=1)
        mem = op.memory_stats()
        emit(f"table1/tile{b}", dt * 1e6,
             f"mem_logical_MB={mem['total_bytes_logical']/2**20:.1f};"
             f"avg_rank={mem['avg_rank']:.1f};"
             f"err={_factor_err(K, fact):.2e}")


def bench_memory_growth():
    """Figure 5: memory vs N for 2D/3D at several eps; fit growth exponent."""
    for d in (2, 3):
        sizes = [scaled(512), scaled(1024), scaled(2048)]
        for eps in (1e-2, 1e-6):
            mems = []
            for n in sizes:
                b = 128 if n >= 1024 else 64
                _, K = covariance_problem(n, d, b)
                op = TLROperator.compress(jnp.asarray(K), b, b, eps)
                mems.append(op.memory_stats()["total_bytes_logical"])
            expo = np.polyfit(np.log(sizes), np.log(mems), 1)[0]
            emit(f"fig5/{d}d_eps{eps:g}", 0.0,
                 f"bytes={mems};growth_exponent={expo:.2f}")


def bench_rank_distributions():
    """Figure 6: rank distribution, regular grid vs random ball (3D)."""
    n, b = scaled(2048), 128
    for geom in ("grid", "ball"):
        _, K = covariance_problem(n, 3, b, geometry=geom)
        op = TLROperator.compress(jnp.asarray(K), b, b, 1e-6)
        ranks = np.sort(np.asarray(op.ranks))[::-1]
        emit(f"fig6/{geom}", 0.0,
             f"max={ranks[0]};median={int(np.median(ranks))};"
             f"over_half_tile={(ranks > b // 2).sum()}")


def bench_compress():
    """PR 2 construction path: batched-SVD compression vs the per-tile host
    SVD loop it replaced, plus the batched-ARA compressor."""
    n, b = scaled(2048), 128
    _, K = covariance_problem(n, 3, b)
    Kj = jnp.asarray(K)

    def old_loop():
        # the pre-PR-2 construction: one host SVD per tile
        nb = n // b
        for i in range(1, nb):
            for j in range(i):
                np.linalg.svd(K[i * b:(i + 1) * b, j * b:(j + 1) * b],
                              full_matrices=False)

    t_old, _ = timeit(old_loop, repeats=1)
    t_new, op = timeit(
        lambda: TLROperator.compress(Kj, b, b, 1e-6), repeats=1)
    t_ara, op_a = timeit(
        lambda: TLROperator.compress(Kj, b, b, 1e-6, method="ara"), repeats=1)
    emit("compress/batched_svd", t_new * 1e6,
         f"host_loop_us={t_old*1e6:.0f};speedup={t_old/t_new:.2f};"
         f"avg_rank={op.memory_stats()['avg_rank']:.1f}")
    emit("compress/batched_ara", t_ara * 1e6,
         f"avg_rank={op_a.memory_stats()['avg_rank']:.1f}")


def bench_factor_time():
    """Figure 7: TLR factor time vs N and eps, against dense Cholesky."""
    for d in (2, 3):
        for n in (scaled(1024), scaled(2048)):
            b = 128
            K, op = _build(n, d, b)
            t_dense, _ = timeit(lambda: np.linalg.cholesky(K), repeats=1)
            for eps in (1e-2, 1e-6):
                dt, fact = timeit(
                    lambda: op.cholesky(CholOptions(eps=eps, bs=8)),
                    repeats=1)
                emit(f"fig7/{d}d_n{n}_eps{eps:g}", dt * 1e6,
                     f"dense_us={t_dense*1e6:.0f};speedup={t_dense/dt:.2f};"
                     f"err={_factor_err(K, fact):.2e}")


def bench_profile():
    """Figure 8a: GEMM share of factorization work (FLOP-weighted)."""
    n, b = scaled(2048), 128
    K, op = _build(n, 3, b)
    fact = op.cholesky(CholOptions(eps=1e-6, bs=16))
    ranks = np.asarray(fact.L.ranks)
    model = factorization_flop_model(
        op.nb, b, int(ranks.max() or b), 16, fact.stats)
    phases = {k: f"{100*v/model['total']:.1f}%"
              for k, v in model["phases"].items()}
    emit("fig8a/profile", 0.0,
         f"gemm_fraction={model['gemm_fraction']:.3f};{phases}")
    assert model["gemm_fraction"] > 0.7


def bench_pcg():
    """Figures 9/10: fractional-diffusion PCG iterations vs eps."""
    n, b = scaled(2048), 128
    _, Kfd = fractional_diffusion_problem(n, b)
    op = TLROperator.compress(jnp.asarray(Kfd), b, b, 1e-10)
    rhs = jnp.asarray(np.random.default_rng(0).standard_normal(op.n))
    for eps in (1e-1, 1e-2, 1e-4, 1e-6):
        Keps = Kfd + eps * np.eye(op.n)
        op_eps = TLROperator.compress(jnp.asarray(Keps), b, b,
                                      min(eps * 1e-2, 1e-8))
        t_fact, fact = timeit(
            lambda: op_eps.cholesky(CholOptions(eps=eps, bs=16)),
            repeats=1)
        t_solve0 = time.perf_counter()
        x, iters, hist = pcg(op, rhs, precond=fact, tol=1e-6, maxiter=300)
        t_solve = time.perf_counter() - t_solve0
        # check_every batches the host-sync convergence checks (ISSUE 6);
        # the iterate history is bitwise identical, only sync count drops.
        t_b0 = time.perf_counter()
        _, it_b, _ = pcg(op, rhs, precond=fact, tol=1e-6, maxiter=300,
                         check_every=8)
        t_batched = time.perf_counter() - t_b0
        emit(f"fig9/eps{eps:g}", t_fact * 1e6,
             f"cg_iters={iters};residual={hist[-1]:.2e};"
             f"solve_us={t_solve*1e6:.0f};"
             f"batched_sync_us={t_batched*1e6:.0f};batched_iters={it_b}")
        assert it_b == iters


def bench_trsm_old_vs_new():
    """PR 2 solve phase: old host-loop TRSV vs the jitted bucketed TRSM,
    single and batched right-hand sides."""
    n, b = scaled(2048), 128
    K, op = _build(n, 3, b)
    fact = op.cholesky(CholOptions(eps=1e-6, bs=16))
    rng = np.random.default_rng(0)
    for m, rhs in (("1", jnp.asarray(rng.standard_normal(n))),
                   ("16", jnp.asarray(rng.standard_normal((n, 16))))):
        for trans in (False, True):
            t_old, x_old = timeit(
                lambda: tlr_trsv_reference(fact.L, rhs, trans=trans),
                repeats=3)
            t_new, x_new = timeit(
                lambda: tlr_trsv(fact.L, rhs, trans=trans), repeats=3)
            err = float(jnp.max(jnp.abs(x_old - x_new)))
            emit(f"trsm/rhs{m}_trans{int(trans)}", t_new * 1e6,
                 f"old_us={t_old*1e6:.0f};speedup={t_old/t_new:.2f};"
                 f"max_abs_diff={err:.2e}")
    t_solve, _ = timeit(lambda: fact.solve(jnp.asarray(
        rng.standard_normal(n))), repeats=3)
    emit("trsm/full_solve", t_solve * 1e6, "both_triangles+perm")


def bench_solve_plans():
    """ISSUE 6 tentpole: TilePlan-dispatched ranked read paths vs the flat
    r_max-wide paths on a skewed-rank factor (most tiles rank 1-4, a few at
    r_max, some empty), plus the auto policy against both manual modes.

    The factor is synthetic so the skew survives ``BENCH_SCALE``: covariance
    compression at small n produces near-uniform ranks, which is exactly the
    regime the ranked paths are *not* for. The store cap ``r_max`` sits well
    above every detected rank -- the ARA regime the plan layer exists for:
    the flat paths pay the cap, the ranked paths pay the histogram.
    """
    from repro.core.tlr import TLRMatrix, num_tiles

    b = 128
    r_max = 128
    nb = max(16, scaled(4096) // b)
    rng = np.random.default_rng(0)
    nt = num_tiles(nb)
    ranks = rng.integers(1, 5, size=nt).astype(np.int32)
    ranks[rng.permutation(nt)[: max(1, nt // 16)]] = 32
    ranks[rng.permutation(nt)[: max(1, nt // 16)]] = 0
    D = np.tril(rng.standard_normal((nb, b, b)) * 0.1)
    D[:, np.arange(b), np.arange(b)] = 2.0 + rng.random((nb, b))
    U = np.zeros((nt, b, r_max))
    V = np.zeros((nt, b, r_max))
    for t, r in enumerate(ranks):
        U[t, :, : int(r)] = rng.standard_normal((b, int(r))) * 0.1
        V[t, :, : int(r)] = rng.standard_normal((b, int(r))) * 0.1
    L = TLRMatrix(D=jnp.asarray(D), U=jnp.asarray(U), V=jnp.asarray(V),
                  ranks=jnp.asarray(ranks))
    plan = tile_plan(L.ranks, L.r_max)
    n = nb * b
    y1 = jnp.asarray(rng.standard_normal(n))
    y16 = jnp.asarray(rng.standard_normal((n, 16)))

    def _compare(fn, tag):
        times, outs = {}, {}
        for mode in ("flat", "ranked", "auto"):
            times[mode], outs[mode] = timeit(fn, mode, repeats=9, warmup=2)
        err = float(jnp.max(jnp.abs(outs["flat"] - outs["ranked"])))
        best = min(times["flat"], times["ranked"])
        emit(tag, times["ranked"] * 1e6,
             f"flat_us={times['flat']*1e6:.0f};"
             f"speedup={times['flat']/times['ranked']:.2f};"
             f"auto_us={times['auto']*1e6:.0f};"
             f"auto_vs_best_manual={best/times['auto']:.2f};"
             f"max_abs_diff={err:.2e}")

    for m, rhs in (("1", y1), ("16", y16)):
        for trans in (False, True):
            _compare(lambda mode: tlr_trsv(L, rhs, trans=trans,
                                           batching=mode),
                     f"plans/trsm_rhs{m}_trans{int(trans)}")

    Dsym = jnp.asarray(D + np.swapaxes(D, 1, 2))
    A = TLRMatrix(D=Dsym, U=L.U, V=L.V, ranks=L.ranks)
    for m, rhs in (("1", y1), ("16", y16)):
        _compare(lambda mode: tlr_matvec(A, rhs, batching=mode),
                 f"plans/matvec_rhs{m}")

    emit("plans/plan_info", 0.0,
         f"nb={nb};b={b};r_max={r_max};"
         f"widths={sorted(set(int(w) for w in plan.widths if w))};"
         f"rank_skew={plan.rank_skew:.2f};"
         f"padded_flop_ratio={plan.padded_flop_ratio():.2f};"
         f"decision={choose_batching(plan)}")


def bench_rank_vs_svd():
    """Figure 11b: ARA-detected ranks vs optimal SVD ranks at eps=1e-6."""
    n, b = scaled(1024), 128
    K, op = _build(n, 3, b)
    fact = op.cholesky(CholOptions(eps=1e-6, bs=8))
    Ld = np.tril(np.asarray(tlr_to_dense(fact.L.D, fact.L.U, fact.L.V,
                                         fact.L.nb, fact.L.b)))
    nb = op.nb
    ara_total = int(np.asarray(fact.L.ranks).sum())
    svd_total = 0
    for i in range(1, nb):
        for j in range(i):
            blk = Ld[i * b:(i + 1) * b, j * b:(j + 1) * b]
            s = np.linalg.svd(blk, compute_uv=False)
            svd_total += int((s > 1e-6).sum())
    ratio = ara_total / max(svd_total, 1)
    emit("fig11b/ara_vs_svd", 0.0,
         f"ara_ranks={ara_total};svd_ranks={svd_total};ratio={ratio:.3f}")


def bench_pivoting():
    """Figures 12/13 + section 6.3: pivoting effect on ranks/time; LDLT cost."""
    n, b = scaled(1024), 128
    K, op = _build(n, 3, b)
    t0, f0 = timeit(lambda: op.cholesky(CholOptions(eps=1e-6, bs=8)),
                    repeats=1)
    base_rank = float(np.asarray(f0.L.ranks).mean())
    for pivot in ("frobenius", "power"):
        dt, fact = timeit(
            lambda: op.cholesky(CholOptions(eps=1e-6, bs=8, pivot=pivot)),
            repeats=1)
        emit(f"fig12/pivot_{pivot}", dt * 1e6,
             f"avg_rank={np.asarray(fact.L.ranks).mean():.1f};"
             f"base_rank={base_rank:.1f};base_us={t0*1e6:.0f};"
             f"err={_factor_err(K, fact):.2e}")
    dt, fl = timeit(lambda: op.ldlt(CholOptions(eps=1e-6, bs=8)),
                    repeats=1)
    emit("sec6.3/ldlt", dt * 1e6,
         f"chol_us={t0*1e6:.0f};avg_rank={np.asarray(fl.L.ranks).mean():.1f};"
         f"err={_factor_err(K, fl):.2e}")


def bench_left_vs_right():
    """ISSUE 4 tentpole: left-looking (ARA sampling chain) vs right-looking
    (eager trailing updates through the column-scoped SYRK) factorization,
    Cholesky and LDL^T."""
    n, b = scaled(1024), 128
    K, op = _build(n, 3, b)
    for ldl in (False, True):
        make = op.ldlt if ldl else op.cholesky
        name = "ldlt" if ldl else "chol"
        base_us = None
        for algo in ("left", "right"):
            dt, fact = timeit(
                lambda: make(CholOptions(eps=1e-6, bs=8, algo=algo)),
                repeats=1)
            extra = (f"err={_factor_err(K, fact):.2e};"
                     f"avg_rank={np.asarray(fact.L.ranks).mean():.1f};"
                     f"column_traces={fact.stats['column_traces']}")
            if algo == "left":
                base_us = dt * 1e6
            else:
                extra += (f";left_us={base_us:.0f};"
                          f"speedup={base_us/(dt*1e6):.2f};"
                          f"flushes={fact.stats['flushes']}")
            emit(f"rightlook/{name}_{algo}", dt * 1e6, extra)


def bench_lookahead():
    """ISSUE 9: sequential vs lookahead schedule on the right-looking
    driver. Lookahead overlaps column k's wide trailing update with column
    k+1's panel, hiding the adaptive-rank host sync; the rows record the
    end-to-end factor time, the mean per-column wall time, and the host-sync
    gap summed from the ``chol.sync`` telemetry spans."""
    from repro import obs

    n, b = scaled(1024), 128
    K, op = _build(n, 3, b)
    base_us = None
    for lookahead in (False, True):
        opts = CholOptions(eps=1e-6, bs=8, algo="right", lookahead=lookahead)
        op.cholesky(opts)                      # warm the jit caches
        tele = obs.current()
        n0 = len(tele.spans) if tele else 0
        t0 = time.perf_counter()
        fact = op.cholesky(opts)
        dt = time.perf_counter() - t0
        sync_s = sum(
            sp.dur for sp in (tele.spans[n0:] if tele else [])
            if sp.name == "chol.sync")
        col_us = [ev["seconds"] * 1e6 for ev in fact.stats["column_events"]]
        extra = (f"lookahead={int(lookahead)};"
                 f"schedule={fact.stats['schedule']['name']};"
                 f"sync_us={sync_s*1e6:.0f};sync_frac={sync_s/dt:.3f};"
                 f"col_us_mean={np.mean(col_us):.0f};"
                 f"col_us_max={np.max(col_us):.0f};"
                 f"err={_factor_err(K, fact):.2e}")
        if lookahead:
            extra += (f";seq_us={base_us:.0f};"
                      f"speedup={base_us/(dt*1e6):.2f}")
        else:
            base_us = dt * 1e6
        emit(f"lookahead/{'on' if lookahead else 'seq'}", dt * 1e6, extra)


def bench_column_buckets():
    """DESIGN section 2: compile vs steady-state time per column bucket.

    The shape-stable pipeline amortizes ~log2(nb) compiled column-step
    variants over nb columns; ``column_events`` records, per column, its
    (T, J) bucket pair, wall time, and whether the call traced (compiled) a
    fresh executable. Total wall time (compile + run) must beat the seed's
    one-executable-per-column driver on the same problem.
    """
    n, b = scaled(2048), 128
    K, op = _build(n, 3, b)
    t0 = time.perf_counter()
    fact = op.cholesky(CholOptions(eps=1e-6, bs=8))
    total = time.perf_counter() - t0
    ev = fact.stats["column_events"]
    buckets = {}
    for e in ev:
        d = buckets.setdefault((e["Tb"], e["Jb"]),
                               {"compile_s": 0.0, "steady_s": 0.0,
                                "cols": 0, "steady_cols": 0})
        d["cols"] += 1
        if e["traced"]:
            d["compile_s"] += e["seconds"]
        else:
            d["steady_s"] += e["seconds"]
            d["steady_cols"] += 1
    # The record names keep their "dynamic_" prefix: the committed
    # baselines key on them.
    for (Tb, Jb), d in sorted(buckets.items()):
        # steady-state mean; compile-inclusive when the bucket's only
        # columns all traced (e.g. the Tb=1 bucket has one column)
        per_col = (d["steady_s"] / d["steady_cols"] if d["steady_cols"]
                   else (d["compile_s"] / d["cols"]))
        emit(f"pipeline/dynamic_bucket_T{Tb}_J{Jb}", per_col * 1e6,
             f"cols={d['cols']};compile_s={d['compile_s']:.2f};"
             f"steady_s={d['steady_s']:.2f}")
    emit("pipeline/dynamic_total", total * 1e6,
         f"column_traces={fact.stats['column_traces']};"
         f"columns={len(ev)};ladder={fact.stats['bucket_ladder']};"
         f"err={_factor_err(K, fact):.2e}")


def bench_share_omega():
    """DESIGN section 2 beyond-paper optimization: shared-Omega sampling."""
    n, b = scaled(1024), 128
    K, op = _build(n, 3, b)
    for share in (False, True):
        dt, fact = timeit(
            lambda: op.cholesky(CholOptions(eps=1e-6, bs=8,
                                            share_omega=share)),
            repeats=1)
        emit(f"design2/share_omega_{share}", dt * 1e6,
             f"err={_factor_err(K, fact):.2e};"
             f"avg_rank={np.asarray(fact.L.ranks).mean():.1f}")


def bench_flop_rate():
    """Figure 8b analogue: factorization FLOP rate vs this host's measured
    batched-GEMM roofline (the paper plots GPU TLR FLOP/s between its two
    batched-GEMM bounds)."""
    # host matmul roofline: a big f64 matmul
    m = 1024
    X = jnp.asarray(np.random.default_rng(0).standard_normal((m, m)))
    f = jax.jit(lambda a: a @ a)
    dt_mm, _ = timeit(f, X, repeats=3)
    peak = 2 * m**3 / dt_mm
    n, b = scaled(2048), 128
    K, op = _build(n, 3, b)
    dt, fact = timeit(
        lambda: op.cholesky(CholOptions(eps=1e-6, bs=16)), repeats=1)
    ranks = np.asarray(fact.L.ranks)
    model = factorization_flop_model(op.nb, b, int(ranks.max() or b), 16,
                                     fact.stats)
    rate = model["total"] / dt
    emit("fig8b/flop_rate", dt * 1e6,
         f"gflops={rate/1e9:.2f};host_gemm_gflops={peak/1e9:.2f};"
         f"fraction={rate/peak:.3f}")


def bench_algebra_round_axpy():
    """PR 3 tile algebra: batched rounding and low-rank add vs the dense
    equivalents (one QR+SVD pass over all nt tiles, no host loop)."""
    n, b = scaled(1024), 64
    _, K = covariance_problem(n, 3, b)
    Kj = jnp.asarray(K)
    op = TLROperator.compress(Kj, b, b, 1e-9)
    S = tlr_axpy(1.0, op.A, op.A)  # accumulated sum, r_max = 2b
    t_round, R = timeit(lambda: tlr_round(S, 1e-6), repeats=3)
    t_dense, _ = timeit(lambda: jnp.linalg.svd(Kj + Kj), repeats=1)
    emit("algebra/round", t_round * 1e6,
         f"dense_svd_us={t_dense*1e6:.0f};speedup={t_dense/t_round:.2f};"
         f"avg_rank={float(np.asarray(R.ranks).mean()):.1f}")
    t_axpy, _ = timeit(lambda: tlr_axpy(2.0, op.A, op.A, eps=1e-6),
                       repeats=3)
    emit("algebra/axpy_rounded", t_axpy * 1e6,
         f"round_us={t_round*1e6:.0f}")


def bench_algebra_gemm():
    """TLR x TLR product vs the dense GEMM it replaces."""
    n, b = scaled(1024), 64
    _, K = covariance_problem(n, 3, b)
    Kj = jnp.asarray(K)
    op = TLROperator.compress(Kj, b, b, 1e-9)
    t_tlr, C = timeit(lambda: tlr_gemm(op.A, op.A, 1e-6), repeats=3)
    t_dense, want = timeit(lambda: Kj @ Kj, repeats=3)
    err = float(jnp.linalg.norm(C.to_dense() - want) /
                jnp.linalg.norm(want))
    emit("algebra/gemm", t_tlr * 1e6,
         f"dense_us={t_dense*1e6:.0f};speedup={t_dense/t_tlr:.2f};"
         f"rel_err={err:.2e};avg_rank="
         f"{float(np.asarray(C.ranks).mean()):.1f}")


def bench_batching():
    """ISSUE 5 tentpole: rank-bucketed dynamic batching vs flat r_max-wide
    batching on a heterogeneous-rank problem (random-ball covariance, ranks
    spread well below r_max), with the cost_analysis-derived padded-vs-
    useful FLOP ratio of the rounding pass reported alongside wall times.
    """
    from functools import partial

    from repro.core import (
        CholOptions as CO, plan_rank_buckets, tlr_round)
    from repro.core.algebra import _round_factors
    from repro.kernels.ops import flop_estimate

    n, b = scaled(2048), 128
    _, K = covariance_problem(n, 3, b, geometry="ball")
    op = TLROperator.compress(jnp.asarray(K), b, b, 1e-4)
    ranks = np.asarray(op.ranks)
    r_max = op.r_max
    nt = int(ranks.shape[0])
    dtype = op.dtype

    # padded-vs-useful FLOPs of the rounding pass at these exact shapes:
    # the flat core runs all nt tiles at r_max; the ranked path runs each
    # rank bucket at its ladder width (count-padded). XLA's own
    # cost_analysis does the counting, so fusion effects are included.
    eps = jnp.asarray(1e-6, dtype)
    core = partial(_round_factors, r_out=min(r_max, b), rel=False, impl="ref")
    z = jnp.zeros((nt, b, r_max), dtype)
    flops_flat = flop_estimate(core, z, z, eps)
    flops_ranked = 0.0
    plan = plan_rank_buckets(ranks, r_max)
    for bk in plan.buckets:
        zb = jnp.zeros((bk.padded, b, bk.width), dtype)
        corew = partial(_round_factors, r_out=min(min(r_max, b), bk.width),
                        rel=False, impl="ref")
        flops_ranked += flop_estimate(corew, zb, zb, eps)
    ratio = flops_flat / max(flops_ranked, 1.0)

    t_flat, Rf = timeit(lambda: tlr_round(op.A, 1e-6), repeats=3)
    t_rank, Rr = timeit(lambda: tlr_round(op.A, 1e-6, batching="ranked"),
                        repeats=3)
    emit("batching/round", t_rank * 1e6,
         f"flat_us={t_flat*1e6:.0f};speedup={t_flat/t_rank:.2f};"
         f"padded_flop_ratio={ratio:.2f};flops_flat={flops_flat:.3e};"
         f"flops_ranked={flops_ranked:.3e};"
         f"avg_rank={ranks.mean():.1f};r_max={r_max};"
         f"rank_buckets={[bk.width for bk in plan.buckets]};"
         f"zero_tiles={plan.zero_count}")

    for algo in ("right", "left"):
        times = {}
        for batching in ("flat", "ranked", "auto"):
            dt, fact = timeit(
                lambda: op.cholesky(CO(eps=1e-6, bs=8, algo=algo,
                                       batching=batching)), repeats=1)
            times[batching] = dt
            cols = fact.stats["column_events"]
            per_col = (np.mean([e["seconds"] for e in cols if not e["traced"]])
                       if any(not e["traced"] for e in cols) else
                       np.mean([e["seconds"] for e in cols]))
            extra = (f"err={_factor_err(K, fact):.2e};"
                     f"per_col_us={per_col*1e6:.0f};"
                     f"avg_rank={np.asarray(fact.L.ranks).mean():.1f}")
            if batching == "ranked":
                extra += (f";flat_us={times['flat']*1e6:.0f};"
                          f"speedup={times['flat']/dt:.2f}")
                if algo == "right":
                    extra += (f";append_widths="
                              f"{sorted(set(fact.stats['append_widths']))}")
            elif batching == "auto":
                # ISSUE 6: CholOptions(batching="auto") must record its
                # decision in stats and track the best manual setting.
                pol = fact.stats["policy"]
                assert pol["requested"] == "auto"
                assert pol["batching"] in ("flat", "ranked")
                best = min(times["flat"], times["ranked"])
                extra += (f";decision={pol['batching']};"
                          f"rank_skew={pol['rank_skew']:.2f};"
                          f"right_flush={pol['right_flush']};"
                          f"best_manual_us={best*1e6:.0f};"
                          f"auto_vs_best_manual={best/dt:.2f}")
            emit(f"batching/{algo}_{batching}", dt * 1e6, extra)


def bench_newton_schulz():
    """Newton-Schulz TLR inverse as a PCG preconditioner: build time and
    iteration-count reduction on the fractional-diffusion system."""
    n, b = scaled(1024), 64
    _, Kfd = fractional_diffusion_problem(n, b)
    op = TLROperator.compress(jnp.asarray(Kfd), b, b, 1e-10)
    rhs = jnp.asarray(np.random.default_rng(0).standard_normal(n))
    _, it_plain, _ = pcg(op, rhs, tol=1e-6, maxiter=300)
    for iters in (4, 8):
        t_build, (Xop, info) = timeit(
            lambda: tlr_newton_schulz(op, iters=iters, eps=1e-8,
                                      scale="norm"), repeats=1)
        t_solve = time.perf_counter()
        _, it_pre, hist = pcg(op, rhs, precond=Xop, tol=1e-6, maxiter=300)
        t_solve = time.perf_counter() - t_solve
        emit(f"algebra/newton_schulz_{iters}", t_build * 1e6,
             f"cg_iters={it_pre};plain_iters={it_plain};"
             f"residual={hist[-1]:.2e};solve_us={t_solve*1e6:.0f};"
             f"avg_rank={info.avg_rank:.1f}")


def bench_serve():
    """ISSUE 7 tentpole: the continuous-batching inference server.

    A resident TLR factorization (deliberately loose, so ``pcg_solve``
    requests genuinely iterate and occupy slots across ticks) serves a
    mixed queue of solve/logdet/sample/pcg requests through fixed-shape
    ``(n, slots)`` RHS blocks. Reports per-kind p50/p99 latency, slot
    occupancy (asserted >= 0.8 -- the Algorithm 5 high-occupancy claim on
    the serving side), and end-to-end throughput. Warmup happens before
    any submit, so latencies are steady-state (zero recompiles; pinned in
    tests/test_serve.py).
    """
    from repro.serve import KINDS, ServeRequest

    n, b = scaled(2048), 64
    K, op = _build(n, 3, b)
    loose = TLROperator.compress(jnp.asarray(K), b, b, 1e-2)
    fact = loose.cholesky(CholOptions(eps=1e-2, bs=8))
    slots, check_every = 8, 4
    srv = fact.serve(operator=op, slots=slots, check_every=check_every)
    rng = np.random.default_rng(0)
    reqs = []
    for k in range(48):
        kind = KINDS[k % len(KINDS)]
        rhs = (rng.standard_normal(n)
               if kind in ("solve", "pcg_solve") else None)
        reqs.append(ServeRequest(kind, rhs=rhs, tol=1e-6, maxiter=100,
                                 seed=k))
    t0 = time.perf_counter()
    for r in reqs:
        srv.submit(r)
    results = srv.run()
    wall = time.perf_counter() - t0
    st = srv.stats
    occ = st.occupancy()
    assert len(results) == len(reqs)
    assert occ >= 0.8, f"occupancy {occ:.3f} < 0.8 on the bench schedule"
    for kind in KINDS:
        p = st.latency_percentiles(kind)
        if p["count"]:
            emit(f"serve/{kind}", p["p50_s"] * 1e6,
                 f"p50_us={p['p50_s']*1e6:.0f};p99_us={p['p99_s']*1e6:.0f};"
                 f"mean_us={p['mean_s']*1e6:.0f};count={p['count']}")
        else:
            # Zero completions of this kind: percentiles are null (the
            # ServerStats contract), recorded as such instead of a crash.
            emit(f"serve/{kind}", 0.0,
                 "p50_us=null;p99_us=null;mean_us=null;count=0")
    pall = st.latency_percentiles()
    if pall["count"]:
        emit("serve/latency_all", pall["p50_s"] * 1e6,
             f"p50_us={pall['p50_s']*1e6:.0f};"
             f"p99_us={pall['p99_s']*1e6:.0f};count={pall['count']}")
    else:
        emit("serve/latency_all", 0.0, "p50_us=null;p99_us=null;count=0")
    emit("serve/occupancy", 0.0,
         f"occupancy={occ:.3f};slots={slots};ticks={st.ticks};"
         f"admitted={st.admitted};completed={st.completed}")
    emit("serve/throughput", wall * 1e6,
         f"requests_per_s={len(reqs)/wall:.1f};wall_s={wall:.3f};"
         f"check_every={check_every}")
    pcg_res = [results[r.rid] for r in reqs if r.kind == "pcg_solve"]
    iters = [r.iterations for r in pcg_res]
    if iters:
        emit("serve/pcg_requests", 0.0,
             f"mean_iters={np.mean(iters):.1f};max_iters={max(iters)};"
             f"converged={sum(r.converged for r in pcg_res)}/{len(pcg_res)}")
    else:
        emit("serve/pcg_requests", 0.0,
             "mean_iters=null;max_iters=null;converged=0/0")


def bench_faults():
    """ISSUE 10: the robustness subsystem under the deterministic fault
    harness (``repro.faults``).

    Four groups of records:

    * ``faults/overhead_*`` -- wall-time cost of ``CholOptions(check=True)``
      on a *clean* factorization, per driver (the ISSUE 10 acceptance gate:
      <= 3% over the unchecked path; CI asserts on ``overhead_pct``);
    * ``faults/recover_*`` -- injected breakdowns that must *recover*:
      an indefinite diagonal tile (jitter ladder, both drivers) and a
      genuine rank spike under a hard cap (eps-loosen/densify ladder);
      each record asserts finite factors and counts the recorded
      ``HealthEvent``s;
    * ``faults/breakdown_detect`` -- an unrecoverable NaN diagonal must
      raise :class:`FactorizationBreakdown` (never return NaN factors);
    * ``faults/serve_*`` -- the serve-side guards: submit-time rejection
      of a non-finite RHS, poisoned-column isolation inside a co-batched
      solve block, and deadline eviction of a stalled request.
    """
    from repro import faults
    from repro.core import (
        FactorizationBreakdown, from_dense, tlr_cholesky,
    )
    from repro.serve import RequestRejected, ServeRequest

    n, b = scaled(2048), 64
    nb = n // b
    _, K = covariance_problem(n, 3, b)
    A = from_dense(jnp.asarray(K), b, b, 1e-9)

    # -- detection overhead on the clean path (both drivers) -----------------
    # Interleaved min-of-N wall times: the min is the standard noise-robust
    # estimator, and alternating the two variants cancels machine drift --
    # a median-of-3 A/B at quick-lane scale swings +-10% run to run, far
    # above the 3% gate this record feeds.
    for algo in ("left", "right"):
        off = CholOptions(eps=1e-6, bs=8, algo=algo)
        on = CholOptions(eps=1e-6, bs=8, algo=algo, check=True)
        fact = tlr_cholesky(A, on)          # warm both executables
        tlr_cholesky(A, off)
        t_off, t_on = [], []
        for _ in range(5):
            t0 = time.perf_counter()
            tlr_cholesky(A, off)
            t_off.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            tlr_cholesky(A, on)
            t_on.append(time.perf_counter() - t0)
        h = fact.stats["health"]
        ks = fact.stats["schedule"]["kind_seconds"]
        check_s = ks.get("check", 0.0)
        emit(f"faults/overhead_{algo}", min(t_on) * 1e6,
             f"clean_us={min(t_off)*1e6:.0f};"
             f"overhead_pct={(min(t_on)/min(t_off) - 1)*100:.2f};"
             f"check_stage_s={check_s:.4f};"
             f"columns_checked={h['columns_checked']};"
             f"events={len(h['events'])}")

    # -- recovery: indefinite diagonal tile -> jitter ladder -----------------
    Abad = faults.make_diag_indefinite(A, nb // 2, magnitude=4.0)
    for algo in ("left", "right"):
        t, fact = timeit(
            lambda: tlr_cholesky(Abad, CholOptions(eps=1e-6, bs=8, algo=algo,
                                                   check=True)),
            repeats=1)
        h = fact.stats["health"]
        spd = [e for e in h["events"] if e["kind"] == "spd_breakdown"]
        finite = all(bool(np.isfinite(np.asarray(x)).all())
                     for x in (fact.L.D, fact.L.U, fact.L.V))
        assert finite and spd, \
            f"indefinite-diag recovery failed ({algo}): " \
            f"finite={finite}, spd events={len(spd)}"
        remedies = ",".join(sorted({e["remedy"] for e in spd}))
        emit(f"faults/recover_indefinite_{algo}", t * 1e6,
             f"recovered=1;spd_events={len(spd)};remedies={remedies};"
             f"total_events={len(h['events'])}")

    # -- recovery: rank spike under a hard cap -> eps-loosen/densify ---------
    # 1-D covariance (rank-1 off-diagonal tiles) so the spiked tile is the
    # only thing near the cap; fixed size -- the recipe is calibrated.
    _, K1 = covariance_problem(256, 1, 32)
    A1 = from_dense(jnp.asarray(K1), 32, 32, 1e-10)
    # scale calibrated so BOTH ladders engage: the left driver needs two
    # eps-loosening re-passes, the right driver's SVD-optimal rounding
    # accepts within the policy floor (a smaller spike never overflows the
    # right driver at all -- its truncation is already optimal).
    A1s = faults.spike_rank(A1, 4, 1, seed=3, scale=3e-4)
    for algo in ("left", "right"):
        t, fact = timeit(
            lambda: tlr_cholesky(A1s, CholOptions(eps=1e-6, bs=8,
                                                  r_max_out=16, algo=algo,
                                                  check=True)),
            repeats=1)
        h = fact.stats["health"]
        overflow = [e for e in h["events"] if e["kind"] == "rank_overflow"]
        finite = all(bool(np.isfinite(np.asarray(x)).all())
                     for x in (fact.L.D, fact.L.U, fact.L.V))
        assert finite and overflow, \
            f"rank-spike recovery failed ({algo}): finite={finite}, " \
            f"overflow events={len(overflow)}"
        remedies = ",".join(sorted({e["remedy"] for e in overflow}))
        emit(f"faults/recover_rankspike_{algo}", t * 1e6,
             f"recovered=1;overflow_events={len(overflow)};"
             f"remedies={remedies}")

    # -- unrecoverable fault -> structured breakdown, never NaN factors ------
    for algo in ("left", "right"):
        detected = 0
        t0 = time.perf_counter()
        with faults.inject(faults.Fault(site="chol.diag", kind="nan",
                                        column=nb // 2)):
            try:
                tlr_cholesky(A, CholOptions(eps=1e-6, bs=8, algo=algo,
                                            check=True))
            except FactorizationBreakdown as e:
                detected = 1
                col = e.report.column
        t = time.perf_counter() - t0
        assert detected, f"NaN diag not detected ({algo})"
        emit(f"faults/breakdown_detect_{algo}", t * 1e6,
             f"detected=1;column={col};injected_at={nb // 2}")

    # -- serve-side degradation guards ---------------------------------------
    ns, bsrv = scaled(1024), 64
    Ks, ops = _build(ns, 3, bsrv)
    fact = ops.cholesky(CholOptions(eps=1e-6, bs=8))
    srv = fact.serve(operator=ops, slots=4, check_every=4)
    rng = np.random.default_rng(0)
    bad = rng.standard_normal(ns)
    bad[7] = np.nan
    t0 = time.perf_counter()
    try:
        srv.submit(ServeRequest("solve", rhs=bad))
        rejected = 0
    except RequestRejected:
        rejected = 1
    t_reject = time.perf_counter() - t0
    assert rejected, "non-finite RHS was admitted"
    r1 = ServeRequest("solve", rhs=rng.standard_normal(ns))
    r2 = ServeRequest("solve", rhs=rng.standard_normal(ns))
    i1, i2 = srv.submit(r1), srv.submit(r2)
    with faults.inject(faults.Fault(site="serve.solve", rid=i1)):
        results = srv.run()
    ok_iso = (not results[i1].ok
              and results[i1].error == "nonfinite_result"
              and results[i2].ok
              and bool(np.isfinite(results[i2].value).all()))
    assert ok_iso, "poisoned column leaked into the co-batched block"
    r3 = ServeRequest("solve", rhs=rng.standard_normal(ns),
                      deadline_ticks=2)
    r4 = ServeRequest("solve", rhs=rng.standard_normal(ns))
    i3, i4 = srv.submit(r3), srv.submit(r4)
    with faults.inject(faults.Fault(site="serve.admit", rid=i3, delay=6)):
        results = srv.run(max_ticks=10)
    assert results[i3].error == "timeout" and results[i4].ok, \
        "deadline eviction failed or took down a healthy request"
    hs = srv.stats.summary()["health"]
    emit("faults/serve_guards", t_reject * 1e6,
         f"rejected={hs['rejected']};isolated={hs['errors']};"
         f"timeouts={hs['timeouts']};co_batched_ok=1")


ALL = [
    bench_tile_size, bench_memory_growth, bench_rank_distributions,
    bench_compress, bench_factor_time, bench_profile, bench_pcg,
    bench_trsm_old_vs_new, bench_solve_plans, bench_rank_vs_svd,
    bench_pivoting, bench_left_vs_right, bench_lookahead,
    bench_column_buckets, bench_share_omega,
    bench_flop_rate,
    bench_algebra_round_axpy, bench_algebra_gemm, bench_newton_schulz,
    bench_batching, bench_serve, bench_faults,
]

SUITES = {
    "all": ALL,
    "build": [bench_compress, bench_memory_growth, bench_rank_distributions],
    "factor": [bench_tile_size, bench_factor_time, bench_profile,
               bench_pivoting, bench_left_vs_right, bench_lookahead,
               bench_column_buckets,
               bench_share_omega, bench_flop_rate, bench_batching],
    "solve": [bench_trsm_old_vs_new, bench_solve_plans, bench_pcg],
    "algebra": [bench_algebra_round_axpy, bench_algebra_gemm,
                bench_newton_schulz],
    "batching": [bench_batching],
    "plans": [bench_solve_plans],
    "serve": [bench_serve],
    "faults": [bench_faults],
}


def main() -> None:
    import argparse

    from repro import obs

    ap = argparse.ArgumentParser()
    ap.add_argument("--suite", default="all", choices=sorted(SUITES))
    ap.add_argument("--json", default=None,
                    help="machine-readable output path "
                         "(default: BENCH_<suite>.json in the cwd)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="also export the run's telemetry as Chrome-trace/"
                         "Perfetto JSON (load at ui.perfetto.dev)")
    args = ap.parse_args()
    # Every bench records under telemetry so the JSON carries the per-phase
    # FLOP/s snapshot (and compare.py can diff it); --trace additionally
    # keeps the full span timeline as a Perfetto file.
    obs.enable()
    for fn in SUITES[args.suite]:
        fn()
    obs.record_retraces()
    snapshot = obs.metrics_snapshot()
    if args.trace:
        obs.export_chrome_trace(args.trace)
        print(f"telemetry trace -> {args.trace}")
    obs.disable()
    write_json(args.json or f"BENCH_{args.suite}.json",
               meta={"suite": args.suite, "telemetry": snapshot})


if __name__ == "__main__":
    main()
