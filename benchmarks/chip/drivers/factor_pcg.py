"""Traffic kind ``factor_pcg``: factor an ill-conditioned operator at a
loose eps and use the factor as the preconditioner of PCG, again and
again, as a user who refits and solves does (the paper's section 6.2).

Set-up builds the configuration's operator on the device with the
library's generator (``repro.core.fractional_diffusion_device``),
compresses it, drops the dense matrix, checks the compression against the
benchmark's own operator (``fracdiff_ref``), and runs one warm-up cycle
(which compiles, or loads, every program the window runs). A cycle is one
factorization with the traffic's options, then ``pcg(op, y,
precond=fact)`` at the traffic's tolerance with the library's default
``check_every``; ``y`` is one standard-normal right-hand side from the
seed. Cycles run back to back: one starts while the time left exceeds the
previous one's duration. ``factor_s`` is the time from the window's start
to the end of its last cycle over the number of cycles: seconds per
factor-and-solve cycle.

With ``--trace 1`` the window is one traced cycle; its factorization's
stats feed the factor metrics and its PCG history (``readings.pcg_history``)
the ``pcg.*`` metrics.

The check, after the window, against the operator rebuilt by the
benchmark: the last factor's ``||(A - L L^T) z|| / ||A z||``; the last
solve's normwise backward error; whether that solve reached its tolerance.
A program without the generator cannot run the cell: the run exits with
code 2 and prints no result.
"""

from __future__ import annotations

import time

import numpy as np

import bench
import fracdiff_ref
import problem
from drivers import factor


def _generator():
    """The library's on-device generator, or exit 2 where the program
    has none."""
    try:
        from repro.core import fractional_diffusion_device
    except ImportError as e:
        bench.log(f"the program cannot build this cell's operator: {e}")
        raise SystemExit(2) from None
    return fractional_diffusion_device


def _probe(ctx, n: int):
    """Four seeded vectors for the operator checks."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(bench.seed32(ctx.seed, 3))
    return jax.random.normal(key, (n, 4), jnp.float32)


def setup_operator(ctx, generator):
    """The operator from the library's generator on the device, its TLR
    compression, and the compression's check against the benchmark's own
    operator; neither dense matrix is kept."""
    import jax
    import jax.numpy as jnp

    cfg = ctx.cfg
    pts = fracdiff_ref.points(cfg)
    A = generator(pts, cfg["s"], cfg["mass"], dtype=jnp.float32,
                  normalize=cfg["normalize"])
    op = factor.compress(A, ctx)
    jax.block_until_ready((op.A.D, op.A.U, op.A.V, op.A.ranks))
    del A
    K = fracdiff_ref.dense_operator(pts, cfg)
    z = _probe(ctx, cfg["n"])
    az = problem.matmul_dense(K, z)
    err = problem.tlr_apply(op.A.D, op.A.U, op.A.V, op.A.ranks, z) - az
    ctx.result.check("compress_err",
                     float(jnp.linalg.norm(err) / jnp.linalg.norm(az)),
                     ctx.limits["compress_err"])
    del K
    return pts, op


def cycle(op, opts, y, pcg_opts):
    """One factorization, then PCG preconditioned by it, to completion."""
    import jax
    from repro.core import pcg

    fact = factor.factor(op, opts, "cholesky")
    x, _, hist = pcg(op, y, precond=fact, **pcg_opts)
    jax.block_until_ready(x)
    return fact, x, hist


def run(ctx) -> None:
    import jax
    import jax.numpy as jnp

    generator = _generator()
    opts = factor.chol_options(ctx)
    pcg_opts = ctx.traffic["pcg"]
    pts, op = setup_operator(ctx, generator)
    y = jax.random.normal(jax.random.PRNGKey(bench.seed32(ctx.seed, 4)),
                          (ctx.cfg["n"],), jnp.float32)
    t0 = time.perf_counter()
    fact, x, hist = cycle(op, opts, y, pcg_opts)
    bench.log(f"warm-up cycle: {time.perf_counter() - t0:.3f} s, batching "
              f"{fact.stats.get('batching')}, pcg {len(hist) - 1} "
              f"iterations, residual {hist[-1]!r}")
    ctx.end_setup()

    durations = []
    fact = x = hist = None
    t_start = time.perf_counter()
    with ctx.traced():
        while True:
            left = ctx.seconds - (time.perf_counter() - t_start)
            if durations and (ctx.trace or left <= durations[-1]):
                break
            fact = x = hist = None           # free the previous factor
            t0 = time.perf_counter()
            fact, x, hist = cycle(op, opts, y, pcg_opts)
            durations.append(time.perf_counter() - t0)
            ctx.readings.factor_stats.append(fact.stats)
        t_end = time.perf_counter()
    ctx.result.attempted = len(durations)
    ctx.e2e["factor_s"] = (t_end - t_start) / len(durations)
    bench.log(f"window: {len(durations)} cycles "
              f"{[round(d, 3) for d in durations]}, factor_s "
              f"{ctx.e2e['factor_s']:.4f}, pcg {len(hist) - 1} iterations")
    ctx.end_window()
    ctx.readings.factor_ranks = np.asarray(fact.L.ranks)
    ctx.readings.factor_shape = {"nb": fact.L.nb, "b": fact.L.b,
                                 "bs": opts.bs}
    ctx.readings.pcg_history = hist
    check(ctx, pts, fact, x, y, hist)


def unconverged(hist, tol: float) -> float:
    """1 where PCG stopped without reaching ``tol`` (breakdown or
    ``maxiter``), else 0."""
    ok = hist.breakdown is None and len(hist) > 0 and hist[-1] < tol
    return 0.0 if ok else 1.0


def check(ctx, pts, fact, x, y, hist) -> None:
    """Compare the last factor and the last solve with the operator
    rebuilt by the benchmark."""
    import jax.numpy as jnp

    res, lim, n = ctx.result, ctx.limits, ctx.cfg["n"]
    t0 = time.perf_counter()
    K = fracdiff_ref.dense_operator(pts, ctx.cfg)
    z = _probe(ctx, n)
    az = problem.matmul_dense(K, z)
    # L L^T approximates P A P^T, with (P v) = v[eperm].
    eperm = (np.asarray(fact.perm)[:, None] * fact.L.b
             + np.arange(fact.L.b)[None, :]).reshape(-1)
    paz = problem.matmul_dense(K, jnp.zeros_like(z).at[eperm].set(z))[eperm]
    L = fact.L
    llz = problem.tlr_apply(L.D, L.U, L.V, L.ranks,
                            fracdiff_ref.lower_t_apply(L.D, L.U, L.V,
                                                       L.ranks, z),
                            lower=True)
    res.check("factor_err",
              float(jnp.linalg.norm(llz - paz) / jnp.linalg.norm(az)),
              lim["factor_err"])
    norm2 = fracdiff_ref.norm2(K)
    be = problem.backward_error(K, norm2, x.reshape(n, 1), y.reshape(n, 1))
    res.check("pcg_berr", float(np.max(be)), lim["pcg_berr"])
    res.check("pcg_unconverged", unconverged(hist, ctx.traffic["pcg"]["tol"]),
              lim["pcg_unconverged"])
    res.failed = 0 if res.correct() else res.attempted
    bench.log(f"check: {time.perf_counter() - t0:.3f} s (||A||_2 {norm2!r}, "
              f"pcg {len(hist) - 1} iterations to {hist[-1]!r}, breakdown "
              f"{hist.breakdown})")
