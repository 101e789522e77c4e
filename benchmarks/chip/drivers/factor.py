"""Traffic kind ``factor``: repeated factorization of one compressed
operator, as a user who refits a model factors it again and again.

Set-up builds the configuration's dense covariance from the seed on the
device, compresses it, and factors it once with the traffic's options
(which compiles, or loads, every program the window runs). The window
factors again with the same options and seed, so no shape changes: a
factorization starts while the time left exceeds the previous one's
duration. ``factor_s`` is the time from the window's start to the end of
its last factorization over the number of factorizations.

With ``--trace 1`` the window is one traced factorization.

The check, after the window: the compressed operator against the dense
matrix; the last factorization's logdet against a blocked dense f32
Cholesky; its solves of 1 and 16 right-hand sides against the dense
matrix (normwise backward error).
"""

from __future__ import annotations

import time

import numpy as np

import bench
import problem


def chol_options(ctx):
    from repro.core import CholOptions

    cfg, traffic = ctx.cfg, ctx.traffic
    return CholOptions(eps=cfg["eps"], bs=cfg["bs"],
                       seed=bench.seed32(ctx.seed, 1),
                       **traffic.get("options", {}))


def compress(K, ctx):
    import jax
    from repro.core import TLROperator

    cfg = ctx.cfg
    return TLROperator.compress(K, cfg["tile"], cfg["r_max"],
                                cfg["compress_eps"], method="ara",
                                bs=cfg["bs"],
                                key=jax.random.PRNGKey(bench.seed32(ctx.seed,
                                                                    2)))


def setup_operator(ctx):
    """Points from the seed, the dense covariance on the device, its TLR
    compression; the dense matrix is dropped."""
    import jax

    pts = problem.points(ctx.cfg, ctx.seed)
    K = problem.dense_covariance(pts, ctx.cfg)
    op = compress(K, ctx)
    jax.block_until_ready((op.A.D, op.A.U, op.A.V, op.A.ranks))
    del K
    return pts, op


def factor(op, opts, method: str):
    import jax

    fact = getattr(op, method)(opts)
    jax.block_until_ready(fact)
    return fact


def run(ctx) -> None:
    method = ctx.traffic.get("method", "cholesky")
    opts = chol_options(ctx)
    pts, op = setup_operator(ctx)
    t0 = time.perf_counter()
    fact = factor(op, opts, method)
    bench.log(f"warm-up factorization: {time.perf_counter() - t0:.3f} s, "
              f"batching {fact.stats.get('batching')}")
    ctx.end_setup()

    durations = []
    fact = None
    t_start = time.perf_counter()
    with ctx.traced():
        while True:
            left = ctx.seconds - (time.perf_counter() - t_start)
            if durations and (ctx.trace or left <= durations[-1]):
                break
            fact = None                      # free the previous factor
            t0 = time.perf_counter()
            fact = factor(op, opts, method)
            durations.append(time.perf_counter() - t0)
            ctx.readings.factor_stats.append(fact.stats)
        t_end = time.perf_counter()
    ctx.result.attempted = len(durations)
    ctx.e2e["factor_s"] = (t_end - t_start) / len(durations)
    bench.log(f"window: {len(durations)} factorizations "
              f"{[round(d, 3) for d in durations]}, factor_s "
              f"{ctx.e2e['factor_s']:.4f}")
    ctx.end_window()
    ctx.readings.factor_ranks = np.asarray(fact.L.ranks)
    ctx.readings.factor_shape = {"nb": fact.L.nb, "b": fact.L.b,
                                 "bs": opts.bs}
    check(ctx, pts, op, fact)


def check(ctx, pts, op, fact) -> None:
    """Compare the compressed operator and the last factorization with
    the dense matrix, rebuilt from the seed."""
    import jax
    import jax.numpy as jnp

    res, lim, n = ctx.result, ctx.limits, ctx.cfg["n"]
    t0 = time.perf_counter()
    K = problem.dense_covariance(pts, ctx.cfg)
    ref = problem.dense_reference(K)
    key = jax.random.PRNGKey(bench.seed32(ctx.seed, 3))
    z = jax.random.normal(jax.random.fold_in(key, 0), (n, 4), jnp.float32)
    az = problem.matmul_dense(K, z)
    A = op.A
    err = problem.tlr_apply(A.D, A.U, A.V, A.ranks, z) - az
    res.check("compress_err",
              float(jnp.linalg.norm(err) / jnp.linalg.norm(az)),
              lim["compress_err"])
    ld = float(fact.logdet())
    res.check("logdet_rel", abs(ld - ref["logdet"]) / abs(ref["logdet"]),
              lim["logdet_rel"])
    for nrhs in (1, 16):
        y = jax.random.normal(jax.random.fold_in(key, nrhs), (n, nrhs),
                              jnp.float32)
        x = fact.solve(y[:, 0] if nrhs == 1 else y).reshape(n, nrhs)
        be = problem.backward_error(K, ref["norm2"], x, y)
        res.check(f"solve{nrhs}_berr", float(np.max(be)),
                  lim[f"solve{nrhs}_berr"])
    res.failed = 0 if res.correct() else res.attempted
    bench.log(f"check: {time.perf_counter() - t0:.3f} s (logdet "
              f"{ld!r}, dense {ref['logdet']!r})")
