"""Quickstart: build a spatial-statistics covariance matrix, factor it in
TLR form with ARA, solve, and sample -- the paper's core workflow, through
the operator-first API (compress -> factor -> solve/logdet/sample).

Run:  PYTHONPATH=src python examples/quickstart.py [--n 2048] [--eps 1e-6]
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np

jax.config.update("jax_enable_x64", True)

from repro.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()

from repro.core import CholOptions, TLROperator, covariance_problem  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=2048)
    ap.add_argument("--tile", type=int, default=128)
    ap.add_argument("--eps", type=float, default=1e-6)
    ap.add_argument("--dim", type=int, default=3)
    args = ap.parse_args()

    print(f"building {args.dim}D exponential covariance, N={args.n}, "
          f"tile={args.tile}")
    pts, K = covariance_problem(args.n, args.dim, args.tile)
    op = TLROperator.compress(jnp.asarray(K), args.tile, eps=args.eps * 1e-2)
    mem = op.memory_stats()
    print(f"TLR memory: {mem['total_bytes_logical']/2**20:.1f} MiB "
          f"(dense {mem['full_dense_bytes']/2**20:.1f} MiB = "
          f"{mem['dense_equivalent_gb']:.3f} GiB, "
          f"compression {mem['compression_ratio']:.1f}x, "
          f"avg rank {mem['avg_rank']:.1f})")

    print(f"factoring with ARA Cholesky (eps={args.eps})")
    fact = op.cholesky(CholOptions(eps=args.eps, bs=16))
    ranks = np.asarray(fact.L.ranks)
    print(f"factor ranks: avg {ranks.mean():.1f}, max {ranks.max()}")

    rng = np.random.default_rng(0)
    x_true = rng.standard_normal(args.n)
    y = jnp.asarray(K @ x_true)
    x = fact.solve(y)
    rel = float(jnp.linalg.norm(x - x_true) / np.linalg.norm(x_true))
    print(f"solve relative error: {rel:.2e}")

    # batched right-hand sides go through the same jitted TRSM
    Y = jnp.asarray(K @ rng.standard_normal((args.n, 4)))
    X = fact.solve(Y)
    print(f"batched solve: rhs {Y.shape} -> {X.shape}")

    ld = float(fact.logdet())
    _, ld_ref = np.linalg.slogdet(K)
    print(f"logdet: {ld:.4f} (dense {ld_ref:.4f})")

    s = fact.sample(jax.random.PRNGKey(0), num=2)
    print(f"MVN samples: shape {s.shape}, std {float(jnp.std(s)):.3f}")

    r = op @ x - y
    print(f"matvec residual check: {float(jnp.linalg.norm(r)):.2e}")


if __name__ == "__main__":
    main()
