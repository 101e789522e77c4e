"""End-to-end driver for the paper's section 6.2 experiment: factor an
ill-conditioned 3D fractional-diffusion operator at low accuracy and use it
as a PCG preconditioner. ``pcg`` consumes the handles directly: the
``TLROperator`` is the matvec, the ``TLRFactorization`` the preconditioner.

Beyond the paper, the tile algebra of PR 3 adds a second preconditioner
family: a Newton-Schulz TLR approximate inverse (core/precond.py), built
from ``tlr_gemm`` + ``tlr_axpy`` + rounding alone -- no factorization --
whose ``.matvec`` plugs into the same ``pcg`` slot.

Run:  PYTHONPATH=src python examples/fractional_diffusion_pcg.py [--n 2048]
      ... --suite ns --check     # Newton-Schulz only + CI assertion
      ... --device --n 32768 --tile 512 --suite cholesky   # chip size

``--device`` builds the operator with ``fractional_diffusion_device`` in
f32 with x64 off (no host matrix, so it runs at chip sizes), compresses it
with ARA at 1e-4 and factors it at eps 1e-2 as PCG's preconditioner, as
the benchmark's ``fracdiff3d-pcg`` cell does.
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()

from repro.core import (  # noqa: E402
    CholOptions, TLROperator, fractional_diffusion_device,
    fractional_diffusion_problem, grid_points, kd_tree_ordering, pcg,
    tlr_newton_schulz,
)


def run_cholesky(op, Kfd, rhs, args):
    print(f"{'eps':>8} {'factor_s':>9} {'cg_iters':>8} {'residual':>10}")
    for eps in (1e-1, 1e-2, 1e-4, 1e-6):
        # paper: factor A + eps*I to preserve definiteness at loose eps
        Keps = Kfd + eps * np.eye(args.n)
        op_eps = TLROperator.compress(jnp.asarray(Keps), args.tile,
                                      eps=min(eps * 1e-2, 1e-8))
        t0 = time.perf_counter()
        fact = op_eps.cholesky(CholOptions(eps=eps, bs=16, schur="diag"))
        t_fact = time.perf_counter() - t0
        x, iters, hist = pcg(op, rhs, precond=fact, tol=1e-6, maxiter=300)
        print(f"{eps:>8g} {t_fact:>9.2f} {iters:>8d} {hist[-1]:>10.2e}")


def build_on_device(args):
    """The operator in f32 on the device (KD-ordered grid, normalized to a
    unit largest diagonal), compressed with ARA at rank cap tile / 4; the
    dense matrix is dropped."""
    pts = grid_points(args.n, 3)
    pts = pts[kd_tree_ordering(pts, args.tile)]
    K = fractional_diffusion_device(pts, dtype=jnp.float32, rows=args.tile,
                                    normalize=True)
    op = TLROperator.compress(K, args.tile, args.tile // 4, 1e-4,
                              method="ara", bs=16)
    jax.block_until_ready(op.A.U)
    return op


def run_device_cholesky(op, rhs):
    print(f"{'eps':>8} {'factor_s':>9} {'cg_iters':>8} {'solve_s':>8} "
          f"{'residual':>10}")
    for eps in (1e-1, 1e-2):
        t0 = time.perf_counter()
        fact = op.cholesky(CholOptions(eps=eps, bs=16))
        jax.block_until_ready(fact.L.U)
        t_fact = time.perf_counter() - t0
        t0 = time.perf_counter()
        x, iters, hist = pcg(op, rhs, precond=fact, tol=1e-6, maxiter=300)
        jax.block_until_ready(x)
        print(f"{eps:>8g} {t_fact:>9.2f} {iters:>8d} "
              f"{time.perf_counter() - t0:>8.2f} {hist[-1]:>10.2e}")


def run_newton_schulz(op, rhs, it_plain, args):
    print(f"{'ns_iters':>8} {'build_s':>9} {'cg_iters':>8} {'residual':>10}"
          f" {'avg_rank':>8}")
    best = it_plain
    for ns_iters in sorted({4, args.ns_iters}):
        t0 = time.perf_counter()
        # norm scaling (alpha = 1/||A||_2 est) compresses the condition
        # number by ~2^iters; trace scaling is the always-safe default
        Xop, info = tlr_newton_schulz(op, iters=ns_iters, eps=args.ns_eps,
                                      scale="norm")
        t_build = time.perf_counter() - t0
        x, iters, hist = pcg(op, rhs, precond=Xop, tol=1e-6, maxiter=300)
        print(f"{ns_iters:>8d} {t_build:>9.2f} {iters:>8d} {hist[-1]:>10.2e}"
              f" {info.avg_rank:>8.1f}")
        best = min(best, iters)
    if args.check:
        assert best < it_plain, (
            f"Newton-Schulz PCG ({best} iters) did not beat "
            f"unpreconditioned PCG ({it_plain} iters)")
        print(f"check OK: {best} < {it_plain} unpreconditioned iters")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=2048)
    ap.add_argument("--tile", type=int, default=128)
    ap.add_argument("--suite", default="all",
                    choices=("all", "cholesky", "ns"))
    ap.add_argument("--ns-iters", type=int, default=8)
    ap.add_argument("--ns-eps", type=float, default=1e-8)
    ap.add_argument("--check", action="store_true",
                    help="assert the Newton-Schulz preconditioner reduces "
                         "PCG iterations (CI examples-smoke)")
    ap.add_argument("--device", action="store_true",
                    help="build the operator on the device in f32 "
                         "(fractional_diffusion_device), x64 off")
    args = ap.parse_args()
    jax.config.update("jax_enable_x64", not args.device)

    if args.device:
        print(f"building 3D fractional-diffusion operator on "
              f"{jax.devices()[0].platform}, N={args.n}, f32")
        op = build_on_device(args)
        rhs = jax.random.normal(jax.random.PRNGKey(0), (args.n,),
                                jnp.float32)
        if args.suite in ("all", "cholesky"):
            run_device_cholesky(op, rhs)
    else:
        print(f"building 3D fractional-diffusion matrix, N={args.n}")
        _, Kfd = fractional_diffusion_problem(args.n, args.tile)
        cond = np.linalg.cond(Kfd) if args.n <= 4096 else float("nan")
        print(f"condition number ~ {cond:.2e}")
        op = TLROperator.compress(jnp.asarray(Kfd), args.tile, eps=1e-10)
        rhs = jnp.asarray(np.random.default_rng(0).standard_normal(args.n))
        if args.suite in ("all", "cholesky"):
            run_cholesky(op, Kfd, rhs, args)

    _, it_plain, hist = pcg(op, rhs, tol=1e-6, maxiter=300)
    print(f"unpreconditioned CG: {it_plain} iters, residual {hist[-1]:.2e}")

    if args.suite in ("all", "ns"):
        run_newton_schulz(op, rhs, it_plain, args)


if __name__ == "__main__":
    main()
