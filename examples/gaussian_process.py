"""Gaussian-process workflow on a TLR-factored covariance: log-likelihood
evaluation and posterior sampling (the paper's spatial-statistics use case),
through the operator-first API -- the correlation-length sweep builds each
candidate operator directly from the point cloud with
``TLROperator.from_kernel``.

Run:  PYTHONPATH=src python examples/gaussian_process.py [--n 2048]
      [--trace out.json]   # Perfetto trace of the whole workflow
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np

jax.config.update("jax_enable_x64", True)

from repro.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()

from repro import obs  # noqa: E402
from repro.core import CholOptions, TLROperator, covariance_problem  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=2048)
    ap.add_argument("--tile", type=int, default=128)
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="record telemetry and write a Chrome-trace / "
                         "Perfetto JSON (load at ui.perfetto.dev)")
    args = ap.parse_args()

    if args.trace:
        obs.enable()

    pts, K = covariance_problem(args.n, 2, args.tile, geometry="ball", seed=3)
    op = TLROperator.compress(jnp.asarray(K), args.tile, eps=1e-8)
    fact = op.cholesky(CholOptions(eps=1e-6, bs=16))

    # draw a "true" field and observe it
    y = fact.sample(jax.random.PRNGKey(1))
    print(f"sampled GP field: n={args.n}, std={float(jnp.std(y)):.3f}")

    # log-likelihood:  -0.5 (y^T K^{-1} y + logdet K + n log 2pi)
    alpha = fact.solve(y)
    ll = -0.5 * (float(y @ alpha) + float(fact.logdet())
                 + args.n * np.log(2 * np.pi))
    # dense reference
    ll_ref = -0.5 * (y @ np.linalg.solve(K, np.asarray(y))
                     + np.linalg.slogdet(K)[1] + args.n * np.log(2 * np.pi))
    print(f"TLR log-likelihood:   {ll:.3f}")
    print(f"dense log-likelihood: {float(ll_ref):.3f}")
    print(f"abs diff: {abs(ll - float(ll_ref)):.2e}")

    # sweep the correlation length: model selection via TLR loglik, each
    # candidate operator built straight from the (KD-ordered) points
    print(f"{'ell':>6} {'loglik':>12}")
    for ell in (0.05, 0.1, 0.2, 0.4):
        oe = TLROperator.from_kernel(pts, "exp", tile=args.tile, eps=1e-8,
                                     ell=ell)
        fe = oe.cholesky(CholOptions(eps=1e-6, bs=16))
        a = fe.solve(y)
        l = -0.5 * (float(y @ a) + float(fe.logdet())
                    + args.n * np.log(2 * np.pi))
        print(f"{ell:>6} {l:>12.2f}")

    if args.trace:
        obs.record_retraces()
        obs.export_chrome_trace(args.trace)
        snap = obs.metrics_snapshot()
        obs.disable()
        print(f"wrote {args.trace}: {snap['spans']} spans, "
              f"wall {snap['wall_s']:.2f}s"
              + (f", padded/useful {snap['padded_flop_ratio']:.2f}"
                 if "padded_flop_ratio" in snap else ""))


if __name__ == "__main__":
    main()
