"""GP posterior serving through the TLR inference server (ISSUE 7): one
resident Cholesky factorization of a spatial covariance answers a mixed
stream of per-user requests -- posterior-mean solves, marginal-likelihood
logdets, prior samples, and iterative solves at per-request tolerance --
continuously batched through fixed ``(n, slots)`` RHS blocks with zero
recompiles after warmup (the "millions of users" serving story, DESIGN.md
section 10).

Run:  PYTHONPATH=src python examples/serve_gp.py [--n 2048] [--slots 8]
      [--trace out.json]   # Perfetto trace: per-tick pack/dispatch/sync
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

jax.config.update("jax_enable_x64", True)

from repro.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()

from repro import obs  # noqa: E402
from repro.core import (  # noqa: E402
    CholOptions, TLROperator, covariance_problem,
)
from repro.serve import KINDS, ServeRequest  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=2048)
    ap.add_argument("--tile", type=int, default=128)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="record telemetry and write a Chrome-trace / "
                         "Perfetto JSON (load at ui.perfetto.dev)")
    args = ap.parse_args()

    if args.trace:
        obs.enable()

    pts, K = covariance_problem(args.n, 2, args.tile, geometry="ball",
                                seed=3)
    op = TLROperator.compress(jnp.asarray(K), args.tile, eps=1e-8)
    fact = op.cholesky(CholOptions(eps=1e-6, bs=16))

    t0 = time.perf_counter()
    srv = fact.serve(operator=op, slots=args.slots, check_every=4)
    print(f"server up: n={args.n}, slots={args.slots}, "
          f"warmup {time.perf_counter() - t0:.2f}s "
          f"(all serve-path executables compiled)")

    # a mixed per-user request stream: each user brings observations y_u
    # and wants alpha_u = K^{-1} y_u (posterior mean weights), the model
    # evidence logdet, or a prior draw for their posterior sampler
    rng = np.random.default_rng(0)
    reqs = []
    for u in range(args.requests):
        kind = KINDS[u % len(KINDS)]
        y_u = (rng.standard_normal(args.n)
               if kind in ("solve", "pcg_solve") else None)
        reqs.append(ServeRequest(kind, rhs=y_u, tol=10.0 ** -rng.integers(4, 9),
                                 maxiter=100, seed=u))
    t0 = time.perf_counter()
    for r in reqs:
        srv.submit(r)
    results = srv.run()
    wall = time.perf_counter() - t0

    st = srv.stats
    print(f"drained {st.completed} requests in {st.ticks} ticks / "
          f"{wall:.3f}s ({st.completed / wall:.0f} req/s), "
          f"occupancy {st.occupancy():.2f}")
    for kind in KINDS:
        p = st.latency_percentiles(kind)
        if not p["count"]:
            print(f"  {kind:>10}: (no completions)")
            continue
        print(f"  {kind:>10}: p50 {p['p50_s']*1e3:7.1f} ms   "
              f"p99 {p['p99_s']*1e3:7.1f} ms   ({p['count']} requests)")

    # spot-check one posterior-mean solve against the sequential path
    r0 = next(r for r in reqs if r.kind == "solve")
    ref = np.asarray(fact.solve(jnp.asarray(r0.rhs)))
    err = float(np.max(np.abs(results[r0.rid].value - ref)))
    print(f"batched-vs-sequential solve max abs diff: {err:.2e}")
    pcg = [results[r.rid] for r in reqs if r.kind == "pcg_solve"]
    if pcg:
        print(f"pcg_solve: {sum(r.converged for r in pcg)}/{len(pcg)} "
              f"converged, iterations "
              f"{sorted(r.iterations for r in pcg)}")

    if args.trace:
        obs.record_retraces()
        obs.export_chrome_trace(args.trace)
        snap = obs.metrics_snapshot(cats=("serve",))
        obs.disable()
        tick = snap["phases"].get("serve.tick", {})
        print(f"wrote {args.trace}: {snap['spans']} serve spans over "
              f"{tick.get('count', 0)} ticks")


if __name__ == "__main__":
    main()
