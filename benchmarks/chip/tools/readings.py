#!/usr/bin/env python3
"""Readings for the limits of a cell's check: the numbers the check
compares, from one process over many seeds (set-up compiles once).

    python3 benchmarks/chip/tools/readings.py --workload cov3d-factor-left \\
        --seeds 101 102 103 --seconds 0 [--precision high]

Each seed runs the cell as ``run.py`` does (set-up, window, check), with
the window ``--seconds`` long (0: one factorization), and prints one JSON
line of its checks and end-to-end numbers. ``--precision`` runs the
control: the library's contractions at a lower matmul precision, set
before anything is traced. Needs the chip.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
import traceback
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import bench  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--precision", default=None)
    ap.add_argument("--compress-only", action="store_true",
                    help="read only the set-up's compressed operator: "
                    "compress_err and the mean tile rank")
    args = ap.parse_args()
    sys.path.insert(0, str(bench.ROOT / "src"))
    import run

    spec_ = bench.spec()
    work, cfg, traffic, limits = bench.cell_files(args.workload, spec_)
    device = bench.device_info(work["chips"])
    bench.enable_compile_cache()
    bench.set_matmul_precision(args.precision or cfg["matmul_precision"])
    counter = bench.CompileCounter()
    if args.compress_only:
        return compress_only(args, cfg)
    for seed in args.seeds:
        res = bench.Result(spec_, False)
        res.device = dict(device)
        ctx = bench.Context(
            cell=args.workload, seed=seed, seconds=args.seconds, trace=False,
            cfg=cfg, traffic=traffic, limits=limits, result=res,
            counter=counter, watchdog=bench.Watchdog(res),
            time_limit=math.inf, t_start=time.perf_counter())
        try:
            run.run_cell(ctx, spec_)
        except Exception:  # noqa: BLE001 -- reported, next seed
            traceback.print_exc()
            res.errors.append("raised")
        ctx.watchdog.stop()
        print(json.dumps({"seed": seed, "precision": args.precision,
                          "correct": res.correct(), "errors": res.errors,
                          "checks": {n: v for n, v, _ in res.checks},
                          "e2e": ctx.e2e}), flush=True)
    return 0


def compress_only(args, cfg) -> int:
    """The compressed operator of each seed against the dense matrix, as
    the factor cells' check compares it."""
    import jax
    import jax.numpy as jnp

    import problem
    from drivers import factor

    for seed in args.seeds:
        ctx = type("Ctx", (), {"cfg": cfg, "seed": seed})()
        t0 = time.perf_counter()
        pts = problem.points(cfg, seed)
        K = problem.dense_covariance(pts, cfg)
        op = factor.compress(K, ctx)
        A = op.A
        key = jax.random.PRNGKey(bench.seed32(seed, 3))
        z = jax.random.normal(jax.random.fold_in(key, 0), (cfg["n"], 4),
                              jnp.float32)
        az = problem.matmul_dense(K, z)
        err = problem.tlr_apply(A.D, A.U, A.V, A.ranks, z) - az
        print(json.dumps({
            "seed": seed, "precision": args.precision,
            "compress_err": float(jnp.linalg.norm(err)
                                  / jnp.linalg.norm(az)),
            "mean_rank": float(jnp.mean(A.ranks)),
            "share_at_r_max": float(jnp.mean(A.ranks >= cfg["r_max"])),
            "seconds": time.perf_counter() - t0}), flush=True)
        del K, op, A
    return 0


if __name__ == "__main__":
    sys.exit(main())
