"""Shared benchmark utilities: timing, problem construction, FLOP model."""

from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache

# TLR validation benches run in f64 like the paper.
jax.config.update("jax_enable_x64", True)
enable_compile_cache()

SCALE = float(os.environ.get("BENCH_SCALE", "1.0"))


def scaled(n: int) -> int:
    return max(256, int(n * SCALE))


def timeit(fn, *args, repeats: int = 3, warmup: int = 1, **kw):
    """Median wall time in seconds; blocks on jax outputs."""
    for _ in range(warmup):
        out = fn(*args, **kw)
        jax.block_until_ready(jax.tree.leaves(out)) if jax.tree.leaves(
            [x for x in jax.tree.leaves(out)
             if isinstance(x, jax.Array)]) else None
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        leaves = [x for x in jax.tree.leaves(out) if isinstance(x, jax.Array)]
        if leaves:
            jax.block_until_ready(leaves)
        times.append(time.perf_counter() - t0)
    return float(np.median(times)), out


RECORDS: list[dict] = []


def emit(name: str, us_per_call: float, derived: str) -> None:
    """The harness CSV contract: name,us_per_call,derived. Every record is
    also retained for the machine-readable JSON dump (``write_json``)."""
    print(f"{name},{us_per_call:.1f},{derived}")
    RECORDS.append({"name": name, "us_per_call": float(us_per_call),
                    "derived": str(derived)})


def reset_records() -> None:
    RECORDS.clear()


def bench_topology() -> dict:
    """The execution topology stamped into every ``BENCH_<suite>.json``:
    device count, backend, the active tile mesh (if any), and the default
    lookahead setting. ``benchmarks/compare.py`` refuses to diff two bench
    files recorded on different topologies unless told to -- a 1-device
    number against an 8-device number is not a regression signal."""
    from repro.core import CholOptions, tile_mesh

    mesh = tile_mesh()
    return {
        "device_count": jax.device_count(),
        "backend": jax.default_backend(),
        "mesh": None if mesh is None else {
            "shape": list(mesh.devices.shape),
            "axes": list(mesh.axis_names),
        },
        "lookahead": bool(CholOptions().lookahead),
    }


def write_json(path: str, meta: dict | None = None) -> None:
    """Dump all emitted records as JSON (the CI artifact contract:
    ``BENCH_<suite>.json`` with wall times plus any derived metrics such as
    the cost_analysis padded-vs-useful FLOP ratio, stamped with the
    execution topology)."""
    import json

    payload = {"bench_scale": SCALE, "topology": bench_topology(),
               "records": list(RECORDS)}
    if meta:
        payload.update(meta)
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {path} ({len(RECORDS)} records)")


# -- analytic FLOP model for the factorization phases -------------------------


def factorization_flop_model(nb: int, b: int, r: int, bs: int,
                             stats: dict, share_omega: bool = True) -> dict:
    """Per-phase padded-arithmetic FLOPs from the recorded column stats.

    Phases (paper Fig. 8a): sampling GEMMs, projection GEMMs, orthog (QR),
    trsm, dense diagonal updates + Cholesky, reductions/misc.
    """
    f = {"sample": 0.0, "project": 0.0, "orthog": 0.0, "trsm": 0.0,
         "dense_diag": 0.0, "chol": 0.0}
    iters = stats["column_iters"]
    for k in range(1, nb):
        T = nb - k                       # tiles below the diagonal
        it = iters[k - 1] if k - 1 < len(iters) else 1
        # sampling: shared W2 hoist: per iter 2 GEMMs over j=(k) tiles for
        # the column + per (tile, j) 2 GEMMs; A-tile sample 2 GEMMs
        per_iter = 2 * (2 * b * r * bs) * k if share_omega else 0
        per_iter += T * k * 2 * (2 * b * r * bs) * (1 if share_omega else 2)
        per_iter += T * 2 * (2 * b * r * bs)
        f["sample"] += it * per_iter
        # orthog: GS projections vs Q (b x r) + QR of (b, bs)
        f["orthog"] += it * T * (2 * 2 * b * r * bs + 2 * b * bs * bs)
        # projection B = expr^T Q: same chain with s=r
        f["project"] += T * k * 4 * (2 * b * r * r) / (2 if share_omega else 1)
        f["project"] += T * 2 * (2 * b * r * r)
        # trsm: triangular solve of (b x b) against r rhs
        f["trsm"] += T * b * b * r
        # dense diagonal update: k low-rank products to (b, b)
        f["dense_diag"] += k * (2 * b * r * r + 2 * b * b * r)
        f["chol"] += b ** 3 / 3
    f["chol"] += b ** 3 / 3  # first diagonal
    total = sum(f.values())
    gemm = f["sample"] + f["project"] + f["dense_diag"] + f["trsm"]
    return {"phases": f, "total": total, "gemm_fraction": gemm / total}
