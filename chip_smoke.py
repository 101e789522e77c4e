#!/usr/bin/env python3
"""Smoke run of the TLR main path on one TPU chip, in one process, in f32.

    python3 chip_smoke.py              # one chip, N=32768, tile 512, eps=1e-2
    python3 chip_smoke.py --chips 4    # sharded right driver on a 2x2 mesh

Phases, each through the entry points a user calls: build the paper's
section 6 covariance (3D exponential kernel, points in the unit ball drawn
from ``--seed``) on the device; a dense f32 reference (Cholesky logdet,
2-norm); ``TLROperator.compress``, checked against the dense matrix;
``.cholesky()`` with the default ``CholOptions`` (left driver, dynamic ARA,
auto batching, Pallas kernels on TPU); ``.cholesky(algo="right")``;
``.ldlt()``; the left factorization again with ``impl="ref"`` to compare
factors; ``solve`` of 1 and 16 right-hand
sides, ``logdet``, ``sample``; and a warmed-up ``TLRServer`` draining mixed
requests without retracing. Every result is checked against the dense
reference; any failed phase or missed threshold exits non-zero, and so does
a run that finds no TPU. Times printed on the way are wall times after
``block_until_ready``, with XLA compile time reported apart.

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# Thresholds for eps = 1e-2 (the paper's headline tolerance), each set
# between the readings of a sound f32 run on a v5e and those of a run whose
# XLA matmuls take the TPU's one-pass bf16 default (PERF.md, PR 11).
COMPRESS_ERR_MAX = 1e-4      # ||(A - A_tlr) z|| / ||A z||, compress_eps
BACKWARD_ERR_MAX = 1e-4      # ||A x - y|| / (||A||_2 ||x|| + ||y||)
LOGDET_REL_MAX = 1e-3        # |logdet - logdet_dense| / |logdet_dense|
# The left driver's f32 accuracy stalls near eps (ROADMAP Reach 1): its
# logdet reads 8.6e-3 at eps=1e-2, so it has a gate of its own.
LEFT_LOGDET_REL_MAX = 3e-2
FACTOR_REL_DIFF_MAX = 5e-2   # ||(L_pallas - L_ref) z|| / ||L_ref z||
SAMPLE_VAR_TOL = 0.25        # |mean(x^2) / (tr(A)/n) - 1|, 16 draws


@dataclasses.dataclass(frozen=True)
class SmokeConfig:
    n: int = 32768
    tile: int = 512
    eps: float = 1e-2
    compress_eps: float = 1e-3
    r_max: int = 128
    bs: int = 16
    seed: int = 0
    impl: str | None = None          # None: the backend default
    requests: int = 36
    slots: int = 8


class Phases:
    """Runs named phases, timing each (compile time apart) and recording
    failures; a failed phase does not stop the phases that do not need it."""

    def __init__(self):
        import jax.monitoring

        self.compile_s = 0.0
        self.compiles = 0
        self.failed: list[str] = []
        self.times: dict[str, dict] = {}

        def on_event(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.compile_s += secs
                self.compiles += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)

    def run(self, name, fn, *args, **kw):
        import jax

        c0, n0, t0 = self.compile_s, self.compiles, time.perf_counter()
        try:
            out = fn(*args, **kw)
            jax.block_until_ready([x for x in jax.tree.leaves(out)
                                   if isinstance(x, jax.Array)])
        except Exception:  # noqa: BLE001 -- reported, the run fails
            traceback.print_exc()
            self.failed.append(name)
            print(f"phase {name}: FAILED", flush=True)
            return None
        wall = time.perf_counter() - t0
        comp = self.compile_s - c0
        self.times[name] = {"wall_s": wall, "compile_s": comp}
        mem = dev_mem()
        used = (f", device {mem['bytes_in_use'] / 2**30:.2f} GiB in use, "
                f"peak {mem['peak_bytes_in_use'] / 2**30:.2f} GiB"
                if "peak_bytes_in_use" in mem else "")
        print(f"phase {name}: wall {wall:.3f} s (compile {comp:.3f} s in "
              f"{self.compiles - n0} programs, run {wall - comp:.3f} s"
              f"{used})", flush=True)
        return out


def check(ok: bool, what: str) -> None:
    print(f"  check {what}: {'ok' if ok else 'MISSED'}", flush=True)
    if not ok:
        raise AssertionError(what)


def dev_mem() -> dict:
    import jax

    return jax.devices()[0].memory_stats() or {}


# -- phases ------------------------------------------------------------------


def build_problem(cfg: SmokeConfig):
    """Points from the seed, the dense covariance built on the device."""
    import jax.numpy as jnp
    from repro.core import covariance_points, exp_covariance_device

    pts = covariance_points(cfg.n, 3, cfg.tile, geometry="ball",
                            seed=cfg.seed)
    return exp_covariance_device(pts, 0.2, dtype=jnp.float32,
                                 rows=min(cfg.tile, cfg.n))


def dense_reference(K, block: int = 1024):
    """Dense f32 reference: logdet from a blocked right-looking dense
    Cholesky, a power-iteration ||K||_2, and tr(K).

    The Cholesky runs as a loop over ``block``-row panels (one compiled
    panel step instead of XLA's fully expanded n x n factorization, which
    takes minutes to compile at n=32768); each step updates the whole
    trailing matrix through a column mask."""
    import jax
    import jax.numpy as jnp

    n = K.shape[0]
    block = math.gcd(n, block)

    @jax.jit
    def ref(K):
        cols = jnp.arange(n)

        def panel(j, carry):
            A, logdet = carry
            P = jax.lax.dynamic_slice_in_dim(A, j * block, block, axis=0)
            Ljj = jnp.linalg.cholesky(
                jax.lax.dynamic_slice_in_dim(P, j * block, block, axis=1))
            logdet += 2.0 * jnp.sum(jnp.log(jnp.diagonal(Ljj)))
            W = jax.scipy.linalg.solve_triangular(Ljj, P, lower=True)
            W = jnp.where(cols[None, :] >= (j + 1) * block, W, 0.0)
            return A - W.T @ W, logdet

        def power(_, x):
            y = K @ x
            return y / jnp.linalg.norm(y)

        with jax.default_matmul_precision("highest"):
            _, logdet = jax.lax.fori_loop(0, n // block, panel,
                                          (K, jnp.zeros((), K.dtype)))
            x = jax.lax.fori_loop(0, 20, power, jnp.ones((n,), K.dtype))
            norm2 = jnp.linalg.norm(K @ x)
        return logdet, norm2, jnp.trace(K)

    logdet, norm2, tr = ref(K)
    return {"logdet": logdet, "norm2": norm2, "trace": tr}


def compress(K, cfg: SmokeConfig):
    from repro.core import TLROperator

    return TLROperator.compress(K, cfg.tile, cfg.r_max, cfg.compress_eps,
                                method="ara", bs=cfg.bs)


def check_compress(op, K, cfg: SmokeConfig) -> float:
    """The compressed operator against the dense matrix on 4 random
    vectors; returns ||(A - A_tlr) z|| / ||A z||."""
    import jax
    import jax.numpy as jnp

    z = jax.random.normal(jax.random.PRNGKey(cfg.seed + 4), (cfg.n, 4),
                          jnp.float32)
    with jax.default_matmul_precision("highest"):
        az = K @ z
    err = float(jnp.linalg.norm(op @ z - az) / jnp.linalg.norm(az))
    ranks = jnp.asarray(op.A.ranks)
    print(f"  compress: rel. operator error {err:.3e} (max "
          f"{COMPRESS_ERR_MAX:g}), avg rank {float(jnp.mean(ranks)):.1f}, "
          f"{float(jnp.mean(ranks >= cfg.r_max)):.3f} of tiles at r_max",
          flush=True)
    check(err <= COMPRESS_ERR_MAX, "compress operator error")
    return err


def chol_options(cfg: SmokeConfig, **kw):
    from repro.core import CholOptions

    return CholOptions(eps=cfg.eps, bs=cfg.bs, seed=cfg.seed, impl=cfg.impl,
                       **kw)


def backward_error(K, ref, x, y) -> float:
    """Normwise backward error of a solve against the dense matrix."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        r = K @ x - y
    nx = jnp.linalg.norm(x, axis=0)
    return float(jnp.max(jnp.linalg.norm(r, axis=0)
                         / (ref["norm2"] * nx + jnp.linalg.norm(y, axis=0))))


def check_factor(name, fact, K, ref, cfg: SmokeConfig,
                 logdet_max: float = LOGDET_REL_MAX) -> dict:
    """Solve of 1 and 16 right-hand sides and logdet against the dense
    reference; returns the measured errors."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(cfg.seed + 1)
    out = {}
    for nrhs in (1, 16):
        shape = (cfg.n,) if nrhs == 1 else (cfg.n, nrhs)
        y = jax.random.normal(jax.random.fold_in(key, nrhs), shape,
                              jnp.float32)
        x = fact.solve(y)
        check(x.shape == y.shape and bool(jnp.all(jnp.isfinite(x))),
              f"{name} solve nrhs={nrhs} finite, shape {shape}")
        be = backward_error(K, ref, x.reshape(cfg.n, -1),
                            y.reshape(cfg.n, -1))
        print(f"  {name} solve nrhs={nrhs}: backward error {be:.3e} "
              f"(max {BACKWARD_ERR_MAX:g})", flush=True)
        check(be <= BACKWARD_ERR_MAX, f"{name} solve nrhs={nrhs} backward "
              "error")
        out[f"backward_err_{nrhs}"] = be
    ld = float(fact.logdet())
    ld_ref = float(ref["logdet"])
    rel = abs(ld - ld_ref) / abs(ld_ref)
    print(f"  {name} logdet {ld:.6f} (dense {ld_ref:.6f}, rel {rel:.3e}, "
          f"max {logdet_max:g})", flush=True)
    check(rel <= logdet_max, f"{name} logdet")
    out["logdet_rel"] = rel
    ranks = jnp.asarray(fact.L.ranks)
    print(f"  {name} factor ranks: mean {float(jnp.mean(ranks)):.1f}, "
          f"max {int(jnp.max(ranks))}; batching "
          f"{fact.stats['batching']}, flushes {fact.stats.get('flushes', 0)}",
          flush=True)
    return out


def check_sample(fact, ref, cfg: SmokeConfig, num: int = 16) -> float:
    """x = fact.sample(key, num) against its definition x = L z and the
    covariance's trace: E|x|^2 / n = tr(A) / n."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(cfg.seed + 2)
    x = fact.sample(key, num)
    check(x.shape == (cfg.n, num) and bool(jnp.all(jnp.isfinite(x))),
          "sample finite, shape")
    z = jax.random.normal(key, (cfg.n, num), jnp.float32)
    lz = fact.tri_matvec(z)
    d = float(jnp.linalg.norm(x - lz) / jnp.linalg.norm(lz))
    check(d <= 1e-5, f"sample == L z (rel diff {d:.2e})")
    var = float(jnp.mean(x * x) / (ref["trace"] / cfg.n))
    print(f"  sample variance / (tr(A)/n) = {var:.4f} "
          f"(tol {SAMPLE_VAR_TOL:g})", flush=True)
    check(abs(var - 1.0) <= SAMPLE_VAR_TOL, "sample variance")
    return var


def factor_distance(fa, fb, cfg: SmokeConfig) -> float:
    """||(L_a - L_b) z|| / ||L_b z|| for 4 random vectors."""
    import jax
    import jax.numpy as jnp

    z = jax.random.normal(jax.random.PRNGKey(cfg.seed + 3), (cfg.n, 4),
                          jnp.float32)
    la, lb = fa.tri_matvec(z), fb.tri_matvec(z)
    return float(jnp.linalg.norm(la - lb) / jnp.linalg.norm(lb))


def serve(fact, op, cfg: SmokeConfig) -> dict:
    """A warmed-up TLRServer drains mixed requests with no retrace."""
    import numpy as np
    from repro.core import trace_counts, trace_counts_diff
    from repro.serve import KINDS, ServeRequest

    srv = fact.serve(operator=op, slots=cfg.slots, check_every=4,
                     seed=cfg.seed)
    before = trace_counts()
    rng = np.random.default_rng(cfg.seed)
    reqs = []
    for u in range(cfg.requests):
        kind = KINDS[u % len(KINDS)]
        rhs = (rng.standard_normal(cfg.n).astype(np.float32)
               if kind in ("solve", "pcg_solve") else None)
        reqs.append(ServeRequest(kind, rhs=rhs, tol=1e-4, maxiter=200,
                                 seed=u))
    t0 = time.perf_counter()
    for r in reqs:
        srv.submit(r)
    results = srv.run()
    wall = time.perf_counter() - t0
    drift = trace_counts_diff(before)
    st = srv.stats
    print(f"  served {st.completed} requests in {st.ticks} ticks, "
          f"{wall:.3f} s, occupancy {st.occupancy():.2f}, "
          f"retraces after warmup {drift or 0}", flush=True)
    check(len(results) == len(reqs) and all(r.ok for r in results.values()),
          "every request answered ok")
    check(not drift, "no retrace after warmup")
    r0 = next(r for r in reqs if r.kind == "solve")
    want = np.asarray(fact.solve(r0.rhs))
    got = np.asarray(results[r0.rid].value)
    diff = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    check(diff <= 1e-4, f"served solve == fact.solve (rel diff {diff:.2e})")
    pcg = [results[r.rid] for r in reqs if r.kind == "pcg_solve"]
    check(all(r.converged for r in pcg), "every pcg_solve converged")
    return {"completed": st.completed, "ticks": st.ticks, "wall_s": wall}


# -- drivers -------------------------------------------------------------------


def run_one_chip(cfg: SmokeConfig, ph: Phases) -> None:
    K = ph.run("build_problem", build_problem, cfg)
    if K is None:
        return
    print(f"  dense A: {K.shape} {K.dtype}, {K.nbytes / 2**30:.2f} GiB",
          flush=True)
    ref = ph.run("dense_reference", dense_reference, K)
    op = ph.run("compress", compress, K, cfg)
    if ref is None or op is None:
        return
    mem = op.memory_stats()
    print(f"  A (TLR): padded {mem['total_bytes_padded'] / 2**30:.3f} GiB, "
          f"logical {mem['total_bytes_logical'] / 2**30:.3f} GiB, "
          f"avg rank {mem['avg_rank']:.1f}", flush=True)
    ph.run("check_compress", check_compress, op, K, cfg)

    # The right driver's accumulation buffers, two (tiles, b, b + r_max)
    # stacks (4.9 GiB at N=32768), need the room the dense matrix takes:
    # it runs first, without K, and K is rebuilt from the seed for its
    # checks.
    del K
    right = ph.run("cholesky_right", op.cholesky,
                   chol_options(cfg, algo="right"))
    K = ph.run("rebuild_problem", build_problem, cfg)
    if K is None:
        return
    if right is not None:
        ph.run("check_cholesky_right", check_factor, "cholesky_right", right,
               K, ref, cfg)
        del right

    facts = {}
    for name, opts, method, logdet_max in (
            ("cholesky_left", chol_options(cfg), "cholesky",
             LEFT_LOGDET_REL_MAX),
            ("ldlt", chol_options(cfg), "ldlt", LOGDET_REL_MAX),
            ("cholesky_left_ref",
             dataclasses.replace(chol_options(cfg), impl="ref"), "cholesky",
             None)):
        f = ph.run(name, getattr(op, method), opts)
        if f is None:
            continue
        facts[name] = f
        if logdet_max is not None:
            ph.run(f"check_{name}", check_factor, name, f, K, ref, cfg,
                   logdet_max)
    left = facts.get("cholesky_left")
    if left is not None:
        lm = left.L.memory_stats()
        print(f"  L (TLR): padded {lm['total_bytes_padded'] / 2**30:.3f} GiB, "
              f"logical {lm['total_bytes_logical'] / 2**30:.3f} GiB",
              flush=True)
        if "cholesky_left_ref" in facts:
            d = ph.run("compare_impl", factor_distance, left,
                       facts["cholesky_left_ref"], cfg)
            if d is not None:
                print(f"  left factor pallas vs ref: rel diff {d:.3e} "
                      f"(max {FACTOR_REL_DIFF_MAX:g})", flush=True)
                if d > FACTOR_REL_DIFF_MAX:
                    ph.failed.append("compare_impl")
        ph.run("sample", check_sample, left, ref, cfg)
        ph.run("serve", serve, left, op, cfg)
    else:
        ph.failed.append("sample")
        ph.failed.append("serve")
    peak = dev_mem().get("peak_bytes_in_use")
    print(f"  device peak_bytes_in_use: "
          f"{'n/a' if peak is None else f'{peak / 2**30:.2f} GiB'}",
          flush=True)


def run_four_chips(cfg: SmokeConfig, ph: Phases) -> None:
    """The sharded right-driver factorization and solve on a 2x2 mesh,
    against the same factorization on one device, in this one process."""
    import jax
    import numpy as np
    from repro.core import set_tile_mesh
    from repro.launch.mesh import make_test_mesh

    K = ph.run("build_problem", build_problem, cfg)
    ref = ph.run("dense_reference", dense_reference, K)
    op = ph.run("compress", compress, K, cfg)
    if K is None or ref is None or op is None:
        return
    opts = chol_options(cfg, algo="right")
    one = ph.run("cholesky_right_1dev", op.cholesky, opts)
    prev = set_tile_mesh(make_test_mesh((2, 2), ("data", "model")))
    try:
        four = ph.run("cholesky_right_mesh", op.cholesky, opts)
        if four is not None:
            ph.run("check_cholesky_right_mesh", check_factor,
                   "cholesky_right_mesh", four, K, ref, cfg)
    finally:
        set_tile_mesh(prev)
    if one is None or four is None:
        return
    devs = four.L.U.sharding.device_set
    print(f"  sharded L.U on {len(devs)} devices: {four.L.U.sharding}",
          flush=True)
    if len(devs) != len(jax.devices()):
        ph.failed.append("mesh_placement")
    same = all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in (
        (four.L.D, one.L.D), (four.L.U, one.L.U), (four.L.V, one.L.V),
        (four.L.ranks, one.L.ranks)))
    d = factor_distance(four, one, cfg)
    print(f"  mesh vs one device: bitwise equal {same}, rel diff {d:.3e}",
          flush=True)
    if not same:
        ph.failed.append("mesh_parity")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--n", type=int, default=None,
                    help="matrix size (default 32768 on one chip, 16384 "
                    "on four)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke.py: no repro package under {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke.py: needs a TPU, JAX found {devs[0].platform}",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke.py: --chips {args.chips} but JAX found "
              f"{len(devs)} devices", file=sys.stderr)
        return 2
    from repro import precision
    from repro.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache(ROOT)}")
    print(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}, "
          f"jax {jax.__version__}, x64 {jax.config.jax_enable_x64}, "
          f"library matmul precision {precision.MATMUL_PRECISION.name}, "
          f"process default {jax.config.jax_default_matmul_precision}",
          flush=True)
    n = args.n or (32768 if args.chips == 1 else 16384)
    cfg = SmokeConfig(n=n, seed=args.seed)
    print(f"config: {cfg}", flush=True)
    ph = Phases()
    t0 = time.perf_counter()
    (run_one_chip if args.chips == 1 else run_four_chips)(cfg, ph)
    total_compile = sum(t["compile_s"] for t in ph.times.values())
    print(f"total: wall {time.perf_counter() - t0:.1f} s, compile "
          f"{total_compile:.1f} s", flush=True)
    if ph.failed:
        print(f"FAILED phases: {ph.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
