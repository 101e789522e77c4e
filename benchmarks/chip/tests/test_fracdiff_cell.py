"""The ``fracdiff3d-pcg`` cell on the CPU at a small size: the benchmark's
own operator against the program's host reference, a whole run of the
``factor_pcg`` traffic, the faults its check must refuse, a program
without the generator, and the readers of the ``pcg.*`` metrics."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench
import fracdiff_ref
import run

CELL = "fracdiff3d-pcg"
SMALL = {"n": 512, "tile": 64, "r_max": 32}
# About three times what sound runs read at this size on the CPU
# (compress 1.6e-5..2.2e-5, factor 2.3e-4..1.3e-3, backward error
# 1.0e-5..1.5e-5); the cell's own limits are set for N=32768 on the chip.
LIMITS = {"compress_err": 1e-4, "factor_err": 5e-3, "pcg_berr": 5e-5,
          "pcg_unconverged": 0}
PCG_METRICS = ["pcg.iters", "pcg.solve_s", "pcg.syncs_per_iter"]
F32 = np.finfo(np.float32).eps


def test_reference_operator_matches_the_host_reference():
    from repro.core import fractional_diffusion

    _, cfg, _, _ = bench.cell_files(CELL, bench.spec())
    cfg = {**cfg, **SMALL}
    pts = fracdiff_ref.points(cfg)
    with jax.enable_x64(False):
        K = np.asarray(fracdiff_ref.dense_operator(pts, cfg, rows=64),
                       np.float64)
    ref = fractional_diffusion(pts, cfg["s"], cfg["mass"])
    dmax = np.diagonal(ref).max()
    off = ~np.eye(512, dtype=bool)
    np.testing.assert_allclose(K[off], ref[off] / dmax, rtol=32 * F32)
    np.testing.assert_allclose(np.diagonal(K), np.diagonal(ref) / dmax,
                               rtol=4 * F32)
    assert np.diagonal(K).max() == 1.0
    # Gershgorin on the stored f32 matrix, with the scaled margin
    h = 1.0 / (512 ** (1.0 / 3.0) - 1.0)
    margin = np.diagonal(K) - (np.abs(K) * off).sum(axis=1)
    assert margin.min() >= 0.9 * cfg["mass"] * h ** 3 / dmax


def test_reference_lower_transpose_apply():
    """``lower_t_apply`` is the transpose of ``problem.tlr_apply(lower=True)``
    on random tiles: ``<L^T z, w> == <z, L w>``."""
    import problem

    rng = np.random.default_rng(0)
    nb, b, r = 3, 8, 4
    D = jnp.asarray(rng.standard_normal((nb, b, b)), jnp.float32)
    U = jnp.asarray(rng.standard_normal((3, b, r)), jnp.float32)
    V = jnp.asarray(rng.standard_normal((3, b, r)), jnp.float32)
    ranks = jnp.asarray([4, 2, 0])
    z = jnp.asarray(rng.standard_normal((nb * b, 2)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((nb * b, 2)), jnp.float32)
    ltz = fracdiff_ref.lower_t_apply(D, U, V, ranks, z)
    lw = problem.tlr_apply(D, U, V, ranks, w, lower=True)
    np.testing.assert_allclose(np.sum(np.asarray(ltz) * np.asarray(w), 0),
                               np.sum(np.asarray(z) * np.asarray(lw), 0),
                               rtol=1e-4)


def _run(make_ctx, seconds=0.5):
    ctx, spec_ = make_ctx(CELL, seconds=seconds, cfg=SMALL)
    ctx.limits = LIMITS
    run.run_cell(ctx, spec_)
    ctx.watchdog.stop()
    return ctx


def test_cell_sound(make_ctx):
    with jax.enable_x64(False):
        ctx = _run(make_ctx)
    res = ctx.result
    assert res.correct(), res.line()
    assert set(res.metrics) == {"setup_s", "factor_s"}
    assert res.attempted >= 1 and res.failed == 0
    assert [n for n, _, _ in res.checks] == [
        "compress_err", "factor_err", "pcg_berr", "pcg_unconverged"]
    hist = ctx.readings.pcg_history
    assert hist.iterations > 0 and hist[-1] < 1e-6
    assert len(ctx.readings.factor_stats) == res.attempted


def _altered_factor(monkeypatch):
    """The factor's highest-rank off-diagonal tile scaled by 1.5."""
    from repro.core import operator as op_mod

    real = op_mod.TLROperator.cholesky

    def broken(self, opts=None):
        fact = real(self, opts)
        t = int(np.argmax(np.asarray(fact.L.ranks)))
        fact.L.U = fact.L.U.at[t].multiply(1.5)
        return fact

    monkeypatch.setattr(op_mod.TLROperator, "cholesky", broken)


def _stopped_pcg(monkeypatch):
    """PCG that gives up after one iteration."""
    import repro.core

    real = repro.core.pcg
    monkeypatch.setattr(repro.core, "pcg",
                        lambda *a, **k: real(*a, **{**k, "maxiter": 1}))


def _zero_solution(monkeypatch):
    """PCG that reports convergence with x = 0."""
    import repro.core

    real = repro.core.pcg

    def broken(*a, **k):
        x, it, hist = real(*a, **k)
        return jnp.zeros_like(x), it, hist

    monkeypatch.setattr(repro.core, "pcg", broken)


@pytest.mark.parametrize("fault, refused_by", [
    (_altered_factor, "factor_err"), (_stopped_pcg, "pcg_unconverged"),
    (_zero_solution, "pcg_berr")])
def test_cell_refuses_faults(make_ctx, monkeypatch, fault, refused_by):
    fault(monkeypatch)
    with jax.enable_x64(False):
        res = _run(make_ctx, seconds=0.2).result
    assert not res.correct(), res.line()
    failed = [n for n, v, lim in res.checks if not v <= lim]
    assert refused_by in failed, res.line()


def test_program_without_the_generator_exits(make_ctx, monkeypatch):
    """Where the program has no on-device generator (the commit before it
    existed), the run ends at once with code 2 and no result line."""
    import repro.core

    monkeypatch.delattr(repro.core, "fractional_diffusion_device")
    ctx, spec_ = make_ctx(CELL, seconds=0.2, cfg=SMALL)
    with pytest.raises(SystemExit) as exc:
        run.run_cell(ctx, spec_)
    ctx.watchdog.stop()
    assert exc.value.code == 2
    assert ctx.result.checks == []


@pytest.mark.parametrize("name", PCG_METRICS)
def test_readers_none_without_a_solve(name):
    assert run.read_metric(name, bench.Readings()) is None


@pytest.mark.parametrize("name", PCG_METRICS)
def test_readers_none_from_a_history_without_counters(name):
    """A program whose history is a plain list of residuals."""
    r = bench.Readings()
    r.pcg_history = [1.0, 1e-3, 1e-7]
    assert run.read_metric(name, r) is None


def test_readers_values_from_a_recorded_history():
    r = bench.Readings()
    r.pcg_history = types.SimpleNamespace(
        iterations=12, host_reads=14,
        telemetry={"phases": {"algebra.pcg": {"count": 1, "seconds": 0.75},
                              "algebra.pcg.check": {"count": 14,
                                                    "seconds": 0.25}}})
    assert run.read_metric("pcg.iters", r) == 12
    assert run.read_metric("pcg.solve_s", r) == pytest.approx(0.75)
    assert run.read_metric("pcg.syncs_per_iter", r) == pytest.approx(14 / 12)
    r.pcg_history.telemetry = None        # an untraced solve
    assert run.read_metric("pcg.solve_s", r) is None


def test_cell_declared():
    """The cell, its metrics and readers, and its place in the factor
    metrics' lists."""
    spec_ = bench.spec()
    work = {w["name"]: w for w in spec_["workloads"]}[CELL]
    assert work["chips"] == 1 and work["traffic"] == "factor_pcg"
    per_layer = {m["name"]: m for m in spec_["per_layer"]}
    for name in PCG_METRICS:
        m = per_layer[name]
        assert m["moves"] == "factor_s" and m["layer"] == "PCG solve"
        assert m["workloads"] == [CELL]
        assert (bench.BENCH / "metrics" / f"{name}.py").is_file()
    for m in spec_["per_layer"]:
        if "cov3d-factor-left" in m.get("workloads", []):
            assert m["workloads"][-1] == CELL, m["name"]
    assert CELL in run.cell_metrics(spec_, CELL, "end_to_end")[1][
        "workloads"]
    limits = bench.load_json(bench.BENCH / "limits" / f"{CELL}.json")
    assert set(limits) == set(LIMITS)


def test_reference_norm2():
    """Power iteration reaches the largest eigenvalue, also for a matrix
    whose near-null eigenvector is the constant vector."""
    rng = np.random.default_rng(1)
    Q, _ = np.linalg.qr(np.c_[np.ones(64), rng.standard_normal((64, 63))])
    lam = np.r_[1e-7, np.linspace(0.1, 1.0, 62), 2.0]
    K = (Q * lam) @ Q.T
    assert fracdiff_ref.norm2(jnp.asarray(K, jnp.float32)) == pytest.approx(
        2.0, rel=1e-5)
