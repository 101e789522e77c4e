"""PCG's spans and counters: ``algebra.pcg`` around a single-vector solve
and ``algebra.pcg.check`` around each host read under telemetry; the
iteration and read counters on the returned history with telemetry off;
and iterates bit-identical to the classic one-read-per-iteration loop."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import CholOptions, TLROperator, covariance_problem, pcg
from repro.precision import vdot


@pytest.fixture(scope="module")
def problem():
    _, K = covariance_problem(256, 2, 64)
    op = TLROperator.compress(jnp.asarray(K), 64, eps=1e-8)
    fact = op.cholesky(CholOptions(eps=1e-3))
    b = jnp.asarray(np.random.default_rng(1).standard_normal(256))
    return op, fact, b


def _classic_pcg(matvec, precond, b, tol, maxiter):
    """PCG as it ran before the counters and spans: three host reads to
    start, one per iteration, the same device ops in the same order."""
    bnorm = float(jnp.linalg.norm(b))
    x = jnp.zeros_like(b)
    r = b - matvec(x)
    z = precond(r)
    p = z
    rz = vdot(r, z)
    hist = [float(jnp.linalg.norm(r)) / bnorm]
    float(rz)
    it = 0
    while it < maxiter:
        Ap = matvec(p)
        pAp = vdot(p, Ap)
        alpha = rz / pAp
        x = x + alpha * p
        r = r - alpha * Ap
        rnorm = jnp.linalg.norm(r)
        z = precond(r)
        rz_new = vdot(r, z)
        p = z + (rz_new / rz) * p
        rz = rz_new
        it += 1
        hist.append(float(rnorm) / bnorm)
        if hist[-1] < tol:
            break
    return x, it, hist


@pytest.mark.parametrize("check_every", [1, 8])
def test_iterates_match_the_classic_loop(problem, check_every):
    op, fact, b = problem
    x0, it0, h0 = _classic_pcg(op.matvec, fact.matvec, b, 1e-12, 40)
    x, it, hist = pcg(op, b, precond=fact, tol=1e-12, maxiter=40,
                      check_every=check_every)
    assert it == it0 and list(hist) == h0
    np.testing.assert_array_equal(np.asarray(x), np.asarray(x0))


@pytest.mark.parametrize("check_every", [1, 8])
def test_counters_with_telemetry_off(problem, check_every):
    op, fact, b = problem
    assert not obs.enabled()
    _, it, hist = pcg(op, b, precond=fact, tol=1e-12, maxiter=40,
                      check_every=check_every)
    assert hist.iterations == it == len(hist) - 1 > 0
    # the right-hand side's norm, the starting residual, one per window
    assert hist.host_reads == 2 + -(-it // check_every)
    assert hist.telemetry is None


def test_spans_under_telemetry(problem):
    op, fact, b = problem
    tel = obs.enable()
    try:
        _, it, hist = pcg(op, b, precond=fact, tol=1e-12, maxiter=40)
    finally:
        obs.disable()
    root = [s for s in tel.spans if s.name == "algebra.pcg"]
    assert len(root) == 1 and root[0].cat == "solve"
    assert root[0].args["iterations"] == it
    assert root[0].args["host_reads"] == hist.host_reads
    checks = [s for s in tel.spans if s.name == "algebra.pcg.check"]
    assert len(checks) == hist.host_reads == it + 2
    assert {s.parent for s in checks} == {root[0].id}
    phases = hist.telemetry["phases"]
    assert phases["algebra.pcg"]["count"] == 1
    assert phases["algebra.pcg.check"]["count"] == hist.host_reads
    # the preconditioner's TRSM sweeps sit inside the solve's span
    assert phases["trsm.sweep"]["count"] == 2 * (it + 1)
    assert phases["algebra.pcg"]["seconds"] >= \
        phases["algebra.pcg.check"]["seconds"]


def test_zero_rhs_reads_once(problem):
    op, fact, b = problem
    x, it, hist = pcg(op, jnp.zeros_like(b), precond=fact)
    assert it == 0 and len(hist) == 0 and hist.host_reads == 1
    assert not np.any(np.asarray(x))
