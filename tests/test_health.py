"""ISSUE 10: breakdown detection, bounded recovery, and the deterministic
fault-injection matrix (DESIGN.md section 13).

Factorization side: ``CholOptions(check=True)`` must reproduce clean-path
factors bitwise, recover injected indefiniteness/rank spikes through the
``RetryPolicy`` ladders (every action a recorded ``HealthEvent``), and
raise a structured :class:`FactorizationBreakdown` -- never return
non-finite factors -- when remedies exhaust. Serve side: non-finite RHS
rejected at submit, poisoned columns isolated from co-batched blocks,
deadlines evict, PCG breakdowns retry with backoff, evicted residents
answer with typed errors.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from repro import faults, obs
from repro.core import (
    CholOptions, FactorizationBreakdown, RetryPolicy, SequentialSchedule,
    Stage, TLROperator, column_flags, covariance_problem, from_dense,
    run_graph, tlr_cholesky,
)
from repro.serve import RequestRejected, ServeRequest


# -- fixtures ------------------------------------------------------------------


@pytest.fixture(scope="module")
def prob3():
    """3-D covariance, nb=4: the generic SPD operand."""
    _, K = covariance_problem(256, 3, 64)
    with pytest.warns(FutureWarning):
        A = from_dense(jnp.asarray(K), 64, 64, 1e-9)
    return K, A


@pytest.fixture(scope="module")
def prob1():
    """1-D covariance, b=32: rank-1 off-diagonal tiles, so a spiked tile
    is the only thing near a hard rank cap (3-D tiles at this size are
    near-full-rank and would overflow a 16-cap everywhere)."""
    _, K = covariance_problem(256, 1, 32)
    with pytest.warns(FutureWarning):
        A = from_dense(jnp.asarray(K), 32, 32, 1e-10)
    return A


@pytest.fixture(scope="module")
def serve_prob():
    rng = np.random.default_rng(0)
    n = 128
    M = rng.standard_normal((n, n))
    A = M @ M.T / n + 2.0 * np.eye(n)
    op = TLROperator.compress(jnp.asarray(A), 32, eps=1e-10)
    return A, op, op.cholesky()


DRIVERS = [("left", False), ("right", False), ("right", True)]
IDS = ["left", "right", "right-lookahead"]


def _finite(fact) -> bool:
    return all(bool(np.isfinite(np.asarray(x)).all())
               for x in (fact.L.D, fact.L.U, fact.L.V))


def _events(fact):
    return fact.stats["health"]["events"]


# -- clean path: checks read, never write --------------------------------------


@pytest.mark.parametrize("algo,lookahead", DRIVERS, ids=IDS)
def test_clean_path_bitwise_parity(prob3, algo, lookahead):
    """check=True on a healthy operand reproduces the unchecked factors
    bitwise (detection only reads), records zero events, and stamps the
    health summary into stats; check=False carries no health machinery."""
    _, A = prob3
    off = tlr_cholesky(A, CholOptions(eps=1e-6, bs=8, algo=algo,
                                      lookahead=lookahead))
    on = tlr_cholesky(A, CholOptions(eps=1e-6, bs=8, algo=algo,
                                     lookahead=lookahead, check=True))
    for a, b in ((off.L.D, on.L.D), (off.L.U, on.L.U), (off.L.V, on.L.V)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert "health" not in off.stats
    h = on.stats["health"]
    assert h["events"] == []
    assert h["columns_checked"] == A.nb
    assert on.stats["schedule"]["checks"] > 0


# -- recovery ladders ----------------------------------------------------------


@pytest.mark.parametrize("algo,lookahead", DRIVERS, ids=IDS)
def test_indefinite_diag_recovers(prob3, algo, lookahead):
    """A genuinely indefinite diagonal tile recovers through the recorded
    SPD ladder (clamp, then escalating jitter as needed) with finite
    factors -- through both drivers and the lookahead schedule."""
    _, A = prob3
    Abad = faults.make_diag_indefinite(A, 2, magnitude=4.0)
    fact = tlr_cholesky(Abad, CholOptions(eps=1e-6, bs=8, algo=algo,
                                          lookahead=lookahead, check=True))
    assert _finite(fact)
    spd = [e for e in _events(fact) if e["kind"] == "spd_breakdown"]
    assert spd, "no spd_breakdown event recorded for an indefinite tile"
    assert all(e["remedy"] in ("clamp", "jitter") for e in spd)
    assert any(e["column"] == 2 for e in spd)


def test_rank_spike_recovers_left(prob1):
    """A planted rank spike under a hard cap recovers through the
    eps-loosen / densify ladder (left driver); the factors stay finite and
    every remedy is on the record."""
    As = faults.spike_rank(prob1, 4, 1, seed=3, scale=1e-4)
    opts = CholOptions(eps=1e-6, bs=8, r_max_out=16, check=True)
    fact = tlr_cholesky(As, opts)
    assert _finite(fact)
    over = [e for e in _events(fact) if e["kind"] == "rank_overflow"]
    assert over and {"eps_loosen"} <= {e["remedy"] for e in over}
    # The retry's loosened-eps steps are cached with the rest: the same
    # retry in a second factorization compiles nothing new.
    obs.enable()
    try:
        again = tlr_cholesky(As, opts)
    finally:
        obs.disable()
    assert _events(again) == _events(fact)
    assert [again.stats[k] for k in ("column_traces", "project_traces",
                                     "diag_traces", "scatter_traces")] \
        == [0, 0, 0, 0]
    assert again.stats["telemetry"]["jit"]["programs"] == 0


def test_rank_spike_accepts_right(prob1):
    """The right driver's rounding is already SVD-optimal, so the same
    spike resolves as a recorded 'accept' (truncation error within the
    policy floor) rather than a re-pass."""
    As = faults.spike_rank(prob1, 4, 1, seed=3, scale=3e-4)
    fact = tlr_cholesky(As, CholOptions(eps=1e-6, bs=8, r_max_out=16,
                                        algo="right", check=True))
    assert _finite(fact)
    over = [e for e in _events(fact) if e["kind"] == "rank_overflow"]
    assert over and all(e["remedy"] == "accept" for e in over)


@pytest.mark.parametrize("algo", ["left", "right"])
def test_rank_spike_breakdown(prob1, algo):
    """A spike too large for any remedy is a typed breakdown carrying the
    column and the remedies tried -- not a silently degraded factor."""
    As = faults.spike_rank(prob1, 4, 1, seed=3, scale=1e-3)
    with pytest.raises(FactorizationBreakdown) as ei:
        tlr_cholesky(As, CholOptions(eps=1e-6, bs=8, r_max_out=16,
                                     algo=algo, check=True))
    rep = ei.value.report
    assert rep.reason == "rank_overflow"
    assert rep.column >= 0
    assert "rank_overflow" in str(ei.value)


# -- unrecoverable faults: structured breakdown, never NaN factors -------------


@pytest.mark.parametrize("algo", ["left", "right"])
def test_nan_diag_breakdown(prob3, algo):
    """A NaN diagonal tile exhausts the jitter ladder (NaN is not fixable
    by shifting) and raises with the remedies it tried."""
    _, A = prob3
    with faults.inject(faults.Fault(site="chol.diag", kind="nan",
                                    column=2)):
        with pytest.raises(FactorizationBreakdown) as ei:
            tlr_cholesky(A, CholOptions(eps=1e-6, bs=8, algo=algo,
                                        check=True))
    rep = ei.value.report
    assert rep.column == 2 and rep.reason == "spd_breakdown"
    assert "jitter" in rep.remedies
    assert "column 2" in str(ei.value)


@pytest.mark.parametrize("algo", ["left", "right"])
def test_nan_panel_breakdown(prob3, algo):
    """A NaN produced mid-panel (healthy pivots) is unrecoverable: the
    check at the stage boundary raises instead of letting the NaN
    propagate through every later column."""
    _, A = prob3
    with faults.inject(faults.Fault(site="chol.panel", kind="nan",
                                    column=1)):
        with pytest.raises(FactorizationBreakdown) as ei:
            tlr_cholesky(A, CholOptions(eps=1e-6, bs=8, algo=algo,
                                        check=True))
    rep = ei.value.report
    assert rep.column == 1 and rep.reason == "nonfinite_panel"


def test_poisoned_input_tile_detected(prob3):
    """A NaN planted in the *operand* poisons the unchecked factorization
    silently (the pre-ISSUE-10 behavior this subsystem exists to stop);
    with check=True the same operand is a structured breakdown at the
    first column that touches the tile."""
    _, A = prob3
    Ap = faults.poison_tile(A, 2, 0)
    silent = tlr_cholesky(Ap, CholOptions(eps=1e-6, bs=8))
    assert not _finite(silent)            # NaN factors, no error raised
    with pytest.raises(FactorizationBreakdown) as ei:
        tlr_cholesky(Ap, CholOptions(eps=1e-6, bs=8, check=True))
    assert ei.value.report.reason == "nonfinite_panel"
    assert ei.value.report.column == 0


# -- units: policy, flags, stage hook ------------------------------------------


def test_retry_policy_schedules():
    rp = RetryPolicy(max_retries=2, shift0=1e-8, growth=16.0,
                     eps_growth=4.0)
    assert rp.shift(0) == pytest.approx(1e-8)
    assert rp.shift(2) == pytest.approx(1e-8 * 256)
    assert rp.eps_at(1e-6, 1) == pytest.approx(4e-6)
    assert rp.eps_floor(1e-6) == pytest.approx(1.6e-5)


def test_column_flags_reductions():
    """The fused device-side scan: non-finite counts, min pivot + argmin,
    and the rank-overflow count, in one host pull."""
    pivots = jnp.asarray([1.0, -2.0, jnp.nan, 3.0])
    arr = jnp.asarray([[1.0, jnp.inf], [0.0, 2.0]])
    flags = column_flags(pivots, (arr,))
    assert flags[0] == 1          # non-finite array entries
    assert flags[1] == 1          # non-finite pivots
    assert flags[2] == -2.0       # min finite pivot
    assert flags[3] == 1          # its index
    ranks = jnp.asarray([4, 2, 4])
    err = jnp.asarray([1e-3, 1e-9, 1e-9])
    flags = column_flags(jnp.ones(2), ranks=ranks, err=err, r_cap=4,
                         eps=1e-6)
    assert flags[4] == 1          # only the at-cap, over-eps tile counts


def test_stage_check_hooks_run_and_time():
    """`Stage.check` runs after the stage body, is counted and timed
    separately, and absent hooks cost nothing (the obs contract)."""
    ran = []
    stages = [
        Stage(name="diag[0]", kind="diag", k=0,
              fn=lambda: ran.append("fn0"),
              check=lambda: ran.append("chk0"), writes=(("x", 0),), seq=0),
        Stage(name="panel[0]", kind="panel", k=0,
              fn=lambda: ran.append("fn1"),
              reads=(("x", 0),), writes=(("y", 0),), seq=1),
    ]
    sched = run_graph(stages, SequentialSchedule())
    assert ran == ["fn0", "chk0", "fn1"]
    assert sched["checks"] == 1
    assert sched["kind_seconds"]["check"] >= 0.0


# -- serve-side degradation ----------------------------------------------------


def test_submit_rejects_nonfinite_rhs(serve_prob):
    _, op, fact = serve_prob
    srv = fact.serve(operator=op, slots=2)
    rhs = np.ones(fact.n)
    rhs[3] = np.inf
    with pytest.raises(RequestRejected, match="non-finite"):
        srv.submit(ServeRequest("solve", rhs=rhs))
    # ValueError compatibility: pre-ISSUE-10 callers guard with ValueError
    with pytest.raises(ValueError):
        srv.submit(ServeRequest("pcg_solve", rhs=rhs))
    assert srv.stats.rejected == 2
    assert srv.pending == 0 and srv.active == 0


def _named_server(fact, op):
    from repro.serve import TLRServer

    srv = TLRServer(slots=2)
    srv.register("f0", fact, operator=op)
    return srv


def test_unknown_and_evicted_fid(serve_prob):
    _, op, fact = serve_prob
    srv = _named_server(fact, op)
    with pytest.raises(RequestRejected, match="unknown factorization"):
        srv.submit(ServeRequest("logdet", fid="nope"))
    rid = srv.submit(ServeRequest("logdet"))
    srv.evict_resident("f0")
    # queued request completed as a typed error, not dropped
    res = srv.results[rid]
    assert not res.ok and res.error == "resident_evicted"
    with pytest.raises(RequestRejected, match="was evicted"):
        srv.submit(ServeRequest("logdet", fid="f0"))
    assert srv.stats.errors >= 1


def test_deadline_timeout_isolated(serve_prob):
    """A stalled request times out at its deadline; the co-batched healthy
    request completes normally in the same server."""
    A, op, fact = serve_prob
    srv = fact.serve(operator=op, slots=2)
    rng = np.random.default_rng(1)
    slow = ServeRequest("solve", rhs=rng.standard_normal(fact.n),
                        deadline_ticks=2)
    ok = ServeRequest("solve", rhs=rng.standard_normal(fact.n))
    rs, ro = srv.submit(slow), srv.submit(ok)
    with faults.inject(faults.Fault(site="serve.admit", rid=rs, delay=6)):
        results = srv.run(max_ticks=10)
    assert results[rs].error == "timeout" and not results[rs].ok
    assert results[rs].value is None
    assert results[ro].ok
    assert np.allclose(results[ro].value, np.linalg.solve(A, ok.rhs),
                       atol=1e-7)
    assert srv.stats.timeouts == 1


def test_poisoned_column_isolated(serve_prob):
    """A NaN column inside a packed solve block degrades only its own
    request; co-batched results are bit-for-bit unaffected."""
    A, op, fact = serve_prob
    srv = fact.serve(operator=op, slots=4)
    rng = np.random.default_rng(2)
    reqs = [ServeRequest("solve", rhs=rng.standard_normal(fact.n))
            for _ in range(3)]
    rids = [srv.submit(r) for r in reqs]
    with faults.inject(faults.Fault(site="serve.solve", rid=rids[1])):
        results = srv.run()
    bad = results[rids[1]]
    assert not bad.ok and bad.error == "nonfinite_result"
    assert bad.value is None
    for r, rid in zip(reqs, rids):
        if rid == rids[1]:
            continue
        out = results[rid]
        assert out.ok and np.isfinite(out.value).all()
        assert np.allclose(out.value, np.linalg.solve(A, r.rhs), atol=1e-7)
    assert srv.stats.errors == 1


def test_pcg_breakdown_retries_with_backoff(serve_prob):
    """PCG against an indefinite operator breaks down; the request
    re-admits with exponential backoff up to its retry budget, then
    completes as a typed degraded result (last finite iterate kept)."""
    A, op, fact = serve_prob
    neg = TLROperator.compress(jnp.asarray(-A), 32, eps=1e-10)
    srv = fact.serve(operator=neg, slots=2)
    rng = np.random.default_rng(3)
    req = ServeRequest("pcg_solve", rhs=rng.standard_normal(fact.n),
                       tol=1e-10, retries=2)
    rid = srv.submit(req)
    results = srv.run(max_ticks=50)
    out = results[rid]
    assert not out.ok and out.error == "pcg_breakdown"
    assert out.breakdown is not None
    assert out.attempts == 3              # 1 admission + 2 retries
    assert srv.stats.pcg_retries == 2
    assert srv.stats.errors == 1


def test_health_counters_in_summary(serve_prob):
    _, op, fact = serve_prob
    srv = fact.serve(operator=op, slots=2)
    h = srv.stats.summary()["health"]
    assert set(h) == {"rejected", "timeouts", "errors", "pcg_retries"}
