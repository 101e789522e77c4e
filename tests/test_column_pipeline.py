"""Shape-stable column pipeline: compile-count regression + impl parity.

The factorization driver pads every column's row batch up to a power-of-two
bucket ladder (DESIGN.md section 2) so a handful of compiled ARA-step
variants serve all nb columns. These tests pin that contract:

* the trace counter in ``stats`` stays at O(log nb) executables,
* bucket padding does not change the math (padded slots are inert),
* the Pallas kernels dispatched through ``CholOptions.impl`` match the
  pure-jnp reference end-to-end through a full factorization.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np
import pytest

from repro import obs, precision
from repro.core import (
    CholOptions, covariance_problem, from_dense, tlr_cholesky, tlr_ldlt,
    tlr_to_dense,
)
from repro.core.cholesky import _bucket_ladder, _bucket_up, _column_buckets


def _problem(n=512, b=64, r_max=None, eps=1e-7):
    _, K = covariance_problem(n, 3, b)
    A = from_dense(jnp.asarray(K), b, r_max or b, eps)
    return K, A


def _dense_L(fact):
    return np.tril(np.asarray(tlr_to_dense(fact.L.D, fact.L.U, fact.L.V,
                                           fact.L.nb, fact.L.b)))


# -- bucket ladder unit behavior ----------------------------------------------


def test_bucket_ladder_shape():
    assert _bucket_ladder(1) == [1]
    assert _bucket_ladder(7) == [1, 2, 4, 7]
    assert _bucket_ladder(8) == [1, 2, 4, 8]
    assert _bucket_ladder(15) == [1, 2, 4, 8, 15]
    assert _bucket_up(3, [1, 2, 4, 7]) == 4
    assert _bucket_up(7, [1, 2, 4, 7]) == 7


@pytest.mark.parametrize("nb", [2, 5, 8, 16, 23])
def test_column_buckets_cover_and_bound(nb):
    """Every column fits its bucket pair; #distinct pairs <= ladder length."""
    ladder = _bucket_ladder(nb - 1)
    pairs = set()
    for k in range(nb - 1):
        T, J = nb - 1 - k, k
        Tb, Jb = _column_buckets(nb, k, ladder)
        assert Tb >= T and Jb >= J and Jb >= 1
        pairs.add((Tb, Jb))
    assert len(pairs) <= len(ladder)
    assert len(pairs) <= math.ceil(math.log2(max(2, nb - 1))) + 1


# -- compile-count regression (tentpole acceptance) ----------------------------


@pytest.mark.parametrize("factor", [tlr_cholesky, tlr_ldlt],
                         ids=["cholesky", "ldlt"])
def test_column_step_compile_count(fresh_column_steps, factor):
    """nb=8, b=64: the ARA column step compiles <= log2(nb)+1 variants."""
    _, A = _problem(n=512, b=64)
    assert A.nb == 8
    fact = factor(A, CholOptions(eps=1e-6, bs=8))
    bound = int(math.log2(A.nb)) + 1
    assert fact.stats["column_traces"] <= bound, fact.stats["column_events"]
    # projection / diagonal executables are ladder-bounded too
    assert fact.stats["project_traces"] <= bound
    assert fact.stats["diag_traces"] <= 1
    # steady state: each bucket compiles once, later columns reuse it
    events = fact.stats["column_events"]
    seen = set()
    for ev in events:
        key = (ev["Tb"], ev["Jb"])
        assert ev["traced"] == (key not in seen)
        seen.add(key)


_FACTOR_ARRAYS = ("D", "U", "V", "ranks")


def _same_factor(f1, f2) -> bool:
    return all(np.array_equal(np.asarray(getattr(f1.L, a)),
                              np.asarray(getattr(f2.L, a)))
               for a in _FACTOR_ARRAYS)


def _traces(fact):
    return [fact.stats[k] for k in
            ("column_traces", "project_traces", "diag_traces")]


@pytest.mark.parametrize("batching", ["flat", "ranked"])
@pytest.mark.parametrize("factor", [tlr_cholesky, tlr_ldlt],
                         ids=["cholesky", "ldlt"])
def test_warm_factorization_traces_nothing(factor, batching):
    """The column steps are cached across factorizations: a second one
    with the same options traces, lowers and compiles nothing (its JIT
    work read under telemetry, which the first ran without) and returns
    the first one's factor bit for bit."""
    _, A = _problem(n=512, b=64)
    opts = CholOptions(eps=1e-6, bs=8, batching=batching)
    first = factor(A, opts)
    obs.enable()
    try:
        warm = factor(A, opts)
    finally:
        obs.disable()
    assert _traces(warm) == [0, 0, 0]
    assert not any(ev["traced"] for ev in warm.stats["column_events"])
    jit = warm.stats["telemetry"]["jit"]
    assert jit["programs"] == 0 and jit["lower_s"] == 0, jit
    assert _same_factor(first, warm)


def _other_eps(monkeypatch):
    return CholOptions(eps=1e-5, bs=8)


def _other_precision(monkeypatch):
    monkeypatch.setattr(precision, "MATMUL_PRECISION", lax.Precision.HIGH)
    return CholOptions(eps=1e-6, bs=8)


@pytest.mark.parametrize("other", [_other_eps, _other_precision],
                         ids=["eps", "matmul_precision"])
def test_column_steps_keyed_on_statics(fresh_column_steps, other):
    """eps (static in the traced ARA step) and the library's matmul
    precision (read while tracing) key the cached steps: another value
    traces its own steps, and going back to the first traces nothing and
    reproduces its factor."""
    _, A = _problem(n=512, b=64)
    fa = tlr_cholesky(A, CholOptions(eps=1e-6, bs=8))
    with pytest.MonkeyPatch.context() as mp:
        fb = tlr_cholesky(A, other(mp))
    fa2 = tlr_cholesky(A, CholOptions(eps=1e-6, bs=8))
    assert fb.stats["column_traces"] >= 1
    assert _traces(fa2) == [0, 0, 0]
    assert _same_factor(fa, fa2)


# -- padding is numerically inert ---------------------------------------------


def test_bucketed_accuracy_matches_dense():
    K, A = _problem(n=512, b=64)
    fact = tlr_cholesky(A, CholOptions(eps=1e-6, bs=8))
    Ld = _dense_L(fact)
    err = np.linalg.norm(K - Ld @ Ld.T, 2)
    assert err < 1e-4
    # padded row slots must never leak into stored ranks
    for ev, ranks in zip(fact.stats["column_events"],
                         fact.stats["column_ranks"]):
        assert len(ranks) == ev["T"]


# -- kernel dispatch parity (impl knob) ---------------------------------------


@pytest.mark.parametrize("batching", ["flat", "ranked"])
def test_impl_interpret_matches_ref(batching):
    """Pallas interpreter path == pure-jnp path through a full factorization."""
    _, A = _problem(n=256, b=64, r_max=32)
    facts = {}
    for impl in ("ref", "interpret"):
        f = tlr_cholesky(A, CholOptions(eps=1e-6, bs=8, batching=batching,
                                        impl=impl))
        facts[impl] = _dense_L(f)
        assert f.stats["impl"] == impl
    np.testing.assert_allclose(facts["interpret"], facts["ref"],
                               rtol=1e-12, atol=1e-12)


def test_impl_interpret_matches_ref_ldlt():
    """Same parity through the 5-product LDL^T chain (Eq. 3)."""
    _, A = _problem(n=256, b=64, r_max=32)
    facts = {}
    for impl in ("ref", "interpret"):
        f = tlr_ldlt(A, CholOptions(eps=1e-6, bs=8, impl=impl))
        facts[impl] = (_dense_L(f), np.asarray(f.d))
    np.testing.assert_allclose(facts["interpret"][0], facts["ref"][0],
                               rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(facts["interpret"][1], facts["ref"][1],
                               rtol=1e-12, atol=1e-12)


def test_impl_knob_validated():
    _, A = _problem(n=256, b=64, r_max=16)
    with pytest.raises(ValueError, match="impl"):
        tlr_cholesky(A, CholOptions(eps=1e-4, bs=8, impl="cuda"))


def test_dynamic_safety_valve_flushes_live_slots():
    """Regression: when the per-column iteration budget trips the safety
    valve, still-live slots must be flushed with their partial bases.
    Before the fix the loop broke with rows missing from the result dict
    and the assembly crashed with a KeyError."""
    _, A = _problem(n=256, b=64)
    # max_iters=1 with an unreachable eps: nothing converges before the
    # valve (rank cap would need r_max/bs = 16 iterations, valve trips
    # after T_col+1), so every column exercises the flush path.
    with pytest.warns(RuntimeWarning, match="safety valve"):
        fact = tlr_cholesky(A, CholOptions(eps=1e-13, bs=4, max_iters=1))
    assert fact.stats["safety_valve"] is True
    assert np.isfinite(np.asarray(fact.L.V)).all()
    assert np.isfinite(np.asarray(fact.L.U)).all()
    # flushed partial bases still carry the ranks accumulated so far
    for ranks in fact.stats["column_ranks"]:
        assert (np.asarray(ranks) > 0).any()


def test_no_safety_valve_on_converging_problems():
    _, A = _problem(n=256, b=64)
    fact = tlr_cholesky(A, CholOptions(eps=1e-6, bs=8))
    assert fact.stats["safety_valve"] is False


@pytest.mark.parametrize("factor", [tlr_cholesky, tlr_ldlt],
                         ids=["cholesky", "ldlt"])
def test_column_events_report_per_tile_err(factor):
    """Every column reports one final ARA error estimate per row tile."""
    _, A = _problem(n=256, b=64)
    fact = factor(A, CholOptions(eps=1e-6, bs=8))
    assert fact.stats["column_events"], "no columns recorded"
    for ev in fact.stats["column_events"]:
        assert ev["err"].shape == (ev["T"],)
        assert np.isfinite(ev["err"]).all()
        # converged tiles report their final residual estimate, <= eps
        # up to the calibration constant
        assert (ev["err"] <= 1e-4).all()


def test_share_omega_false_through_ops_layer():
    """The per-tile-Omega sampling path also routes through the ops layer."""
    K, A = _problem(n=256, b=64)
    f = tlr_cholesky(A, CholOptions(eps=1e-6, bs=8, share_omega=False,
                                    impl="ref"))
    Ld = _dense_L(f)
    assert np.linalg.norm(K - Ld @ Ld.T, 2) < 1e-4


# -- the column's ARA against a plain ARA over its dense tiles -----------------


def _column_and_reference(factor, opts, r_max, k):
    """Column ``k`` of a factorization through the driver's column ARA,
    and a plain reference on the same operator: ``ara_iteration`` stepped
    from Python over the column's dense tile expressions (the column's
    sampler applied to the identity), with the same key and the shared
    Omega, until every tile converges or the column's budget of
    ``p.iters`` steps per row tile has passed; then the projection and
    the triangular solve in plain jnp."""
    from repro.core.ara import ara_iteration, dense_batch_sampler, init_state
    from repro.core.batching import bucket_width
    from repro.core.cholesky import (_ColumnPipeline, _HostPulls,
                                     _build_column_data, _column_ara)

    _, K = covariance_problem(512, 3, 64)
    A = from_dense(jnp.asarray(K), 64, r_max, 1e-7)
    fact = factor(A, opts)
    L, d = fact.L, fact.d
    ldl = factor is tlr_ldlt
    opts = dataclasses.replace(opts, ldl=ldl)
    p = opts.ara_params(A.r_max)
    pipe = _ColumnPipeline(opts, p)
    ladder = _bucket_ladder(A.nb - 1)
    rows, perm = np.arange(k + 1, A.nb), np.arange(A.nb)
    T, b = len(rows), A.b
    widths = (None, None)
    if opts.batching == "ranked":
        widths = (bucket_width(np.asarray(A.ranks), A.r_max),
                  bucket_width(np.asarray(L.ranks), A.r_max))
    key = jax.random.PRNGKey(11)
    Lkk, dk = L.D[k], (d[k] if ldl else None)
    reads = _HostPulls()
    Q, V, ranks, info = _column_ara(pipe, A, L, rows, k, perm, d, Lkk, dk,
                                    key, ladder, widths, reads)
    assert reads.total == 1

    Tb, Jb = _column_buckets(A.nb, k, ladder)
    data = _build_column_data(A, L, rows, k, perm, d, ldl, Tb=Tb, Jb=Jb)
    E = pipe.sample(data, jnp.eye(b, dtype=A.dtype))[:T]
    sample, sample_t, E = dense_batch_sampler(E)
    state = init_state(T, b, p, E.dtype)
    tile_iters = np.zeros(T, np.int64)
    while not bool(state.converged.all()) and int(state.it) <= p.iters * T:
        tile_iters += ~np.asarray(state.converged)
        state = ara_iteration(sample, E, state, key, p, share_omega=True,
                              T=T, b=b)
    Vr = jax.vmap(lambda B: jax.scipy.linalg.solve_triangular(
        Lkk, B, lower=True))(sample_t(E, state.Q))
    if ldl:
        Vr = Vr / dk[None, :, None]
    ref = dict(ranks=np.asarray(state.rank), err=np.asarray(state.err),
               iters=int(state.it), tile_iters=tile_iters,
               valve=not bool(state.converged.all()), Q=state.Q, V=Vr)
    return (np.asarray(Q)[:T], np.asarray(V)[:T], np.asarray(ranks)[:T],
            info), ref, p


_PARITY = {
    "cholesky-flat": (tlr_cholesky, dict(batching="flat"), 64, 1e-4, 2),
    "cholesky-ranked": (tlr_cholesky, dict(batching="ranked"), 64, 1e-4, 2),
    "ldlt-flat": (tlr_ldlt, dict(batching="flat"), 64, 1e-4, 2),
    "ldlt-ranked": (tlr_ldlt, dict(batching="ranked"), 64, 1e-4, 2),
    # eps out of reach at r_max 16: every tile fills its buffer and takes
    # one more step, the full-rank step that records its error
    "cholesky-full-rank": (tlr_cholesky, dict(batching="flat"), 16, 1e-9, 2),
    # no prior column (J = 0, padded to Jb), and a one-row column
    "cholesky-first-column": (tlr_cholesky, dict(batching="flat"), 64, 1e-4,
                              0),
    "ldlt-last-column": (tlr_ldlt, dict(batching="ranked"), 64, 1e-4, 6),
}


@pytest.mark.parametrize("case", list(_PARITY))
def test_column_ara_matches_plain_ara(case):
    factor, extra, r_max, eps, k = _PARITY[case]
    opts = CholOptions(eps=eps, bs=8, **extra)
    (Q, V, ranks, info), ref, p = _column_and_reference(factor, opts, r_max,
                                                        k=k)
    np.testing.assert_array_equal(ranks, ref["ranks"])
    np.testing.assert_allclose(info["err"], ref["err"], rtol=1e-9, atol=0)
    np.testing.assert_array_equal(info["tile_iters"], ref["tile_iters"])
    assert info["iters"] == ref["iters"]
    assert info["slots"] == info["Tb"] * info["iters"]
    assert info["safety_valve"] is ref["valve"] is False
    np.testing.assert_allclose(Q, np.asarray(ref["Q"]), rtol=0, atol=1e-9)
    scale = float(np.abs(np.asarray(ref["V"])).max())
    np.testing.assert_allclose(V, np.asarray(ref["V"]), rtol=0,
                               atol=1e-9 * scale)
    if case == "cholesky-full-rank":
        assert (ranks == r_max).all()
        assert info["iters"] == p.iters + 1
    else:
        assert ranks.max() < r_max


def test_column_ara_stops_at_the_safety_valve():
    """The column budget (``p.iters`` steps per row tile, the budget of
    Algorithm 5's slot queue) ends the column's ARA one step past it, as
    it ends the plain loop, with the warning and the partial bases."""
    opts = CholOptions(eps=1e-13, bs=4, max_iters=1, batching="flat")
    with pytest.warns(RuntimeWarning, match="safety valve"):
        (_, _, ranks, info), ref, _ = _column_and_reference(
            tlr_cholesky, opts, 64, k=5)
    T = 2
    assert info["iters"] == ref["iters"] == T + 1
    assert info["safety_valve"] is ref["valve"] is True
    np.testing.assert_array_equal(ranks, ref["ranks"])
    assert (ranks > 0).all()
    np.testing.assert_array_equal(info["tile_iters"], ref["tile_iters"])


@pytest.mark.parametrize("factor", [tlr_cholesky, tlr_ldlt],
                         ids=["cholesky", "ldlt"])
def test_rank_overflow_repass_runs_the_column_ara(monkeypatch, factor):
    """The eps-loosened re-pass of overflowing rows goes through the same
    column ARA as the main pass: one read for its loop, and it keeps
    every retried row within ``r_max_out``."""
    from repro import faults
    from repro.core import cholesky as chol
    from repro.core.tlr import tril_index

    _, K = covariance_problem(256, 1, 32)
    with pytest.warns(FutureWarning):
        A = from_dense(jnp.asarray(K), 32, 32, 1e-10)
    As = faults.spike_rank(A, 4, 1, seed=3, scale=1e-4)
    opts = CholOptions(eps=1e-6, bs=8, r_max_out=16, check=True)
    calls = []
    real = chol._column_ara

    def spy(pipe, A, Lout, rows, k, *args, pull, **kw):
        n0 = pull.total
        out = real(pipe, A, Lout, rows, k, *args, pull=pull, **kw)
        calls.append((pipe.opts, k, list(rows), pull.total - n0, out))
        return out

    monkeypatch.setattr(chol, "_column_ara", spy)
    fact = factor(As, opts)
    loosen = [e for e in fact.stats["health"]["events"]
              if e["remedy"] == "eps_loosen"]
    assert loosen
    repass = [c for c in calls if c[0].eps > opts.eps]
    assert len(calls) == As.nb - 1 + len(repass)
    assert [(c[1], c[2], c[0].eps) for c in repass] \
        == [(e["column"], e["detail"]["rows"], e["detail"]["eps"])
            for e in loosen]
    p = opts.ara_params(16)
    for o, k, rows, n_reads, (_, _, ranks, info) in repass:
        assert (o.batching, o.check) == ("flat", False)
        assert n_reads == 1
        assert (np.asarray(ranks)[:len(rows)] <= 16).all()
        assert not info["safety_valve"] and info["iters"] <= p.iters + 1
    for e in loosen:
        k = e["column"]
        for i in e["detail"]["rows"]:
            assert int(fact.L.ranks[tril_index(i, k)]) <= 16
