"""Shape-stable column pipeline: compile-count regression + impl parity.

The factorization driver pads every column's row batch up to a power-of-two
bucket ladder (DESIGN.md section 2) so a handful of compiled ARA-step
variants serve all nb columns. These tests pin that contract:

* the trace counter in ``stats`` stays at O(log nb) executables,
* bucket padding does not change the math (padded slots are inert),
* the Pallas kernels dispatched through ``CholOptions.impl`` match the
  pure-jnp reference end-to-end through a full factorization.
"""

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


@pytest.mark.parametrize("mode", ["dynamic", "fused"])
def test_column_step_compile_count(fresh_column_steps, mode):
    """nb=8, b=64: the ARA column step compiles <= log2(nb)+1 variants."""
    _, A = _problem(n=512, b=64)
    assert A.nb == 8
    fact = tlr_cholesky(A, CholOptions(eps=1e-6, bs=8, mode=mode))
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


@pytest.mark.parametrize("mode", ["dynamic", "fused"])
@pytest.mark.parametrize("factor", [tlr_cholesky, tlr_ldlt],
                         ids=["cholesky", "ldlt"])
def test_warm_factorization_traces_nothing(factor, mode):
    """The column steps are cached across factorizations: a second one
    with the same options traces, lowers and compiles nothing (its JIT
    work read under telemetry, which the first ran without) and returns
    the first one's factor bit for bit."""
    _, A = _problem(n=512, b=64)
    opts = CholOptions(eps=1e-6, bs=8, mode=mode)
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


def test_explicit_bucket_still_bounded():
    """Algorithm 5 slot buffers (bucket>0) stay ladder-bounded as well."""
    _, A = _problem(n=512, b=64)
    fact = tlr_cholesky(A, CholOptions(eps=1e-6, bs=8, mode="dynamic",
                                       bucket=3))
    # slot batch (one bucketed size) + tail columns: still a handful
    assert fact.stats["column_traces"] <= 2 * (int(math.log2(A.nb)) + 1)


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


@pytest.mark.parametrize("mode", ["dynamic", "fused"])
def test_impl_interpret_matches_ref(mode):
    """Pallas interpreter path == pure-jnp path through a full factorization."""
    _, A = _problem(n=256, b=64, r_max=32)
    facts = {}
    for impl in ("ref", "interpret"):
        f = tlr_cholesky(A, CholOptions(eps=1e-6, bs=8, mode=mode, impl=impl))
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
        fact = tlr_cholesky(A, CholOptions(eps=1e-13, bs=4, mode="dynamic",
                                           max_iters=1))
    assert fact.stats["safety_valve"] is True
    assert np.isfinite(np.asarray(fact.L.V)).all()
    assert np.isfinite(np.asarray(fact.L.U)).all()
    # flushed partial bases still carry the ranks accumulated so far
    for ranks in fact.stats["column_ranks"]:
        assert (np.asarray(ranks) > 0).any()


def test_no_safety_valve_on_converging_problems():
    _, A = _problem(n=256, b=64)
    fact = tlr_cholesky(A, CholOptions(eps=1e-6, bs=8, mode="dynamic"))
    assert fact.stats["safety_valve"] is False


@pytest.mark.parametrize("mode", ["dynamic", "fused"])
def test_column_events_report_per_tile_err(mode):
    """Stats-schema parity: dynamic-mode columns report the same per-tile
    ARA error estimates fused mode always has."""
    _, A = _problem(n=256, b=64)
    fact = tlr_cholesky(A, CholOptions(eps=1e-6, bs=8, mode=mode))
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


# -- the dynamic ARA loop on the device against the host loop ------------------


def _column_both_ways(factor, opts, r_max, k):
    """Column ``k`` of an f32 factorization run through the device ARA loop
    and through the host loop with one batch for the whole column (the
    queue empty after the first fill), on the same operands and key."""
    from repro.core.batching import bucket_width
    from repro.core.cholesky import (_ColumnPipeline, _HostPulls,
                                     _ara_device_loop, _ara_host_loop,
                                     _build_column_data)

    _, K = covariance_problem(512, 3, 64)
    A = from_dense(jnp.asarray(K, jnp.float32), 64, r_max, 1e-7)
    fact = factor(A, opts)
    L, d = fact.L, fact.d
    p = opts.ara_params(A.r_max)
    pipe = _ColumnPipeline(opts, p)
    ladder = _bucket_ladder(A.nb - 1)
    rows, perm = np.arange(k + 1, A.nb), np.arange(A.nb)
    Tb, Jb = _column_buckets(A.nb, k, ladder)
    widths = (None, None)
    if opts.batching == "ranked":
        widths = (bucket_width(np.asarray(A.ranks), A.r_max),
                  bucket_width(np.asarray(L.ranks), A.r_max))
    data = _build_column_data(A, L, rows, k, perm, d, opts.ldl, Tb=Tb,
                              Jb=Jb, wA=widths[0], wL=widths[1])
    key = jax.random.PRNGKey(11)
    dk = d[k] if opts.ldl else None
    reads = _HostPulls()
    Qd, ranks_d, ranks_dh, info_d = _ara_device_loop(pipe, data, key, k,
                                                     len(rows), reads)
    assert reads.total == 1
    Qh, ranks_hh, info_h = _ara_host_loop(
        pipe, A, L, rows, k, perm, d, key, Tb, Tb, Jb, widths, _HostPulls(),
        np.asarray(A.ranks))
    np.testing.assert_array_equal(np.asarray(ranks_d)[:len(rows)], ranks_dh)
    return dict(
        device=(Qd, ranks_dh, info_d, pipe.project(data, Qd, L.D[k], dk)),
        host=(Qh, ranks_hh, info_h, pipe.project(data, Qh, L.D[k], dk)),
        p=p, Tb=Tb)


_PARITY = {
    "cholesky-flat": (tlr_cholesky, dict(batching="flat"), 64, 1e-4),
    "cholesky-ranked": (tlr_cholesky, dict(batching="ranked"), 64, 1e-4),
    "ldlt-flat": (tlr_ldlt, dict(batching="flat"), 64, 1e-4),
    "ldlt-ranked": (tlr_ldlt, dict(batching="ranked"), 64, 1e-4),
    # eps out of reach at r_max 16: every tile fills its buffer and takes
    # one more step, the full-rank step that records its error
    "cholesky-full-rank": (tlr_cholesky, dict(batching="flat"), 16, 1e-9),
}


@pytest.mark.parametrize("case", list(_PARITY))
def test_device_ara_loop_matches_the_host_loop(case):
    factor, extra, r_max, eps = _PARITY[case]
    opts = CholOptions(eps=eps, bs=8, **extra)
    with jax.enable_x64(False):
        run = _column_both_ways(factor, opts, r_max, k=2)
        (Qd, rd, info_d, Vd), (Qh, rh, info_h, Vh) = run["device"], run["host"]
        np.testing.assert_array_equal(rd, rh)
        np.testing.assert_array_equal(info_d["err"], info_h["err"])
        np.testing.assert_array_equal(info_d["tile_iters"], info_h["tile_iters"])
        assert info_d["iters"] == info_h["iters"]
        assert info_d["safety_valve"] is info_h["safety_valve"] is False
        assert (info_d["ara_loop"], info_h["ara_loop"]) == ("device", "host")
        np.testing.assert_allclose(np.asarray(Qd), np.asarray(Qh),
                                   rtol=0, atol=1e-6)
        scale = float(np.abs(np.asarray(Vh)).max())
        np.testing.assert_allclose(np.asarray(Vd), np.asarray(Vh),
                                   rtol=0, atol=1e-6 * scale)
    if case == "cholesky-full-rank":
        assert (rd == r_max).all()
        assert info_d["iters"] == run["p"].iters + 1
    else:
        assert rd.max() < r_max


def test_device_ara_loop_stops_at_the_host_loops_safety_valve():
    """The column budget (``p.iters`` steps per row tile) ends both loops
    at the same step, one past the budget, and both flush the partial
    bases with the same warning."""
    opts = CholOptions(eps=1e-13, bs=4, max_iters=1, batching="flat")
    with jax.enable_x64(False), pytest.warns(RuntimeWarning,
                                             match="safety valve"):
        run = _column_both_ways(tlr_cholesky, opts, 64, k=5)
    (_, rd, info_d, _), (_, rh, info_h, _) = run["device"], run["host"]
    T = 2
    assert info_d["iters"] == info_h["iters"] == T + 1
    assert info_d["safety_valve"] is info_h["safety_valve"] is True
    np.testing.assert_array_equal(rd, rh)
    np.testing.assert_array_equal(info_d["tile_iters"], info_h["tile_iters"])


def test_refilling_columns_keep_the_host_loop():
    """Under ``bucket > 0`` a column with more rows than slots evicts and
    refills on the host; a column that fits its slots runs on the device."""
    _, A = _problem(n=512, b=64)
    fact = tlr_cholesky(A, CholOptions(eps=1e-6, bs=8, bucket=2))
    events = fact.stats["column_events"]
    host = [e for e in events if e["T"] > e["Tb"]]
    assert len(host) == 5
    for e in events:
        assert e["ara_loop"] == ("host" if e in host else "device")
    for e in host:
        # every row held a slot, though fewer slots than rows: refilled
        assert len(e["tile_iters"]) == e["T"] > e["Tb"]
        assert e["tile_iters"].min() >= 1
    assert fact.stats["ara_device_columns"] == len(events) - len(host)
    Ld = _dense_L(fact)
    K, _ = _problem(n=512, b=64)
    assert np.linalg.norm(K - Ld @ Ld.T, 2) < 1e-4
