"""Telemetry layer (``repro.obs``): span nesting, Chrome-trace schema,
metrics parity with the drivers' ``stats``, and the disabled-mode pin
(ISSUE 8 satellite: no registry drift, bounded overhead when off)."""

import json
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import (CholOptions, TLROperator, trace_counts,
                        trace_counts_diff)
from repro.core.batching import tile_plan


@pytest.fixture(autouse=True)
def _telemetry_off():
    """Every test starts and ends with telemetry disabled -- a leaked
    enabled state would contaminate the rest of the suite's timings."""
    obs.disable()
    yield
    obs.disable()


def _problem(n=256, b=32, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, 2))
    d = np.linalg.norm(X[:, None] - X[None], axis=-1)
    K = np.exp(-d / 0.5) + 1e-2 * np.eye(n)
    return TLROperator.compress(jnp.asarray(K), b, b, 1e-8)


# -- span mechanics ------------------------------------------------------------


def test_span_nesting_and_ordering():
    tel = obs.enable()
    with obs.span("outer", cat="factor", k=0) as outer:
        with obs.span("inner_a", cat="factor"):
            pass
        with obs.span("inner_b", cat="factor") as ib:
            ib.set(flops=10.0)
    obs.disable()
    by_name = {s.name: s for s in tel.spans}
    assert set(by_name) == {"outer", "inner_a", "inner_b"}
    out, ia, ib = by_name["outer"], by_name["inner_a"], by_name["inner_b"]
    # parent/depth linkage
    assert out.parent == -1 and out.depth == 0
    assert ia.parent == out.id and ib.parent == out.id
    assert ia.depth == ib.depth == 1
    # temporal containment and sibling ordering
    assert out.ts <= ia.ts and ia.ts + ia.dur <= ib.ts + ib.dur
    assert ib.ts + ib.dur <= out.ts + out.dur + 1e-9
    assert ib.args["flops"] == 10.0
    assert out.args == {"k": 0}


def test_disabled_span_is_shared_noop():
    assert not obs.enabled()
    h1 = obs.span("a", cat="x", big=list(range(3)))
    h2 = obs.span("b")
    assert h1 is h2 is obs.NOOP_SPAN
    with h1 as h:
        assert h.set(x=1) is h
    assert obs.current() is None


def test_subtree_selection():
    tel = obs.enable()
    with obs.span("r1") as r1:
        with obs.span("c1"):
            with obs.span("g1"):
                pass
    with obs.span("r2"):
        pass
    obs.disable()
    names = {s.name for s in tel.subtree(r1)}
    assert names == {"r1", "c1", "g1"}
    assert {s.name for s in tel.subtree(None)} == {"r1", "c1", "g1", "r2"}


# -- Chrome-trace / Perfetto schema --------------------------------------------


def _assert_chrome_trace_schema(obj):
    """The subset of the Trace Event Format Perfetto actually validates:
    the object form, ph/pid/tid/name on every event, ts+dur on complete
    events, and JSON-serializability of the whole object."""
    assert isinstance(obj, dict) and isinstance(obj["traceEvents"], list)
    json.dumps(obj)  # must be pure-JSON types throughout
    for ev in obj["traceEvents"]:
        assert ev["ph"] in ("X", "C", "M")
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        assert isinstance(ev["name"], str)
        if ev["ph"] == "X":
            assert ev["ts"] >= 0 and ev["dur"] >= 0
        if ev["ph"] == "M":
            assert ev["name"] in ("process_name", "thread_name")
            assert isinstance(ev["args"]["name"], str)


def test_chrome_trace_export_covers_all_layers(tmp_path):
    """One recording spanning factorize + solve + serve exports a valid
    trace containing spans from all three layers (the acceptance
    criterion): per-column phase spans with per-bucket children on the
    factor track, and per-tick spans on the serve track."""
    op = _problem()
    obs.enable()
    fact = op.cholesky(CholOptions(eps=1e-8, algo="right",
                                   batching="ranked"))
    fact.solve(jnp.ones((op.n,)))
    srv = fact.serve(slots=4)
    from repro.serve import ServeRequest

    srv.submit(ServeRequest("solve", rhs=np.ones(op.n)))
    srv.submit(ServeRequest("logdet"))
    srv.run()
    path = tmp_path / "trace.json"
    obj = obs.export_chrome_trace(str(path))
    obs.disable()

    _assert_chrome_trace_schema(obj)
    on_disk = json.loads(path.read_text())
    assert on_disk["traceEvents"]  # file round-trips

    evs = obj["traceEvents"]
    cats = {e.get("cat") for e in evs if e["ph"] == "X"}
    assert {"factor", "solve", "serve"} <= cats
    names = {e["name"] for e in evs if e["ph"] == "X"}
    assert {"chol.factorize", "chol.diag", "chol.panel",
            "trsm.sweep", "serve.tick"} <= names

    # per-column phase spans carry per-bucket children (ranked panel)
    assert "round.bucket" in names
    # serve.tick spans have pack/dispatch/sync-or-evict children on the
    # serve track
    serve_names = {e["name"] for e in evs
                   if e["ph"] == "X" and e.get("cat") == "serve"}
    assert {"serve.tick", "serve.pack", "serve.dispatch",
            "serve.evict"} <= serve_names
    # counter events: the retrace registry fold-in (driver emits one per
    # factorization) and serve occupancy
    counters = {e["name"] for e in evs if e["ph"] == "C"}
    assert "retraces" in counters and "occupancy" in counters
    # one thread-name metadata row per used track
    tids_meta = {e["tid"] for e in evs
                 if e["ph"] == "M" and e["name"] == "thread_name"}
    tids_used = {e["tid"] for e in evs if e["ph"] in ("X", "C")}
    assert tids_used <= tids_meta


def test_span_tree_nesting_in_trace():
    """Factorization spans nest: every chol.panel/chol.diag span lies
    inside the chol.factorize root's [ts, ts+dur] window."""
    op = _problem(n=128, b=32, seed=1)
    obs.enable()
    op.cholesky(CholOptions(eps=1e-8, algo="left"))
    tel = obs.disable()
    roots = [s for s in tel.spans if s.name == "chol.factorize"]
    assert len(roots) == 1
    r = roots[0]
    phases = [s for s in tel.spans if s.name in ("chol.diag", "chol.panel")]
    assert phases
    for s in phases:
        assert r.ts - 1e-9 <= s.ts
        assert s.ts + s.dur <= r.ts + r.dur + 1e-9
        assert s.depth == r.depth + 1


# -- JIT work ------------------------------------------------------------------


def _fresh_jit():
    """A jitted function no earlier call has compiled."""
    import jax

    return jax.jit(lambda x: x * 3.0 + 1.0)


def test_jit_listener_counts_a_fresh_program():
    f = _fresh_jit()
    x = jnp.ones((7, 5))
    tel = obs.enable()
    with obs.span("outer", cat="factor"):
        with obs.span("first", cat="factor"):
            f(x)
        with obs.span("second", cat="factor"):
            f(x)                       # cached: nothing to trace or lower
    obs.disable()
    by_name = {s.name: s for s in tel.spans}
    jit = by_name["first"].jit
    assert jit["traces"] >= 1 and jit["programs"] == 1
    assert jit["lower_s"] > 0 and jit["trace_s"] > 0
    assert jit["compile_s"] > 0
    # only the innermost open span holds it
    assert by_name["second"].jit is None and by_name["outer"].jit is None
    snap = obs.metrics_snapshot(tel)
    assert snap["jit"] == pytest.approx(jit)


def test_jit_listener_silent_while_disabled():
    """With telemetry off the listener records nothing, even into a span
    left open by the recording that was switched off."""
    f = _fresh_jit()
    tel = obs.enable()
    h = obs.span("left_open", cat="factor")
    h.__enter__()
    obs.disable()
    f(jnp.ones((3, 11)))
    h.__exit__(None, None, None)
    assert [s.jit for s in tel.spans] == [None]
    assert obs.metrics_snapshot(tel)["jit"] == dict.fromkeys(
        obs.telemetry.JIT_KEYS, 0)


def test_factorization_snapshot_carries_its_jit_work(fresh_column_steps):
    """A factorization's snapshot counts the programs its own subtree
    compiled: the first left-driver factorization of a configuration jits
    its column steps (the cache is emptied first)."""
    op = _problem(n=128, b=32, seed=5)
    obs.enable()
    fact = op.cholesky(CholOptions(eps=1e-6))
    obs.disable()
    jit = fact.stats["telemetry"]["jit"]
    assert jit["programs"] >= fact.stats["column_traces"] >= 1
    assert jit["traces"] >= jit["programs"]
    assert jit["lower_s"] > 0 and jit["compile_s"] > 0
    # no FLOP rate from host span time
    assert "flops_per_s" not in fact.stats["telemetry"]
    assert all("flops_per_s" not in row
               for row in fact.stats["telemetry"]["phases"].values())


# -- metrics parity with existing stats ----------------------------------------


def test_metrics_parity_with_driver_stats():
    op = _problem()
    obs.enable()
    fact = op.cholesky(CholOptions(eps=1e-8, algo="right",
                                   batching="ranked"))
    obs.disable()
    stats = fact.stats
    snap = stats["telemetry"]
    # the plan-level analytic ratio is copied verbatim from stats["policy"]
    assert snap["padded_flop_ratio_plan"] == \
        stats["policy"]["padded_flop_ratio"]
    # per-column phases: one chol.diag per column, one chol.panel per
    # off-diagonal column (matching column_events), flushes matching stats
    nb = op.nb
    ph = snap["phases"]
    assert ph["chol.diag"]["count"] == nb
    assert ph["chol.panel"]["count"] == len(stats["column_events"]) == nb - 1
    if stats["flushes"]:
        assert ph["chol.flush"]["count"] == stats["flushes"]
    # phase seconds aggregate real wall time: the panel phase total is
    # bounded by the column_events seconds (panel span nests inside the
    # timed column section)
    col_s = sum(e["seconds"] for e in stats["column_events"])
    assert 0 < ph["chol.panel"]["seconds"] <= col_s * 1.5 + 0.5
    # FLOP attribution flows up: padded >= useful > 0 where attached
    if "padded_flop_ratio" in snap:
        assert snap["padded_flop_ratio"] >= 1.0
        assert snap["flops_padded"] >= snap["flops"] > 0
    # retraces snapshot mirrors the registry
    assert set(snap["retraces"]) <= set(trace_counts())


def test_bucket_flops_match_plan_estimates():
    """round.bucket spans carry the same cost_analysis FLOPs as
    TilePlan.bucket_flops at the dispatched shapes."""
    from repro.core.batching import bucketed_round_tiles

    rng = np.random.default_rng(3)
    n, b, w = 24, 16, 16
    ranks = np.zeros(n, np.int64)
    ranks[:20] = rng.integers(1, w + 1, 20)
    U = jnp.asarray(rng.standard_normal((n, b, w)))
    for t in range(n):
        U = U.at[t, :, ranks[t]:].set(0.0)
    V = U
    plan = tile_plan(ranks, w)
    obs.enable()
    bucketed_round_tiles(U, V, ranks, 1e-10, r_out=w)
    tel = obs.disable()
    spans = [s for s in tel.spans if s.name == "round.bucket"]
    assert len(spans) == len(plan.buckets)
    est = plan.bucket_flops(b, w)
    got = sorted(s.args["flops_padded"] for s in spans)
    assert got == sorted(est)
    for s in spans:
        assert 0 < s.args["flops"] <= s.args["flops_padded"]
        assert s.args["bytes"] > 0


def test_server_stats_telemetry_merge_and_null_latencies():
    """ServerStats: empty kinds report null percentiles (not a crash, not
    a fake 0.0), zero-tick servers summarize cleanly, and an enabled
    recording merges the serve-category snapshot into summary()."""
    from repro.serve.stats import ServerStats

    st = ServerStats(slots=4)
    p = st.latency_percentiles("solve")
    assert p["count"] == 0
    assert p["p50_s"] is None and p["p99_s"] is None
    summ = st.summary()           # zero ticks: no NaN, no divide-by-zero
    assert summ["ticks"] == 0 and summ["requests_per_s"] == 0.0
    assert summ["latency"]["p50_s"] is None
    assert "telemetry" not in summ  # disabled mode adds nothing
    json.dumps(summ)               # null-safe JSON

    obs.enable()
    with obs.span("serve.tick", cat="serve"):
        pass
    summ = st.summary()
    obs.disable()
    assert summ["telemetry"]["phases"]["serve.tick"]["count"] == 1


# -- disabled-mode pin ---------------------------------------------------------


def test_disabled_mode_no_registry_drift_and_same_results():
    """With telemetry off, a factorization leaves the compile-count
    registry exactly as the instrumentation-free code would (spans live
    outside jitted bodies), and enabling telemetry afterwards neither
    recompiles nor changes results."""
    op = _problem(n=128, b=32, seed=2)
    o = CholOptions(eps=1e-8, algo="right", batching="ranked")
    fact_cold = op.cholesky(o)           # warm the executables
    snap = trace_counts()
    fact_off = op.cholesky(o)
    assert trace_counts_diff(snap) == {}  # no telemetry, no drift
    assert "telemetry" not in fact_off.stats
    obs.enable()
    fact_on = op.cholesky(o)
    obs.disable()
    assert trace_counts_diff(snap) == {}  # enabled: still zero recompiles
    assert "telemetry" in fact_on.stats
    np.testing.assert_array_equal(np.asarray(fact_on.L.ranks),
                                  np.asarray(fact_off.L.ranks))
    np.testing.assert_allclose(np.asarray(fact_on.L.D),
                               np.asarray(fact_off.L.D), rtol=0, atol=0)
    del fact_cold


def test_disabled_span_overhead_bound():
    """The disabled fast path is a dict-free global check: even a
    pessimistic per-call bound (< 5 us on CPU) keeps any real driver loop
    (thousands of span sites per factorization) under the 5% wall-time
    budget -- a per-call microbench is stable where an end-to-end ratio
    on a ~1 s factorization is timer noise."""
    assert not obs.enabled()
    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        with obs.span("x", cat="factor"):
            pass
    per_call = (time.perf_counter() - t0) / n
    assert per_call < 5e-6, f"disabled span cost {per_call * 1e9:.0f} ns"


@pytest.mark.slow
def test_disabled_mode_wall_time_overhead():
    """End-to-end: a warmed factorization with telemetry off stays within
    5% of itself re-run (the instrumented code *is* the disabled path --
    this guards against accidentally un-gating attribute computation)."""
    op = _problem(n=256, b=32, seed=4)
    o = CholOptions(eps=1e-8, algo="right", batching="ranked")
    op.cholesky(o)                       # warm
    reps = 3
    times = []
    for _ in range(2 * reps):
        t0 = time.perf_counter()
        op.cholesky(o)
        times.append(time.perf_counter() - t0)
    base = min(times[:reps])
    again = min(times[reps:])
    # two interleaved samples of the same disabled path: generous 25%
    # band absorbs CI jitter while still catching a hot un-gated loop
    assert again <= base * 1.25 + 0.05


def test_export_without_recording_raises():
    with pytest.raises(RuntimeError):
        obs.to_chrome_trace()
