"""The left driver's own counts: its device-to-host reads (against an
independent count of every host-read entry point it uses), the dynamic
ARA loop's slot occupancy, and the spans it opens under telemetry, which
leave the factors bit for bit as they are without it."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.array import ArrayImpl

from repro import obs
from repro.core import CholOptions, TLROperator


@pytest.fixture(autouse=True)
def _telemetry_off():
    obs.disable()
    yield
    obs.disable()


def _problem(n=256, b=32, seed=0):
    """nb = 8 tile columns of a 2D exponential covariance."""
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, 2))
    d = np.linalg.norm(X[:, None] - X[None], axis=-1)
    K = np.exp(-d / 0.5) + 1e-2 * np.eye(n)
    return TLROperator.compress(jnp.asarray(K), b, b, 1e-8)


@contextlib.contextmanager
def _host_reads(monkeypatch):
    """Count every read of a device array to the host. On the CPU backend
    ``np.asarray`` takes the buffer protocol and never calls
    ``ArrayImpl.__array__``, and ``jax.transfer_guard`` does not fire, so
    each entry point is wrapped: the NumPy constructors, the scalar
    conversions, the list/item reads and ``jax.device_get``, which reads
    a whole pytree in one go. Nested entries count once."""
    count = [0]
    depth = [0]

    def wrap(owner, name, device_arg):
        real = getattr(owner, name)

        def counted(*args, **kwargs):
            hit = depth[0] == 0 and isinstance(device_arg(args), jax.Array)
            count[0] += hit
            depth[0] += 1
            try:
                return real(*args, **kwargs)
            finally:
                depth[0] -= 1

        monkeypatch.setattr(owner, name, counted)

    for name in ("asarray", "array"):
        wrap(np, name, lambda a: a[0] if a else None)
    for name in ("__array__", "__int__", "__float__", "__bool__",
                 "__index__", "__complex__", "item", "tolist"):
        wrap(ArrayImpl, name, lambda a: a[0])
    wrap(jax, "device_get",
         lambda a: next((x for x in jax.tree_util.tree_leaves(a[0])
                         if isinstance(x, jax.Array)), None))
    yield count


OPTIONS = {
    "dynamic": CholOptions(eps=1e-6),
    "dynamic-ranked": CholOptions(eps=1e-6, batching="ranked"),
    "bucketed": CholOptions(eps=1e-6, bucket=2),
    "fused": CholOptions(eps=1e-6, mode="fused"),
    "fused-ranked": CholOptions(eps=1e-6, mode="fused", batching="ranked"),
    "checked": CholOptions(eps=1e-6, check=True),
    "pivoted": CholOptions(eps=1e-6, pivot="frobenius"),
}


@pytest.mark.parametrize("case", list(OPTIONS))
def test_pull_count_equals_an_independent_count(monkeypatch, case):
    op = _problem()          # fresh ranks: the batching plan is read too
    opts = OPTIONS[case]
    with _host_reads(monkeypatch) as reads:
        fact = op.cholesky(opts)
    stats = fact.stats
    assert op.nb == 8
    assert stats["syncs"] == reads[0] > 0
    per_col = [e["syncs"] for e in stats["column_events"]]
    assert len(per_col) == op.nb - 1 and min(per_col) > 0
    assert sum(per_col) + stats["diag_syncs"] <= stats["syncs"]
    # one pull per diagonal: the modified-Cholesky flag (and the pivot)
    assert stats["diag_syncs"] == op.nb * (1 + (opts.pivot is not None))
    if opts.mode == "dynamic" and opts.bucket == 0 and not opts.check:
        # per column: the diagonal's reads, the ARA loop's one read and
        # the panel's end; once per factorization: A's ranks
        assert stats["syncs"] <= op.nb * (3 + (opts.pivot is not None))
        assert per_col == [2] * (op.nb - 1)


@pytest.mark.parametrize("case", ["dynamic", "dynamic-ranked", "bucketed"])
def test_slot_occupancy_bounds(case):
    fact = _problem(seed=1).cholesky(OPTIONS[case])
    events = fact.stats["column_events"]
    for e, iters in zip(events, fact.stats["column_iters"]):
        ti = e["tile_iters"]
        assert len(ti) == e["T"]
        assert ti.min() >= 1 and ti.max() <= iters
        assert e["slots"] == e["Tb"] * iters
    occ = sum(int(e["tile_iters"].sum()) for e in events) \
        / sum(e["slots"] for e in events)
    assert 0 < occ <= 1


def test_default_options_run_every_ara_loop_on_the_device():
    fact = _problem(seed=1).cholesky(OPTIONS["dynamic"])
    events = fact.stats["column_events"]
    assert fact.stats["ara_device_columns"] == len(events) == 7
    assert {e["ara_loop"] for e in events} == {"device"}


def test_fused_mode_records_no_occupancy():
    fact = _problem(seed=1).cholesky(OPTIONS["fused"])
    assert all(e["tile_iters"] is None and e["slots"] is None
               for e in fact.stats["column_events"])


@pytest.mark.parametrize("case", ["dynamic", "fused-ranked", "bucketed"])
def test_telemetry_leaves_the_factor_bit_identical(case):
    op = _problem(seed=2)
    opts = OPTIONS[case]
    op.cholesky(opts)                # reads the batching plan once
    off = op.cholesky(opts)
    tel = obs.enable()
    on = op.cholesky(opts)
    obs.disable()
    for a, b in ((off.L.ranks, on.L.ranks), (off.L.U, on.L.U),
                 (off.L.V, on.L.V), (off.L.D, on.L.D)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert on.stats["syncs"] == off.stats["syncs"]
    # the spans the panel stage opens, one per event they stand for
    names = [s.name for s in tel.spans]
    assert names.count("chol.pull") == on.stats["syncs"]
    assert names.count("chol.commit") == op.nb - 1
    assert names.count("chol.project") == op.nb - 1
    # one per step of the host ARA loop; the device loop opens none
    host_iters = sum(it for it, e in zip(on.stats["column_iters"],
                                         on.stats["column_events"])
                     if e["ara_loop"] == "host")
    assert names.count("chol.ara_iter") == host_iters
    ph = on.stats["telemetry"]["phases"]
    assert ph["chol.pull"]["count"] == on.stats["syncs"]
    # a pull is inside the span it serves: the ARA step's, the panel's,
    # the diagonal's or the factorization's
    by_id = {s.id: s for s in tel.spans}
    parents = {by_id[s.parent].name for s in tel.spans
               if s.name == "chol.pull"}
    assert parents <= {"chol.ara_iter", "chol.panel", "chol.diag",
                       "chol.factorize"}
