"""The readers of the left driver's telemetry metrics on synthetic
readings: each value from the stats it names, and None where the
factorization's stats lack what it reads (no traced factorization, a
program without the counter, the fused ARA mode)."""

import numpy as np
import pytest

import bench
import run

NEW = ["chol.jit_s", "chol.syncs_per_col", "chol.sync_wait_s",
       "ara.slot_occupancy"]


def _stats():
    """What the left driver's stats hold at nb = 3 under telemetry."""
    return {
        "column_iters": [4, 2],
        "syncs": 21,
        "column_events": [
            {"k": 0, "T": 2, "Tb": 2, "syncs": 9, "slots": 8,
             "tile_iters": np.array([4, 3])},
            {"k": 1, "T": 1, "Tb": 1, "syncs": 5, "slots": 2,
             "tile_iters": np.array([2])},
        ],
        "telemetry": {
            "jit": {"trace_s": 0.25, "lower_s": 1.5, "compile_s": 0.75,
                    "traces": 40, "programs": 6},
            "phases": {"chol.pull": {"count": 21, "seconds": 0.5}},
        },
    }


@pytest.mark.parametrize("name", NEW)
def test_none_without_stats(name):
    assert run.read_metric(name, bench.Readings()) is None


@pytest.mark.parametrize("name", NEW)
def test_none_from_a_program_without_the_counters(name):
    """Stats as a program without these counters gives them, untraced."""
    stats = {"column_iters": [4, 2],
             "column_events": [{"k": 0, "T": 2}, {"k": 1, "T": 1}]}
    assert run.read_metric(name, bench.Readings(factor_stats=[stats])) \
        is None


def test_values_from_the_last_factorization():
    r = bench.Readings(factor_stats=[{}, _stats()])
    assert run.read_metric("chol.jit_s", r) == pytest.approx(2.5)
    assert run.read_metric("chol.syncs_per_col", r) == pytest.approx(7.0)
    assert run.read_metric("chol.sync_wait_s", r) == pytest.approx(0.5)
    # (4 + 3 + 2) live slot-iterations over 8 + 2 dispatched
    assert run.read_metric("ara.slot_occupancy", r) == pytest.approx(90.0)


def test_fused_mode_has_no_occupancy():
    stats = _stats()
    for e in stats["column_events"]:
        e["tile_iters"] = e["slots"] = None
    r = bench.Readings(factor_stats=[stats])
    assert run.read_metric("ara.slot_occupancy", r) is None
    assert run.read_metric("chol.syncs_per_col", r) == pytest.approx(7.0)


def test_metrics_declared_for_the_factor_cells():
    """Each new metric has its entry and its reader, in both cells."""
    spec_ = bench.spec()
    per_layer = {m["name"]: m for m in spec_["per_layer"]}
    for name in NEW:
        m = per_layer[name]
        assert m["moves"] == "factor_s"
        assert m["workloads"] == ["cov3d-factor-left", "cov2d-factor-left"]
        assert (bench.BENCH / "metrics" / f"{name}.py").is_file()
