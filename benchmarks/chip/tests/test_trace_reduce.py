"""Trace reduction: interval arithmetic by hand, and the reduction of a
small trace recorded on a v5e (``testdata/small.xplane.pb``, written by
``tools/record_testdata.py``: a left Cholesky at N=1024 and a solve)."""

from pathlib import Path

import pytest

import trace_reduce as tr

SMALL = Path(__file__).resolve().parents[1] / "testdata" / "small.xplane.pb"


def test_union_and_gaps_by_hand():
    ivs = [(0, 10), (5, 15), (20, 30), (22, 25)]
    assert tr.union_seconds(ivs) == pytest.approx(25e-9)
    assert tr._gaps(ivs, -5, 40) == [(-5, 0), (15, 20), (30, 40)]
    assert tr._gaps(ivs, 0, 12) == []


def test_hlo_names():
    assert tr.hlo_name("%lr_sample_pallas.1 = f32[7,512,16]{2,1,0} "
                       "custom-call(f32[7,2,512,128] %p)") == \
        "lr_sample_pallas"
    assert tr.hlo_name("fusion.12") == "fusion"
    assert tr.hlo_name("%copy-start.6 = (f32[7]) copy-start()") == \
        "copy-start"


def test_attribution_takes_the_innermost_span():
    events = [(0, 100, "bench.window"), (10, 50, "chol.panel"),
              (20, 30, "np.asarray(jax.Array)"), (60, 90, "serve.tick")]
    assert tr._attribute([5, 25, 40, 70, 95], events) == [
        "bench.window", "chol.panel > np.asarray(jax.Array)", "chol.panel",
        "serve.tick", "bench.window"]


def test_recorded_trace():
    s = tr.reduce_trace(str(SMALL))
    assert s.devices == 1
    assert 0 < s.busy_s <= s.window_s
    lr = s.kernel_seconds("lr_sample_pallas")
    assert lr > 0
    assert lr == pytest.approx(s.op_seconds["lr_sample_pallas"])
    idle = sum(s.idle_gaps.values())
    assert idle <= s.window_s - s.busy_s + 1e-9
    assert s.gap_count > 0
    assert any(k.startswith("chol.") for k in s.idle_gaps)
    b = s.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][1] == max(s.op_seconds.values())


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce_trace(str(SMALL), window="no.such.window")
