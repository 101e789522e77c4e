"""A whole run of each cell on the CPU at a small size: the traffic
driven, the metrics reported, the check passed; and the same run with
the timed path broken underneath, which the check has to refuse."""

import numpy as np
import pytest

import run


def _run(ctx, spec_):
    run.run_cell(ctx, spec_)
    ctx.watchdog.stop()
    return ctx.result


@pytest.mark.parametrize("cell", ["cov3d-factor-left", "cov2d-factor-left"])
def test_factor_cell_sound(make_ctx, cell):
    ctx, spec_ = make_ctx(cell, seconds=0.5)
    res = _run(ctx, spec_)
    assert res.correct(), res.line()
    assert set(res.metrics) == {"setup_s", "factor_s"}
    assert res.attempted >= 1 and res.failed == 0
    assert [n for n, _, _ in res.checks] == [
        "compress_err", "logdet_rel", "solve1_berr", "solve16_berr"]


def _altered_factor(monkeypatch):
    """A factorization whose answer is altered where it is produced: one
    off-diagonal tile of L scaled."""
    from repro.core import operator as op_mod

    real = op_mod.TLROperator.cholesky

    def broken(self, opts=None):
        fact = real(self, opts)
        fact.L.U = fact.L.U.at[fact.L.U.shape[0] // 2].multiply(1.5)
        return fact

    monkeypatch.setattr(op_mod.TLROperator, "cholesky", broken)


def _unchanged_state(monkeypatch):
    """A step that returns its state unchanged: every ARA step of the
    left driver hands back the state it was given, converged."""
    from repro.core import cholesky as chol

    def stuck(self, *a, **k):
        real(self, *a, **k)
        dyn = self.dyn_step

        def step(data, state, key):
            dyn(data, state, key)
            return state._replace(converged=state.converged | True)

        self.dyn_step = step

    real = chol._ColumnPipeline.__init__
    monkeypatch.setattr(chol._ColumnPipeline, "__init__", stuck)


@pytest.mark.parametrize("fault", [_altered_factor, _unchanged_state])
def test_factor_cell_refuses_faults(make_ctx, monkeypatch, fault):
    fault(monkeypatch)
    ctx, spec_ = make_ctx("cov3d-factor-left", seconds=0.2)
    res = _run(ctx, spec_)
    assert not res.correct(), res.line()


def test_watchdog_prints_the_last_line(make_ctx, capsys):
    """A stalled phase still ends with the result line, correct false."""
    import json
    import os
    import time

    ctx, spec_ = make_ctx("cov3d-factor-left")
    exits = []
    real_exit = os._exit
    try:
        os._exit = exits.append
        ctx.watchdog.arm("setup", 0.1)
        time.sleep(1.5)
    finally:
        os._exit = real_exit
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert exits == [3]
    assert line["correct"] is False
    assert list(line)[-1] == "checks"
    assert np.isfinite(line["attempted"])
