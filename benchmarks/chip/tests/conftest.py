"""The benchmark's own tests, run by path:

    python -m pytest -q benchmarks/chip/tests

They run on the CPU at small sizes (f32, x64 off, the kernels' reference
path); ``make_ctx`` builds a run of a cell as ``run.py`` would, with the
configuration cut down, and without the look for a chip.
"""

import math
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import bench  # noqa: E402

SMALL = {"n": 1024, "tile": 128, "r_max": 64}
# Limits for this small size, about three times what sound runs read here
# on the CPU (compress 2.1e-5..3.4e-5, logdet 3.4e-3..5.5e-3, solve
# backward error 3.9e-5..6.5e-5); the cells' own limits are set for
# N=32768 on the chip.
FACTOR_LIMITS = {"compress_err": 1e-4, "logdet_rel": 3e-2,
                 "solve1_berr": 2e-4, "solve16_berr": 2e-4}


@pytest.fixture
def make_ctx():
    def make(cell, seconds=1.0, trace=False, cfg=None, traffic=None,
             seed=20260917):
        spec_ = bench.spec()
        work, c, t, _ = bench.cell_files(cell, spec_)
        c = {**c, **SMALL, **(cfg or {})}
        t = {**t, **(traffic or {})}
        res = bench.Result(spec_, trace)
        res.device = {"platform": "cpu", "kind": "cpu", "count": 1}
        ctx = bench.Context(
            cell=cell, seed=seed, seconds=seconds, trace=trace, cfg=c,
            traffic=t, limits=FACTOR_LIMITS, result=res,
            counter=bench.CompileCounter(), watchdog=bench.Watchdog(res),
            time_limit=math.inf, t_start=time.perf_counter())
        ctx.readings.device_kind = "TPU v5 lite"
        return ctx, spec_

    return make
