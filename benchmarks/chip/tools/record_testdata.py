#!/usr/bin/env python3
"""Record the small device trace that ``tests/test_trace_reduce.py``
reads: a left Cholesky at N=1024, tile 256, and a solve, under the
program's spans and the harness's ``bench.window`` annotation. Needs the
chip; writes ``testdata/small.xplane.pb``.

    python3 benchmarks/chip/tools/record_testdata.py
"""

from __future__ import annotations

import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import bench  # noqa: E402
import problem  # noqa: E402
import trace_reduce  # noqa: E402


def main() -> int:
    sys.path.insert(0, str(bench.ROOT / "src"))
    import jax
    import jax.numpy as jnp
    from repro import obs
    from repro.core import CholOptions, TLROperator

    bench.device_info(1)
    cfg = {"n": 1024, "dim": 3, "tile": 256, "ell": 0.2, "nugget": 1e-8}
    K = problem.dense_covariance(problem.points(cfg, 5), cfg)
    op = TLROperator.compress(K, 256, 64, 1e-3, method="ara", bs=16)
    opts = CholOptions(eps=1e-2, bs=16, seed=5)
    y = jnp.ones((1024, 4), jnp.float32)
    obs.enable()
    fact = op.cholesky(opts)                 # compiles, outside the trace
    fact.solve(y).block_until_ready()
    out = Path(tempfile.mkdtemp())
    po = jax.profiler.ProfileOptions()
    po.python_tracer_level = 0
    po.host_tracer_level = 2
    jax.profiler.start_trace(str(out), profiler_options=po)
    with jax.profiler.TraceAnnotation("bench.window"):
        fact = op.cholesky(opts)
        fact.solve(y).block_until_ready()
    jax.profiler.stop_trace()
    obs.disable()
    dest = HERE / "testdata" / "small.xplane.pb"
    dest.parent.mkdir(exist_ok=True)
    shutil.copy(trace_reduce.find_xplane(str(out)), dest)
    shutil.rmtree(out)
    s = trace_reduce.reduce_trace(str(dest))
    print(f"{dest}: {dest.stat().st_size} bytes, window {s.window_s} s, "
          f"busy {s.busy_s} s, lr_sample_pallas "
          f"{s.kernel_seconds('lr_sample_pallas')} s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
