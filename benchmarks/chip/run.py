#!/usr/bin/env python3
"""The chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 benchmarks/chip/run.py --workload cov3d-factor-left \\
        --seed 1234 --seconds 45 --trace 0

A cell names a configuration (``configs/<config>.json``) and a traffic
mix (``traffic/<mix>.json``); the mix's ``kind`` names the driver that
runs it (``drivers/<kind>.py``); ``limits/<cell>.json`` holds the limits of
its correctness check; each per-layer metric is read by
``metrics/<metric>.py``. A new cell, configuration, mix or metric is new
files and new entries in ``BENCHMARK.json``.

The run sets up (inputs from ``--seed``, every program the window uses
compiled or loaded from ``<checkout>/.jax_cache``), measures for
``--seconds``, then checks what the window produced against plain
references. With ``--trace 0`` it reports the cell's end-to-end metrics;
with ``--trace 1`` a shorter traced window and its per-layer metrics,
with the device's busy and window seconds and a breakdown. The last line
of standard output is the result; a run without a TPU, or with fewer
chips than the cell asks for, or without the program beside the
benchmark, exits non-zero and prints none.

``--precision`` runs the library's contractions at a lower matmul
precision than the configuration states: the control that the check
must refuse.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import bench  # noqa: E402


def cell_metrics(spec_: dict, cell: str, kind: str) -> list[dict]:
    """The cell's end-to-end (``kind="end_to_end"``) or per-layer metrics:
    those without a ``workloads`` list, and those whose list names it."""
    return [m for m in spec_[kind]
            if "workloads" not in m or cell in m["workloads"]]


def read_metric(name: str, readings):
    """The value of per-layer metric ``name`` from its reader
    ``metrics/<name>.py``, or None where it finds nothing to read."""
    path = HERE / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read(readings)


def run_cell(ctx: bench.Context, spec_: dict) -> None:
    """Drive the cell's traffic and fill in the result's metrics."""
    driver = importlib.import_module(f"drivers.{ctx.traffic['kind']}")
    driver.run(ctx)
    res = ctx.result
    if ctx.trace:
        for m in cell_metrics(spec_, ctx.cell, "per_layer"):
            v = read_metric(m["name"], ctx.readings)
            if v is not None:
                res.metrics[m["name"]] = float(v)
    else:
        for m in cell_metrics(spec_, ctx.cell, "end_to_end"):
            res.metrics[m["name"]] = float(ctx.e2e[m["name"]])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--precision", choices=("highest", "high", "default"),
                    default=None)
    args = ap.parse_args(argv)

    spec_ = bench.spec()
    work, cfg, traffic, limits = bench.cell_files(args.workload, spec_)
    if not (bench.ROOT / "src" / "repro").is_dir():
        bench.log(f"no program under test: {bench.ROOT / 'src' / 'repro'} "
                  "is missing")
        return 2
    sys.path.insert(0, str(bench.ROOT / "src"))
    try:
        device = bench.device_info(work["chips"])
    except RuntimeError as e:
        bench.log(str(e))
        return 2
    cold = not (bench.CACHE_DIR.is_dir() and any(bench.CACHE_DIR.iterdir()))
    bench.enable_compile_cache()
    result = bench.Result(spec_, bool(args.trace))
    result.device = device
    ctx = bench.Context(
        cell=args.workload, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), cfg=cfg, traffic=traffic, limits=limits,
        result=result, counter=bench.CompileCounter(),
        watchdog=bench.Watchdog(result),
        time_limit=bench.COLD_LIMIT_S if cold else bench.WARM_LIMIT_S,
        t_start=T_START)
    ctx.readings.device_kind = device["kind"]
    ctx.watchdog.arm("setup", ctx.time_limit - args.seconds
                     - bench.CHECK_RESERVE_S)
    precision = args.precision or cfg["matmul_precision"]
    bench.set_matmul_precision(precision)
    bench.log(f"cell {args.workload}: config {work['config']}, traffic "
              f"{work['traffic']}, seed {args.seed}, {args.seconds} s, trace "
              f"{args.trace}, matmul precision {precision}, device "
              f"{device}, compile cache {'cold' if cold else 'warm'}")
    try:
        run_cell(ctx, spec_)
    except Exception:  # noqa: BLE001 -- the run is reported as not correct
        traceback.print_exc()
        result.errors.append(traceback.format_exc(limit=1).strip()
                             .splitlines()[-1])
    ctx.watchdog.stop()
    result.emit()
    return 0


if __name__ == "__main__":
    sys.exit(main())
