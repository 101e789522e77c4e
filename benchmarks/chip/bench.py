"""What every cell shares: the spec files, the device check, the compile
counter, the watchdog and the result line.

A run prints, in this order: progress on standard error; each compared
number beside its limit as the last lines of standard error; one JSON
object as the last line of standard output, with the keys ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (``breakdown`` in a
traced run) and, last, ``checks``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import math
import os
import shutil
import sys
import threading
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
CACHE_DIR = ROOT / ".jax_cache"

# A first run in a checkout compiles every program and may take 1200 s;
# a run whose cache is filled must end within 360 s. The watchdog ends a
# run a little before either.
COLD_LIMIT_S = 1140.0
WARM_LIMIT_S = 330.0
# Room kept after the window for the correctness check.
CHECK_RESERVE_S = 90.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def spec(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def cell_files(cell: str, spec_: dict) -> tuple[dict, dict, dict, dict]:
    """The workload entry, its configuration, its traffic mix and its
    limits, each from its own file, found by name."""
    work = {w["name"]: w for w in spec_["workloads"]}
    if cell not in work:
        raise SystemExit(f"unknown workload {cell!r}; known: {sorted(work)}")
    w = work[cell]
    conf = {c["name"]: c for c in spec_["configs"]}[w["config"]]
    cfg = load_json(ROOT / conf["file"])
    traffic = load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    limits = load_json(BENCH / "limits" / f"{cell}.json")
    return w, cfg, traffic, limits


def seed32(seed: int, salt: int = 0) -> int:
    """A 31-bit seed for JAX's PRNG keys, from any whole number."""
    import numpy as np

    return int(np.random.default_rng([seed, salt]).integers(0, 2**31 - 1))


# -- device ----------------------------------------------------------------------


def device_info(chips: int) -> dict:
    """Platform, kind and count as JAX reports them; raises without a TPU
    or with fewer chips than the cell asks for."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise RuntimeError(f"needs a TPU, JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise RuntimeError(f"the cell needs {chips} chips, JAX found "
                           f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int | None:
    """Peak bytes in use on the fullest device, where the backend says."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``,
    with every program kept (JAX keeps only those that took a second or
    more to compile by default), so a second run compiles nothing."""
    import jax

    CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return str(CACHE_DIR)


def set_matmul_precision(name: str) -> None:
    """Carry the matmul precision the configuration states (or the
    control's lower one) into the library before anything is traced: the
    library names this precision in every XLA contraction, so a
    process-wide default would change nothing."""
    from jax import lax
    from repro import precision

    precision.MATMUL_PRECISION = {
        "highest": lax.Precision.HIGHEST, "high": lax.Precision.HIGH,
        "default": lax.Precision.DEFAULT}[name]


class CompileCounter:
    """Programs the process compiled or loaded from the persistent cache
    (one ``backend_compile`` event each), and of those the cache hits."""

    def __init__(self):
        import jax.monitoring

        self.programs = 0
        self.hits = 0
        self.seconds = 0.0

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.programs += 1
                self.seconds += secs

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


# -- the result line --------------------------------------------------------------


class Result:
    """The run's result: counts, metrics, device, checks. Emitted once,
    by the main thread or by the watchdog, whichever comes first."""

    def __init__(self, spec_: dict, trace: bool):
        self.trace = trace
        self.units = {m["name"]: m["unit"]
                      for m in spec_["end_to_end"] + spec_["per_layer"]}
        self.attempted = 0
        self.failed = 0
        self.metrics: dict[str, float] = {}
        self.device: dict = {}
        self.breakdown: dict | None = None
        self.checks: list[tuple[str, float, float]] = []
        self.errors: list[str] = []
        self._lock = threading.Lock()
        self._emitted = False

    def check(self, name: str, value: float, limit: float) -> bool:
        """Record one compared number; it passes at or under its limit
        (NaN never passes)."""
        value = float(value)
        self.checks.append((name, value, float(limit)))
        return value <= limit

    def correct(self) -> bool:
        return (not self.errors and bool(self.checks)
                and all(v <= lim for _, v, lim in self.checks))

    def line(self) -> dict:
        out = {
            "correct": self.correct(),
            "attempted": self.attempted,
            "failed": self.failed,
            "metrics": {k: {"value": v, "unit": self.units[k]}
                        for k, v in self.metrics.items()},
            "device": self.device,
        }
        if self.trace and self.breakdown is not None:
            out["breakdown"] = self.breakdown
        out["checks"] = {n: {"value": _num(v), "limit": lim}
                         for n, v, lim in self.checks}
        return out

    def emit(self) -> bool:
        """Print the check lines and the result line, once."""
        with self._lock:
            if self._emitted:
                return False
            self._emitted = True
            line = self.line()
            for e in self.errors:
                log(f"error: {e}")
            for n, v, lim in self.checks:
                log(f"check {n} {v!r} limit {lim!r} "
                    f"{'ok' if v <= lim else 'FAILED'}")
            sys.stderr.flush()
            print(json.dumps(line), flush=True)
            return True


def _num(v: float):
    """JSON has no NaN or infinity; those are reported as strings."""
    return v if math.isfinite(v) else str(v)


class Watchdog:
    """Ends a run that overstays its phase: prints the result line with
    ``correct`` false and exits with code 3, instead of hanging."""

    def __init__(self, result: Result):
        self.result = result
        self.deadline = math.inf
        self.phase = "start"
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._watch, daemon=True)
        self._thread.start()

    def arm(self, phase: str, seconds_from_now: float) -> None:
        self.phase = phase
        self.deadline = time.monotonic() + seconds_from_now

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=5)

    def _watch(self) -> None:
        while not self._stop.wait(0.5):
            if time.monotonic() > self.deadline:
                self.result.errors.append(
                    f"watchdog: phase {self.phase!r} overran its deadline")
                if self.result.emit():
                    os._exit(3)
                return


# -- what a cell driver is given ---------------------------------------------------


@dataclasses.dataclass
class Readings:
    """What the per-layer metric readers (``metrics/<name>.py``) read."""

    device_kind: str = ""
    setup_programs: int | None = None
    factor_stats: list = dataclasses.field(default_factory=list)
    factor_ranks: object = None         # host ranks of the traced factor
    factor_shape: dict = dataclasses.field(default_factory=dict)
    trace: object = None                # trace_reduce.TraceSummary


@dataclasses.dataclass
class Context:
    """One run of one cell: its files, arguments and shared state."""

    cell: str
    seed: int
    seconds: float
    trace: bool
    cfg: dict
    traffic: dict
    limits: dict
    result: Result
    counter: CompileCounter
    watchdog: Watchdog
    time_limit: float                   # seconds the whole run may take
    t_start: float                      # process clock at start
    readings: Readings = dataclasses.field(default_factory=Readings)
    e2e: dict = dataclasses.field(default_factory=dict)

    def end_setup(self) -> None:
        """Set-up is over: record ``setup_s`` and the programs it
        compiled or loaded, and bound the window."""
        self.e2e["setup_s"] = time.perf_counter() - self.t_start
        self.readings.setup_programs = self.counter.programs
        log(f"setup: {self.e2e['setup_s']:.3f} s, "
            f"{self.counter.programs} programs "
            f"({self.counter.hits} from the persistent cache, "
            f"{self.counter.seconds:.1f} s)")
        self._window_programs = (self.counter.programs, self.counter.hits)
        left = self.time_limit - (time.perf_counter() - self.t_start)
        self.watchdog.arm("window", left - CHECK_RESERVE_S)

    def end_window(self) -> None:
        """The window is over: report the programs compiled in it (there
        should be none), read the memory peak, and bound the check."""
        progs = self.counter.programs - self._window_programs[0]
        hits = self.counter.hits - self._window_programs[1]
        log(f"window: {progs - hits} programs compiled inside the window, "
            f"{hits} loaded from the persistent cache")
        self.result.device["memory_peak_bytes"] = memory_peak_bytes()
        left = self.time_limit - (time.perf_counter() - self.t_start)
        self.watchdog.arm("check", left)

    @contextlib.contextmanager
    def traced(self):
        """Profile the block on the device, with the program's spans on
        (they enter ``jax.profiler.TraceAnnotation``), and reduce the trace
        into ``readings.trace``. Without ``--trace 1`` it does nothing."""
        if not self.trace:
            yield
            return
        import jax
        from repro import obs

        import trace_reduce

        out = ROOT / ".bench_trace" / self.cell
        shutil.rmtree(out, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        if not obs.enabled():
            obs.enable()
        jax.profiler.start_trace(str(out), profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                yield
        finally:
            jax.profiler.stop_trace()
            obs.disable()
        t0 = time.perf_counter()
        summ = trace_reduce.reduce_trace(trace_reduce.find_xplane(str(out)))
        log(f"trace: window {summ.window_s:.3f} s, device busy "
            f"{summ.busy_s:.3f} s, {summ.gap_count} idle gaps, reduced in "
            f"{time.perf_counter() - t0:.1f} s")
        self.readings.trace = summ
        self.result.device["busy_s"] = summ.busy_s
        self.result.device["window_s"] = summ.window_s
        self.result.breakdown = summ.breakdown()
