"""Percent of the traced factorization in which no operation ran on the
device: ``1 - busy / window`` from the profiler trace
(``trace_reduce``). Moves ``factor_s``."""

MOVES = "factor_s"


def read(r):
    if r.trace is None or not r.factor_stats or r.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.trace.busy_s / r.trace.window_s)
