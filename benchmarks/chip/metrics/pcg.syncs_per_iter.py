"""Device-to-host reads of the traced cycle's PCG solve per iteration:
``host_reads`` over ``iterations`` of the returned ``PCGHistory``
(``core/solve.py``), counted with telemetry off too. Each read waits for
the device work before it. Moves ``factor_s``."""

MOVES = "factor_s"


def read(r):
    hist = getattr(r, "pcg_history", None)
    reads = getattr(hist, "host_reads", None)
    iters = getattr(hist, "iterations", None)
    if reads is None or not iters:
        return None
    return reads / iters
