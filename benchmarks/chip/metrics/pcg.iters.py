"""Iterations of the traced cycle's PCG solve: ``iterations`` of the
``PCGHistory`` it returned (``core/solve.py``), counted with telemetry
off too. Each iteration is one TLR matvec, one preconditioner solve (two
TRSM sweeps of ``nb`` column steps) and one host read. Moves
``factor_s``."""

MOVES = "factor_s"


def read(r):
    hist = getattr(r, "pcg_history", None)
    return getattr(hist, "iterations", None)
