"""ARA block iterations per column of the traced factorization, mean of
``stats["column_iters"]`` (``core/ara.py``, ``core/cholesky.py``); each
iteration is one jitted step and one host sync. Moves ``factor_s``."""

MOVES = "factor_s"


def read(r):
    if not r.factor_stats:
        return None
    iters = r.factor_stats[-1].get("column_iters") or []
    return sum(iters) / len(iters) if iters else None
