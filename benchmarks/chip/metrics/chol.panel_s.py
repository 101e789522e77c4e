"""Host seconds of the Cholesky driver's panel stages (ARA sampling,
projection, TRSM of each column) in the traced factorization:
``stats["schedule"]["kind_seconds"]["panel"]`` (``core/stages.py``).
The left driver pulls ranks to the host inside every column, so each
stage ends on a device sync. Moves ``factor_s``."""

MOVES = "factor_s"


def read(r):
    if not r.factor_stats:
        return None
    return r.factor_stats[-1]["schedule"]["kind_seconds"].get("panel")
