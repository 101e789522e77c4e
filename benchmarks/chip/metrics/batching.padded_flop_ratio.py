"""Dispatched over useful FLOPs of the batching plan the ``auto`` policy
chose for the traced factorization, ``stats["policy"]
["padded_flop_ratio"]`` (``core/batching.py``). Moves ``factor_s``."""

MOVES = "factor_s"


def read(r):
    if not r.factor_stats:
        return None
    return r.factor_stats[-1].get("policy", {}).get("padded_flop_ratio")
