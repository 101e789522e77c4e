"""Device-to-host reads of the traced factorization per tile column:
``stats["syncs"]`` (every read the left driver makes, counted by its
one read helper) over the ``nb`` columns. Each read waits for the
device work it depends on. Moves ``factor_s``."""

MOVES = "factor_s"


def read(r):
    if not r.factor_stats:
        return None
    stats = r.factor_stats[-1]
    syncs = stats.get("syncs")
    if syncs is None:
        return None
    return syncs / (len(stats["column_iters"]) + 1)
