"""Share of the dynamic ARA loop's dispatched slots that held a live
tile, in percent, over the traced factorization: the iterations each
row tile entered unconverged (``column_events[k]["tile_iters"]``) over
the slot width of every step (``column_events[k]["slots"]``). Finished
tiles are masked but still computed until the last tile of a batch
converges; this counts them. The fused mode records neither. Moves
``factor_s``."""

MOVES = "factor_s"


def read(r):
    if not r.factor_stats:
        return None
    events = [e for e in r.factor_stats[-1].get("column_events", [])
              if e.get("tile_iters") is not None]
    slots = sum(e["slots"] for e in events)
    if slots <= 0:
        return None
    return 100.0 * sum(int(sum(e["tile_iters"])) for e in events) / slots
