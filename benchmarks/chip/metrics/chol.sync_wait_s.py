"""Host seconds the traced factorization spent in its device-to-host
reads: the ``chol.pull`` spans the left driver opens around each read
under telemetry (``stats["telemetry"]["phases"]["chol.pull"]``). Moves
``factor_s``."""

MOVES = "factor_s"


def read(r):
    if not r.factor_stats:
        return None
    phases = r.factor_stats[-1].get("telemetry", {}).get("phases", {})
    row = phases.get("chol.pull")
    return None if row is None else row["seconds"]
