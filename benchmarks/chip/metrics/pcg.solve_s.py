"""Host seconds of the traced cycle's PCG solve: its ``algebra.pcg``
span, from the span's metrics snapshot on the returned ``PCGHistory``
(``telemetry["phases"]["algebra.pcg"]``, ``core/solve.py``). The span
ends at the solve's last host read. Moves ``factor_s``."""

MOVES = "factor_s"


def read(r):
    snap = getattr(getattr(r, "pcg_history", None), "telemetry", None)
    row = (snap or {}).get("phases", {}).get("algebra.pcg")
    return None if row is None else row["seconds"]
