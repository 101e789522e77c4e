"""Programs set-up compiled or loaded from the persistent cache: the
``backend_compile`` events of ``jax.monitoring`` (one per program, a
cache hit included) up to the window's start. Moves ``setup_s``."""

MOVES = "setup_s"


def read(r):
    return r.setup_programs
