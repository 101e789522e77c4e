"""Seconds of JIT work inside the traced factorization: jaxpr tracing,
lowering to MLIR and backend compiles or persistent-cache loads, summed
over the factorization's spans (``stats["telemetry"]["jit"]``, from the
``jax.monitoring`` listener of ``repro.obs``). The left driver builds
new jitted column steps per factorization, so this is the lowering the
device sits out in every factorization. Moves ``factor_s``."""

MOVES = "factor_s"


def read(r):
    if not r.factor_stats:
        return None
    jit = r.factor_stats[-1].get("telemetry", {}).get("jit")
    if not jit:
        return None
    return jit["trace_s"] + jit["lower_s"] + jit["compile_s"]
