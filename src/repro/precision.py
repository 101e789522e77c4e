"""Matmul precision of the library's XLA contractions.

On a TPU, XLA runs an f32 contraction that names no precision as a single
bf16 pass, about three significant digits. The TLR algorithms need f32
accuracy there: the adaptive randomized approximation stops on error
estimates at the tile tolerance, and at one bf16 pass those estimates sit
on the bf16 noise floor, so compression runs every tile up to its rank cap.
Every einsum, matmul and dot of the library's XLA paths goes through the
helpers below, which name ``MATMUL_PRECISION`` explicitly, as the Pallas
kernels do; the result then does not depend on the process-wide
``jax_default_matmul_precision``. On a CPU, and in f64, the setting
changes nothing.

The helpers read ``MATMUL_PRECISION`` when a function is traced, so setting
it to ``jax.lax.Precision.DEFAULT`` before the first call measures what the
one-pass default would do.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

MATMUL_PRECISION = lax.Precision.HIGHEST


def einsum(subscripts, *operands, **kw):
    return jnp.einsum(subscripts, *operands, precision=MATMUL_PRECISION,
                      **kw)


def matmul(a, b):
    return jnp.matmul(a, b, precision=MATMUL_PRECISION)


def vdot(a, b):
    return jnp.vdot(a, b, precision=MATMUL_PRECISION)
