"""Pallas TPU kernel: rank-masked uniform batched GEMM.

The TPU replacement for MAGMA's *non-uniform* batched GEMM: every operand is
padded to (b, r_max) and carries a per-item effective rank. Padding columns
are zero by construction of the TLR store, so the extra FLOPs are numerically
inert; the kernel additionally applies an explicit iota-mask on the
contraction dimension so it also works with *unpadded* (garbage-tailed)
inputs, matching the semantics of a true variable-rank batch.

    C[t] = A[t][:, :k_t] @ B[t][:k_t, :]      k_t = ranks[t]

Large (m, n) tiles are handled by gridding the output into (bm, bn) blocks
with the full contraction dimension resident in VMEM (r_max <= 1024 keeps
operand panels under ~1 MB at bf16 for bm = 256).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .lr_sample import HIGHEST


def _bgemm_kernel(rank_ref, a_ref, b_ref, c_ref):
    k = a_ref.shape[-1]
    rank = rank_ref[pl.program_id(0)]
    mask = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1) < rank
    a = jnp.where(mask, a_ref[0], jnp.zeros((), a_ref.dtype))
    acc_dtype = (
        jnp.float32 if a_ref.dtype in (jnp.bfloat16, jnp.float16)
        else a_ref.dtype
    )
    c_ref[0] = jnp.dot(a, b_ref[0], precision=HIGHEST,
                       preferred_element_type=acc_dtype).astype(c_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def batched_gemm_pallas(A, B, ranks, *, bm: int = 0, bn: int = 0,
                        interpret: bool = True):
    """C[t] = A[t] @ diag(mask(ranks[t])) @ B[t].

    A: (T, m, k), B: (T, k, n), ranks: (T,) int32 -> C: (T, m, n).
    ``ranks`` is a scalar-prefetch operand: it lives in SMEM for the whole
    grid and each step reads its tile's rank as a scalar.
    """
    T, m, k = A.shape
    n = B.shape[-1]
    if T == 0:  # an empty batch (e.g. the pair grid of a one-row bucket)
        return jnp.zeros((0, m, n), A.dtype)
    bm = bm or m
    bn = bn or n
    grid = (T, pl.cdiv(m, bm), pl.cdiv(n, bn))
    return pl.pallas_call(
        _bgemm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bm, k), lambda t, i, j, rk: (t, i, 0)),
                pl.BlockSpec((1, k, bn), lambda t, i, j, rk: (t, 0, j)),
            ],
            out_specs=pl.BlockSpec((1, bm, bn),
                                   lambda t, i, j, rk: (t, i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((T, m, n), A.dtype),
        interpret=interpret,
        name="batched_gemm_pallas",
    )(ranks.astype(jnp.int32), A, B)
