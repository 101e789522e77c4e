"""Pallas TPU kernel: fused low-rank update-chain sampling (Eq. 2 hot spot).

Computes, for every row tile ``t`` in a block column,

    Y[t] = sum_j  U[t, j] @ (V[t, j]^T @ W2[j])

where ``W2[j] = V(k,j) (U(k,j)^T Omega)`` is the shared per-column
intermediate (hoisted out of the row loop when Omega is shared -- the
beyond-paper optimization of DESIGN.md section 2).

On the GPU the paper launches this as two marshaled MAGMA batched GEMMs with
an HBM round trip for the (r x s) intermediate. The TPU-native version fuses
the two products per (t, j) grid cell: ``V^T W2`` stays in VMEM and feeds the
MXU immediately, and the j-axis reduction accumulates into a VMEM scratch
across sequential grid steps (a revisiting grid -- the Pallas analogue of the
paper's parallel-buffer row reduction, without materializing the buffers in
HBM).

Block shapes: the natural operands (b x r), (b x s) already fit VMEM for the
paper's tile sizes (b <= 1024, r <= 128: 1 MB at f32), so BlockSpecs map one
tile per grid cell and tile the *batch* dimensions; b and r are padded to
MXU-friendly multiples of 128 by construction of the TLR store. Accumulation
is f32 when inputs are bf16 (MXU-native mixed precision).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# f32 operands get f32-accurate MXU products (bf16 operands are unaffected)
HIGHEST = jax.lax.Precision.HIGHEST


def _lr_sample_kernel(ui_ref, vi_ref, w2_ref, y_ref, acc_ref):
    """Grid cell (t, j): acc += U[t,j] @ (V[t,j]^T @ W2[j])."""
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # (r, s) intermediate never leaves VMEM; both matmuls hit the MXU.
    t3 = jax.lax.dot_general(vi_ref[0, 0], w2_ref[0],
                             (((0,), (0,)), ((), ())), precision=HIGHEST,
                             preferred_element_type=acc_ref.dtype)
    acc_ref[...] += jnp.dot(ui_ref[0, 0], t3, precision=HIGHEST,
                            preferred_element_type=acc_ref.dtype)

    @pl.when(j == nj - 1)
    def _flush():
        y_ref[0] = acc_ref[...].astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "width"))
def lr_sample_pallas(Ui, Vi, W2, *, interpret: bool = True,
                     width: int | None = None):
    """Y[t] = sum_j U[t,j] @ (V[t,j]^T @ W2[j]).

    Args:
      Ui, Vi: (T, k, b, r)  row tiles of L for the column being sampled.
      W2:     (k, b, s)     shared per-j intermediate.
      width:  optional TilePlan bucket width; the factor operands slice to
              it before the ``pallas_call`` so the BlockSpecs (VMEM blocks,
              MXU work per grid cell) shrink to the bucket's ladder width
              (exact: factor columns past each tile's rank are zero).
    Returns:
      Y: (T, b, s)
    """
    if width is not None and width < Ui.shape[-1]:
        Ui = Ui[:, :, :, :width]
        Vi = Vi[:, :, :, :width]
    T, k, b, r = Ui.shape
    s = W2.shape[-1]
    if k == 0:
        return jnp.zeros((T, b, s), Ui.dtype)
    acc_dtype = (
        jnp.float32 if Ui.dtype in (jnp.bfloat16, jnp.float16) else Ui.dtype
    )
    return pl.pallas_call(
        _lr_sample_kernel,
        grid=(T, k),
        in_specs=[
            pl.BlockSpec((1, 1, b, r), lambda t, j: (t, j, 0, 0)),
            pl.BlockSpec((1, 1, b, r), lambda t, j: (t, j, 0, 0)),
            pl.BlockSpec((1, b, s), lambda t, j: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, b, s), lambda t, j: (t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((T, b, s), Ui.dtype),
        scratch_shapes=[pltpu.VMEM((b, s), acc_dtype)],
        interpret=interpret,
        name="lr_sample_pallas",
    )(Ui, Vi, W2)
