"""Pallas TPU kernel: batched TLR tile matvec chain  p[t] = U_t (V_t^T x_t).

The per-tile two-product chain of the TLR matrix-vector product (Algorithm 7
and section 4.4). The (r,) intermediate never leaves VMEM. The segment
reduction scattering tile products into block rows stays outside the kernel
(XLA segment-sum handles it well); the kernel removes the HBM round trip of
the intermediate, which is what limits the GPU version.

``x`` blocks arrive pre-gathered per tile, (T, b, nrhs); nrhs >= 1 unifies
the vector and multi-vector cases (the lane dimension wants >= 128 on real
TPUs; nrhs pads up for the dry-run configuration).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .lr_sample import HIGHEST


def _tile_chain_kernel(u_ref, v_ref, x_ref, out_ref):
    acc_dtype = (
        jnp.float32 if u_ref.dtype in (jnp.bfloat16, jnp.float16)
        else u_ref.dtype
    )
    t1 = jax.lax.dot_general(v_ref[0], x_ref[0], (((0,), (0,)), ((), ())),
                             precision=HIGHEST,
                             preferred_element_type=acc_dtype)
    out_ref[0] = jnp.dot(u_ref[0], t1, precision=HIGHEST,
                         preferred_element_type=acc_dtype).astype(
        out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "width"))
def tile_chain_pallas(U, V, X, *, interpret: bool = True,
                      width: int | None = None):
    """out[t] = U[t] @ (V[t]^T @ X[t]);  U,V: (T,b,r), X: (T,b,s).

    ``width``: optional TilePlan bucket width (DESIGN.md section 9). The
    factor operands are sliced to it *before* the ``pallas_call``, so the
    BlockSpecs -- and with them each grid cell's VMEM footprint and MXU
    work -- shrink to the bucket's ladder width instead of r_max. Exact,
    because factor columns past each tile's rank are zero.
    """
    if width is not None and width < U.shape[-1]:
        U = U[:, :, :width]
        V = V[:, :, :width]
    T, b, r = U.shape
    s = X.shape[-1]
    return pl.pallas_call(
        _tile_chain_kernel,
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, b, r), lambda t: (t, 0, 0)),
            pl.BlockSpec((1, b, r), lambda t: (t, 0, 0)),
            pl.BlockSpec((1, b, s), lambda t: (t, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, b, s), lambda t: (t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((T, b, s), U.dtype),
        interpret=interpret,
        name="tile_chain_pallas",
    )(U, V, X)
