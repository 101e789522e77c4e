"""Pallas TPU kernels for the TLR hot spots (validated interpret=True on CPU).

Kernels (each: <name>.py kernel + ref.py oracle + ops.py dispatch):
  lr_sample    fused low-rank update-chain sampling (Eq. 2) -- the ARA
               sampling hot spot, ~the paper's 90% GEMM fraction
  batched_gemm rank-masked uniform batched GEMM (MAGMA non-uniform batch
               replacement)
  tlr_matvec   per-tile two-product chain of the TLR matvec (Alg. 7)
  batched_qr   MGS economy QR of stacked low-rank factors (the rounding
               pass of the tile algebra, core/algebra.py)
  small_svd    one-sided-Jacobi SVD of the r x r rounding cores

Each ``pallas_call`` carries ``name=`` equal to its jitted wrapper's name
(``lr_sample_pallas``, ...): the custom call, and so the op in a device
trace, keeps that name whatever function holds the kernel.
"""

from .ops import (  # noqa: F401
    batched_gemm, batched_qr, default_impl, lr_sample, small_svd, tile_chain,
)
from .lr_sample import lr_sample_pallas  # noqa: F401
from .batched_gemm import batched_gemm_pallas  # noqa: F401
from .batched_qr import batched_qr_pallas  # noqa: F401
from .small_svd import small_svd_pallas  # noqa: F401
from .tlr_matvec import tile_chain_pallas  # noqa: F401
from . import ref  # noqa: F401
