"""Tile Low Rank (TLR) symmetric matrix representation.

The matrix is partitioned into an ``nb x nb`` grid of ``b x b`` tiles.
Diagonal tiles are stored dense; each strictly-lower off-diagonal tile
``A(i, j), i > j`` is stored as a low rank factorization ``U V^T`` padded to a
static maximum rank ``r_max`` (XLA requires static shapes; the CUDA original
reallocates per-tile storage instead). The upper triangle is implied by
symmetry: ``A(j, i) = V U^T``.

Packed lower-triangle indexing: tile ``(i, j)`` with ``i > j`` lives at flat
index ``i * (i - 1) // 2 + j``.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..precision import matmul


def tril_index(i: int, j: int) -> int:
    """Flat index of strictly-lower tile (i, j), i > j."""
    if i <= j:
        raise ValueError(f"tril_index requires i > j, got ({i}, {j})")
    return i * (i - 1) // 2 + j


def num_tiles(nb: int) -> int:
    return nb * (nb - 1) // 2


def tril_pairs(nb: int) -> np.ndarray:
    """(nt, 2) array of (i, j) pairs in packed order."""
    out = np.zeros((num_tiles(nb), 2), dtype=np.int64)
    for i in range(1, nb):
        for j in range(i):
            out[tril_index(i, j)] = (i, j)
    return out


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TLRMatrix:
    """Symmetric TLR matrix (pytree).

    Attributes:
      D:     (nb, b, b)      dense diagonal tiles.
      U:     (nt, b, r_max)  left low-rank factors, zero-padded past ``ranks``.
      V:     (nt, b, r_max)  right low-rank factors, zero-padded past ``ranks``.
      ranks: (nt,) int32     per-tile numerical rank (<= r_max).
    """

    D: jax.Array
    U: jax.Array
    V: jax.Array
    ranks: jax.Array

    @property
    def nb(self) -> int:
        return self.D.shape[0]

    @property
    def b(self) -> int:
        return self.D.shape[1]

    @property
    def n(self) -> int:
        return self.nb * self.b

    @property
    def r_max(self) -> int:
        return self.U.shape[2]

    @property
    def dtype(self):
        return self.D.dtype

    # -- conversion ---------------------------------------------------------

    def to_dense(self) -> jax.Array:
        return tlr_to_dense(self.D, self.U, self.V, self.nb, self.b)

    # -- accounting ---------------------------------------------------------

    def memory_stats(self) -> dict:
        """Logical (paper's Sum 2*b*k_ij) and padded byte counts.

        Byte counts follow the *stored* dtypes: diagonal tiles are always
        held in the compute dtype (``D.dtype``); the off-diagonal U/V
        factors may be stored lower-precision (``store_dtype`` under the
        section 7 mixed-precision proposal), and every low-rank byte count
        uses that stored itemsize consistently. ``full_dense_bytes`` /
        ``dense_equivalent_gb`` are what an uncompressed matrix would
        occupy at the compute dtype.
        """
        compute_itemsize = jnp.dtype(self.dtype).itemsize
        store_itemsize = jnp.dtype(self.U.dtype).itemsize  # mixed-prec storage
        ranks = np.asarray(self.ranks)
        dense_bytes = self.D.size * compute_itemsize
        logical_lr = int(2 * self.b * ranks.sum()) * store_itemsize
        padded_lr = (self.U.size + self.V.size) * store_itemsize
        full_dense = self.n * self.n * compute_itemsize
        return {
            "n": self.n,
            "tile_size": self.b,
            "compute_dtype": str(jnp.dtype(self.dtype)),
            "store_dtype": str(jnp.dtype(self.U.dtype)),
            "dense_diag_bytes": int(dense_bytes),
            "lowrank_bytes_logical": int(logical_lr),
            "lowrank_bytes_padded": int(padded_lr),
            "total_bytes_logical": int(dense_bytes + logical_lr),
            "total_bytes_padded": int(dense_bytes + padded_lr),
            "full_dense_bytes": int(full_dense),
            "dense_equivalent_gb": float(full_dense) / 2**30,
            "compression_ratio": float(full_dense)
            / float(dense_bytes + logical_lr),
            "avg_rank": float(ranks.mean()) if ranks.size else 0.0,
            "max_rank": int(ranks.max()) if ranks.size else 0,
        }


def _tile_of(A: jax.Array, i: int, j: int, b: int) -> jax.Array:
    return A[i * b : (i + 1) * b, j * b : (j + 1) * b]


@partial(jax.jit, static_argnums=(3, 4))
def tlr_to_dense(D, U, V, nb: int, b: int):
    n = nb * b
    out = jnp.zeros((n, n), D.dtype)
    for i in range(nb):
        out = out.at[i * b : (i + 1) * b, i * b : (i + 1) * b].set(D[i])
    for i in range(1, nb):
        for j in range(i):
            t = tril_index(i, j)
            block = matmul(U[t], V[t].T)
            out = out.at[i * b : (i + 1) * b, j * b : (j + 1) * b].set(block)
            out = out.at[j * b : (j + 1) * b, i * b : (i + 1) * b].set(block.T)
    return out


def from_dense(
    A: jax.Array | np.ndarray,
    b: int,
    r_max: int,
    eps: float,
    *,
    rel: bool = False,
    store_dtype=None,
) -> TLRMatrix:
    """Deprecated shim: use ``TLROperator.compress`` / ``.from_dense``.

    Same truncation semantics (keep singular values > eps absolute, or
    > eps * s_max with ``rel``; ``store_dtype`` for mixed-precision U/V
    storage), but construction now routes through the batched compression
    path -- one batched SVD over all nt tiles instead of the per-tile host
    SVD loop this function used to run. Returns the bare ``TLRMatrix``.
    """
    from .operator import TLROperator
    from .solve import _deprecated

    _deprecated("from_dense", "TLROperator.compress / TLROperator.from_dense")

    return TLROperator.compress(
        A, b, r_max, eps, rel=rel, store_dtype=store_dtype).A


def zeros_like_structure(nb: int, b: int, r_max: int, dtype) -> TLRMatrix:
    nt = num_tiles(nb)
    return TLRMatrix(
        D=jnp.zeros((nb, b, b), dtype),
        U=jnp.zeros((nt, b, r_max), dtype),
        V=jnp.zeros((nt, b, r_max), dtype),
        ranks=jnp.zeros((nt,), jnp.int32),
    )


def rank_heatmap(A: TLRMatrix) -> np.ndarray:
    """(nb, nb) array of tile ranks (diag = b, upper mirrored) for plots."""
    nb, b = A.nb, A.b
    H = np.zeros((nb, nb), np.int32)
    ranks = np.asarray(A.ranks)
    for i in range(nb):
        H[i, i] = b
    for i in range(1, nb):
        for j in range(i):
            H[i, j] = H[j, i] = ranks[tril_index(i, j)]
    return H
