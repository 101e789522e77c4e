"""Batched TLR tile algebra: rounding, structured ops, GEMM / SYRK.

The factorizations of PR 1-2 consume a TLR matrix; this module lets the
repo *compute with* TLR matrices -- the GEMM-centric operation set the
paper's performance story is built on (and what Boukaram et al.,
arXiv:1902.01829, implement as batched QR/SVD compression on GPUs):

* ``tlr_round``      -- recompress every off-diagonal tile's accumulated
  low-rank sum ``[U1|U2][V1|V2]^T`` in one batched rank-masked QR +
  small-SVD pass (``kernels/batched_qr.py`` + ``kernels/small_svd.py``,
  dispatched through ``kernels.ops`` so the ``ref/interpret/pallas``
  ladder applies).
* ``tlr_axpy`` / ``tlr_scale`` / ``tlr_transpose`` / ``tlr_add_diag`` --
  structured ops; addition is an exact low-rank concatenation (ranks add)
  with optional rounding.
* ``tlr_gemm``       -- TLR x TLR product on the general (nonsymmetric)
  tile grid ``TLRTiles``: the ``nb`` inner products per output tile are
  accumulated as batched ``(b, r) @ (r, b)`` chains concatenated into a
  single wide batched GEMM, then one rounding pass compresses all output
  tiles at once.
* ``tlr_syrk``       -- symmetric Schur update ``A - L L^T`` for
  lower-triangular TLR ``L``; the per-tile inner-product count ``j`` is
  padded up the power-of-two bucket ladder of ``core/buckets.py``, so
  ~log2(nb) compiled accumulation variants serve all nt output tiles --
  the update kernel a right-looking factorization needs.
* ``tlr_syrk_column`` / ``tlr_round_tiles`` -- the column-scoped SYRK
  and accumulated-tile rounding pass driving the right-looking
  factorization (``core/cholesky.py``, ``algo="right"``): per factored
  column, every trailing tile eagerly receives that column's single
  rank-r outer product as a concatenated factor-pair append, bucket-
  laddered over the trailing rows (DESIGN.md section 7).

No function here loops over tiles on the host in the hot path: all tile
math happens in jitted batched cores whose compile count is exposed via
``algebra_trace_count()`` (the contract ``tests/test_algebra.py`` pins,
mirroring ``trsm_trace_count``). Error model: a rounding pass at absolute
threshold ``eps`` perturbs each tile by at most ``sqrt(r) * eps`` in
Frobenius norm, so the whole matrix moves by <= ``sqrt(nt * r) * eps``
(DESIGN.md section 6).
"""

from __future__ import annotations

import dataclasses
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from .batching import (bucket_width, bucketed_round_tiles, resolve_batching,
                       shard_tile_batch)
from .buckets import (_bucket_ladder, _bucket_up, _pad_axis, trace_count,
                      trace_event)
from .tlr import TLRMatrix, tril_index, tril_pairs
from ..kernels import ops
from .. import obs
from ..precision import einsum, matmul


# -- general (nonsymmetric) tile grid -----------------------------------------


def offd_index(i: int, j: int, nb: int) -> int:
    """Flat index of off-diagonal tile (i, j), i != j, row-major skipping
    the diagonal: tile (i, j) lives at ``i*(nb-1) + (j - (j > i))``."""
    if i == j:
        raise ValueError(f"offd_index requires i != j, got ({i}, {j})")
    return i * (nb - 1) + (j if j < i else j - 1)


@lru_cache(maxsize=None)
def offd_pairs(nb: int) -> np.ndarray:
    """(no, 2) array of all off-diagonal (i, j) pairs in packed order."""
    out = np.zeros((nb * (nb - 1), 2), dtype=np.int64)
    for i in range(nb):
        for j in range(nb):
            if i != j:
                out[offd_index(i, j, nb)] = (i, j)
    return out


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TLRTiles:
    """General (nonsymmetric) TLR matrix (pytree): the result type of
    ``tlr_gemm`` and operand type of the operator arithmetic.

    Same storage discipline as ``TLRMatrix`` but with *all* ``nb*(nb-1)``
    off-diagonal tiles stored explicitly (packed per ``offd_index``):

      D:     (nb, b, b)      dense diagonal tiles.
      U, V:  (no, b, r_max)  low-rank factors, zero-padded past ``ranks``.
      ranks: (no,) int32     leading meaningful columns per tile.
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
    def shape(self) -> tuple[int, int]:
        return (self.n, self.n)

    @property
    def dtype(self):
        return self.D.dtype

    def to_dense(self) -> jax.Array:
        return _tiles_to_dense(self.D, self.U, self.V, self.nb, self.b)

    def matvec(self, x: jax.Array) -> jax.Array:
        """y = A @ x; x is (n,) or batched (n, m)."""
        xb = x.reshape(self.nb, self.b, *x.shape[1:])
        yb = _gen_matvec(self.D, self.U, self.V, xb, self.nb)
        return yb.reshape(x.shape)

    def __matmul__(self, x):
        if isinstance(x, (jax.Array, np.ndarray)):
            return self.matvec(jnp.asarray(x))
        return NotImplemented

    def transpose(self) -> "TLRTiles":
        return tlr_transpose(self)

    def symmetrize(self, eps=None, r_max_out=None, *, impl=None) -> TLRMatrix:
        return symmetrize(self, eps, r_max_out, impl=impl)

    def round(self, eps, r_max_out=None, *, impl=None) -> "TLRTiles":
        return tlr_round(self, eps, r_max_out, impl=impl)


@partial(jax.jit, static_argnums=(3, 4))
def _tiles_to_dense(D, U, V, nb: int, b: int):
    out = jnp.zeros((nb * b, nb * b), D.dtype)
    for i in range(nb):
        out = out.at[i * b:(i + 1) * b, i * b:(i + 1) * b].set(D[i])
    for t, (i, j) in enumerate(offd_pairs(nb)):
        out = out.at[i * b:(i + 1) * b, j * b:(j + 1) * b].set(
            matmul(U[t], V[t].T))
    return out


@partial(jax.jit, static_argnums=(4,))
def _gen_matvec(D, U, V, xb, nb: int):
    pairs = offd_pairs(nb)
    rows = jnp.asarray(pairs[:, 0], jnp.int32)
    cols = jnp.asarray(pairs[:, 1], jnp.int32)
    yb = einsum("kbc,kc...->kb...", D, xb)
    xj = jnp.take(xb, cols, axis=0)
    y = einsum("tbr,tr...->tb...", U,
                   einsum("tbr,tb...->tr...", V, xj))
    return yb.at[rows].add(y)


# -- symmetric <-> general conversion -----------------------------------------


@lru_cache(maxsize=None)
def _generalize_indices(nb: int):
    """For each general pair (i, j): its packed-lower index and whether the
    stored tile is the transpose (i < j, so the U/V roles swap)."""
    pairs = offd_pairs(nb)
    idx = np.empty(len(pairs), np.int32)
    flip = np.empty(len(pairs), bool)
    for t, (i, j) in enumerate(pairs):
        if i > j:
            idx[t], flip[t] = tril_index(i, j), False
        else:
            idx[t], flip[t] = tril_index(j, i), True
    return idx, flip


def generalize(A: TLRMatrix) -> TLRTiles:
    """Mirror a symmetric TLR matrix onto the full general tile grid."""
    idx, flip = _generalize_indices(A.nb)
    U0 = jnp.take(A.U, jnp.asarray(idx), axis=0)
    V0 = jnp.take(A.V, jnp.asarray(idx), axis=0)
    f = jnp.asarray(flip)[:, None, None]
    return TLRTiles(
        D=A.D,
        U=jnp.where(f, V0, U0),
        V=jnp.where(f, U0, V0),
        ranks=jnp.take(A.ranks, jnp.asarray(idx)),
    )


@lru_cache(maxsize=None)
def _symmetrize_indices(nb: int):
    """(low, up) general-grid slots of each packed-lower pair (i, j)."""
    pairs = tril_pairs(nb)
    low = np.asarray([offd_index(int(i), int(j), nb) for i, j in pairs],
                     np.int32)
    up = np.asarray([offd_index(int(j), int(i), nb) for i, j in pairs],
                    np.int32)
    return low, up


def symmetrize(G: TLRTiles, eps=None, r_max_out=None, *,
               impl=None, batching: str = "flat") -> TLRMatrix:
    """Project onto the symmetric part, 0.5 (G + G^T), as a ``TLRMatrix``.

    Each lower tile is the exact rank-2r concatenation
    ``[G(i,j)/2 | G(j,i)^T/2]``; pass ``eps`` to recompress. The ``ranks``
    of the unrounded concat follow the axpy convention (see ``tlr_axpy``).
    """
    low_np, up_np = _symmetrize_indices(G.nb)
    low, up = jnp.asarray(low_np), jnp.asarray(up_np)
    Ul, Vl = jnp.take(G.U, low, axis=0), jnp.take(G.V, low, axis=0)
    Uu, Vu = jnp.take(G.U, up, axis=0), jnp.take(G.V, up, axis=0)
    half = jnp.asarray(0.5, G.dtype)
    out = TLRMatrix(
        D=half * (G.D + jnp.swapaxes(G.D, 1, 2)),
        U=jnp.concatenate([half * Ul, half * Vu], axis=-1),
        V=jnp.concatenate([Vl, Uu], axis=-1),
        ranks=(G.r_max + jnp.take(G.ranks, up)).astype(jnp.int32),
    )
    if eps is not None:
        out = tlr_round(out, eps, r_max_out, impl=impl, batching=batching)
    return out


# -- the batched rounding pass ------------------------------------------------

# One entry per freshly compiled algebra-core variant (rounding pass, GEMM
# assembly, SYRK bucket step), recorded under the "algebra" key of the
# unified registry in ``core/buckets.py``. The python body of a jitted core
# runs exactly once per compile, so this is a real compile count: it must
# stay O(log nb) per shape family and *never* scale with nt
# (tests/test_algebra.py pins it).


def algebra_trace_count() -> int:
    """Compiled algebra-core variants so far (process-wide); a view of
    ``trace_count("algebra")`` in the unified registry."""
    return trace_count("algebra")


def _truncate_svd(W, s, Z, Q_left, Q_right, eps, r_out: int, rel: bool,
                  impl: str):
    """Shared truncation tail: given core SVD ``W s Z^T`` and the two
    orthonormal bases it lives in, build zero-padded (U, V, ranks, err).
    ``err`` is the per-tile Frobenius norm of the discarded part -- the
    bases are orthonormal, so it is exactly the 2-norm of the dropped
    singular values (no reconstruction needed)."""
    N, _, kin = W.shape
    b = Q_left.shape[1]
    cut = eps * (s[:, :1] if rel else jnp.ones_like(s[:, :1]))
    ranks = jnp.clip(jnp.sum(s > cut, axis=1), 0, r_out).astype(jnp.int32)
    k = min(r_out, kin)
    mask = (jnp.arange(k)[None, :] < ranks[:, None]).astype(W.dtype)
    full = jnp.full((N,), Q_left.shape[2], jnp.int32)
    dropped = jnp.where(jnp.arange(kin)[None, :] < ranks[:, None],
                        jnp.zeros_like(s), s)
    err = jnp.sqrt(jnp.sum(dropped * dropped, axis=1))
    U = ops.batched_gemm(
        Q_left, W[:, :, :k] * (s[:, None, :k] * mask[:, None, :]), full,
        impl=impl)
    if Q_right is None:
        V = Z[:, :, :k] * mask[:, None, :]
    else:
        V = ops.batched_gemm(Q_right, Z[:, :, :k] * mask[:, None, :], full,
                             impl=impl)
    if r_out > k:
        pad = ((0, 0), (0, 0), (0, r_out - k))
        U, V = jnp.pad(U, pad), jnp.pad(V, pad)
    return U, V, ranks, err


def _round_factors_impl(U, V, eps, *, r_out: int, rel: bool, impl: str):
    """Recompress (U, V) factor stacks, r_in <= b: batched QR of both
    sides, SVD of the r_in x r_in core R_u R_v^T, truncate at eps. The
    unjitted body, shared with the rank-bucketed cores in
    ``core/batching.py`` (which jit it per bucket width)."""
    N, b, r_in = U.shape
    Qu, Ru = ops.batched_qr(U, impl=impl)
    Qv, Rv = ops.batched_qr(V, impl=impl)
    full = jnp.full((N,), r_in, jnp.int32)
    core = ops.batched_gemm(Ru, jnp.swapaxes(Rv, 1, 2), full, impl=impl)
    W, s, Z = ops.small_svd(core, impl=impl)
    return _truncate_svd(W, s, Z, Qu, Qv, eps, r_out, rel, impl)


def _compress_dense_impl(T, eps, *, r_out: int, rel: bool, impl: str):
    """Compress dense (N, b, b) tiles: QR then SVD of the b x b R factor
    (unjitted body, shared with ``core/batching.py``)."""
    Q, R = ops.batched_qr(T, impl=impl)
    W, s, Z = ops.small_svd(R, impl=impl)
    return _truncate_svd(W, s, Z, Q, None, eps, r_out, rel, impl)


@partial(jax.jit, static_argnames=("r_out", "rel", "impl"))
def _round_factors(U, V, eps, *, r_out: int, rel: bool, impl: str):
    trace_event("algebra")
    return _round_factors_impl(U, V, eps, r_out=r_out, rel=rel, impl=impl)


@partial(jax.jit, static_argnames=("r_out", "rel", "impl"))
def _compress_dense_tiles(T, eps, *, r_out: int, rel: bool, impl: str):
    trace_event("algebra")
    return _compress_dense_impl(T, eps, r_out=r_out, rel=rel, impl=impl)


@obs.traced("algebra.round", cat="algebra")
def tlr_round(A, eps, r_max_out=None, *, rel: bool = False, impl=None,
              batching: str = "flat"):
    """Recompress every off-diagonal tile of ``A`` at threshold ``eps``.

    ``A`` is a ``TLRMatrix`` or ``TLRTiles`` whose tiles may hold
    accumulated sums ``[U1|U2][V1|V2]^T`` (ranks up to ``A.r_max``, which
    may exceed ``b`` after repeated concatenation). One batched pass over
    all tiles -- no host loop: factored QR + core SVD when ``r_max <= b``,
    densify-then-compress when the accumulated width exceeds the tile size
    (cheaper *and* exact there, since the tile is only b x b). Truncation
    keeps singular values ``> eps`` (absolute; ``rel`` cuts against each
    tile's s_max), so ranks are monotone non-increasing in ``eps``.

    ``batching="ranked"`` dispatches through the rank-bucketed layer
    (``core/batching.py``, DESIGN.md section 8): tiles are marshaled into
    rank-homogeneous batches and each bucket recompresses at its own ladder
    width instead of ``r_max`` (rank-0 tiles skip the kernels entirely).
    Same truncation semantics; ``"flat"`` is the compatibility path.
    """
    ops.resolve_impl(impl)  # validate; each op resolves its own default
    batching = resolve_batching(batching, A.ranks, A.r_max)
    b, r_in = A.b, A.r_max
    r_out = r_max_out or min(r_in, b)
    N = A.U.shape[0]
    if N == 0:
        z = jnp.zeros((0, b, r_out), A.dtype)
        return dataclasses.replace(A, U=z, V=z,
                                   ranks=jnp.zeros((0,), jnp.int32))
    if batching == "ranked":
        U, V, ranks, _ = bucketed_round_tiles(A.U, A.V, A.ranks, eps,
                                              r_out=r_out, rel=rel, impl=impl)
        return dataclasses.replace(A, U=U, V=V, ranks=ranks)
    eps = jnp.asarray(eps, A.dtype)
    if r_in <= b:
        U, V, ranks, _ = _round_factors(A.U, A.V, eps, r_out=r_out, rel=rel,
                                        impl=impl)
    else:
        dense = ops.batched_gemm(A.U, jnp.swapaxes(A.V, 1, 2), A.ranks,
                                 impl=impl)
        U, V, ranks, _ = _compress_dense_tiles(dense, eps, r_out=r_out,
                                               rel=rel, impl=impl)
    return dataclasses.replace(A, U=U, V=V, ranks=ranks)


@obs.traced("algebra.round_tiles", cat="algebra")
def tlr_round_tiles(U, V, eps, r_out=None, *, rel: bool = False, impl=None,
                    ranks=None, batching: str = "flat"):
    """Round a raw stack of accumulated tile factors ``U V^T``.

    The batched core of :func:`tlr_round`, exposed for callers that manage
    their own tile subsets instead of a whole ``TLRMatrix`` grid -- the
    right-looking factorization's panel and flush rounding passes
    (``core/cholesky.py``). ``U`` / ``V`` are ``(N, b, W)`` concatenated
    factor stacks (zero columns are inert, so callers need not track a
    per-tile used-width); returns ``(U, V, ranks, err)`` at width ``r_out``
    with ranks allowed to truncate to 0 and ``err`` the per-tile Frobenius
    norm of the discarded singular values. Width ``W > b`` takes the
    densify-then-compress path (exact for b x b tiles), ``W <= b`` the
    factored QR + core-SVD path.

    With ``batching="ranked"`` and a per-tile ``ranks`` (content-width)
    bound, the pass runs through the rank buckets of ``core/batching.py``
    instead of one W-wide batch (``ranks[t]`` must upper-bound tile ``t``'s
    nonzero columns -- the storage invariant / axpy width convention).
    """
    ops.resolve_impl(impl)  # validate; each op resolves its own default
    batching = resolve_batching(batching, ranks, U.shape[2])
    N, b, w_in = U.shape
    r_out = r_out or min(w_in, b)
    if batching == "ranked":
        if ranks is None:
            raise ValueError(
                "tlr_round_tiles(batching='ranked') needs the per-tile "
                "``ranks`` content-width bounds to build the buckets")
        return bucketed_round_tiles(U, V, ranks, eps, r_out=r_out, rel=rel,
                                    impl=impl)
    eps = jnp.asarray(eps, U.dtype)
    if w_in <= b:
        return _round_factors(U, V, eps, r_out=r_out, rel=rel, impl=impl)
    dense = ops.batched_gemm(U, jnp.swapaxes(V, 1, 2),
                             jnp.full((N,), w_in, jnp.int32), impl=impl)
    return _compress_dense_tiles(dense, eps, r_out=r_out, rel=rel, impl=impl)


# -- structured ops -----------------------------------------------------------


def tlr_scale(alpha, A):
    """alpha * A (exact; scales diagonal tiles and left factors)."""
    alpha = jnp.asarray(alpha, A.dtype)
    return dataclasses.replace(A, D=alpha * A.D, U=alpha * A.U)


def tlr_axpy(alpha, A, B, eps=None, r_max_out=None, *, impl=None,
             batching: str = "flat"):
    """alpha * A + B by low-rank concatenation, optionally rounded.

    Exact when ``eps`` is None: each tile becomes ``[alpha*U_A | U_B]
    [V_A | V_B]^T`` (r_max adds). The combined ``ranks`` are
    ``A.r_max + B.ranks``: the A-part's zero tail between ``rank_A`` and
    ``A.r_max`` sits *inside* the counted prefix, which is sound (zero
    columns are inert in every product) and keeps the "columns past ranks
    are zero" layout invariant; the next rounding pass compacts it away.
    ``A`` and ``B`` must share structure type, nb, and b.
    """
    if type(A) is not type(B) or A.nb != B.nb or A.b != B.b:
        raise ValueError(
            f"tlr_axpy needs matching structures, got {type(A).__name__}"
            f"(nb={A.nb}, b={A.b}) and {type(B).__name__}"
            f"(nb={B.nb}, b={B.b})")
    alpha = jnp.asarray(alpha, A.dtype)
    out = dataclasses.replace(
        A,
        D=alpha * A.D + B.D,
        U=jnp.concatenate([alpha * A.U, B.U], axis=-1),
        V=jnp.concatenate([A.V, B.V], axis=-1),
        ranks=(A.r_max + B.ranks).astype(jnp.int32),
    )
    if eps is not None:
        out = tlr_round(out, eps, r_max_out, impl=impl, batching=batching)
    return out


@lru_cache(maxsize=None)
def _transpose_perm(nb: int) -> np.ndarray:
    pairs = offd_pairs(nb)
    return np.asarray([offd_index(int(j), int(i), nb) for i, j in pairs],
                      np.int32)


def tlr_transpose(A):
    """A^T (exact). Identity for the symmetric ``TLRMatrix``; for
    ``TLRTiles`` the U/V roles swap and tiles move to mirrored slots."""
    if isinstance(A, TLRMatrix):
        return A
    perm = jnp.asarray(_transpose_perm(A.nb))
    return TLRTiles(
        D=jnp.swapaxes(A.D, 1, 2),
        U=jnp.take(A.V, perm, axis=0),
        V=jnp.take(A.U, perm, axis=0),
        ranks=jnp.take(A.ranks, perm),
    )


def tlr_add_diag(A, diag):
    """Dense add onto the diagonal tiles: ``diag`` is a scalar (alpha * I)
    or a (nb, b, b) stack of dense tiles."""
    diag = jnp.asarray(diag, A.dtype)
    if diag.ndim == 0:
        add = diag * jnp.eye(A.b, dtype=A.dtype)[None]
    elif diag.shape == A.D.shape:
        add = diag
    else:
        raise ValueError(
            f"diag must be scalar or shape {A.D.shape}, got {diag.shape}")
    return dataclasses.replace(A, D=A.D + add)


# -- TLR x TLR GEMM -----------------------------------------------------------


@lru_cache(maxsize=None)
def _gemm_indices(nb: int):
    """Host-built gather grids for the GEMM accumulation (setup only --
    the hot path consumes them as device constants).

    For off-diagonal output (i, j): its own slot in A and B, plus the
    ``nb - 2`` middle slots ``A(i, m), B(m, j)`` for m not in {i, j}. For
    diagonal output i: the ``nb - 1`` middle slots ``A(i, m), B(m, i)``.
    """
    pairs = offd_pairs(nb)
    no, K = len(pairs), max(nb - 2, 0)
    oi = pairs[:, 0].astype(np.int32)
    oj = pairs[:, 1].astype(np.int32)
    own = np.asarray([offd_index(int(i), int(j), nb) for i, j in pairs],
                     np.int32)
    mid_a = np.zeros((no, K), np.int32)
    mid_b = np.zeros((no, K), np.int32)
    for t, (i, j) in enumerate(pairs):
        mids = [m for m in range(nb) if m != i and m != j]
        mid_a[t] = [offd_index(int(i), m, nb) for m in mids]
        mid_b[t] = [offd_index(m, int(j), nb) for m in mids]
    dmid_a = np.zeros((nb, nb - 1), np.int32)
    dmid_b = np.zeros((nb, nb - 1), np.int32)
    for i in range(nb):
        mids = [m for m in range(nb) if m != i]
        dmid_a[i] = [offd_index(i, m, nb) for m in mids]
        dmid_b[i] = [offd_index(m, i, nb) for m in mids]
    return oi, oj, own, mid_a, mid_b, dmid_a, dmid_b


def _lrlr_dense_sum(Ua, Va, Ub, Vb, ranks_a, impl: str):
    """sum_k Ua_k (Va_k^T Ub_k) Vb_k^T as dense (N, b, b), fully batched.

    Inputs are (N, K, b, r*) term stacks. The per-term chains are flat
    batched GEMMs; the K-reduction is one wide GEMM over the concatenated
    width K*rb (the "concat the factors, multiply once" form).
    """
    N, K, b, ra = Ua.shape
    rb = Ub.shape[-1]
    if K == 0 or N == 0:
        return jnp.zeros((N, b, b), Ua.dtype)
    flat = lambda x: x.reshape(N * K, *x.shape[2:])  # noqa: E731
    fullb = jnp.full((N * K,), b, jnp.int32)
    W = ops.batched_gemm(jnp.swapaxes(flat(Va), 1, 2), flat(Ub), fullb,
                         impl=impl)                       # (NK, ra, rb)
    P = ops.batched_gemm(flat(Ua), W,
                         ranks_a.reshape(N * K).astype(jnp.int32),
                         impl=impl)                       # (NK, b, rb)
    Pc = P.reshape(N, K, b, rb).transpose(0, 2, 1, 3).reshape(N, b, K * rb)
    Vc = Vb.transpose(0, 2, 1, 3).reshape(N, b, K * rb)
    fullw = jnp.full((N,), K * rb, jnp.int32)
    return ops.batched_gemm(Pc, jnp.swapaxes(Vc, 1, 2), fullw, impl=impl)


@partial(jax.jit, static_argnames=("nb", "r_out", "rel", "impl"))
def _gemm_core(Da, Ua, Va, ranks_a, Db, Ub, Vb, eps, *, nb: int, r_out: int,
               rel: bool, impl: str):
    """The whole TLR x TLR product as one jitted batched computation."""
    trace_event("algebra")
    b = Da.shape[1]
    oi, oj, own, mid_a, mid_b, dmid_a, dmid_b = (
        jnp.asarray(x) for x in _gemm_indices(nb))
    no = own.shape[0]
    fullb = jnp.full((no,), b, jnp.int32)

    # dense diagonal of C: D_A(i) D_B(i) + sum_{m != i} lr x lr
    Dc = ops.batched_gemm(Da, Db, jnp.full((nb,), b, jnp.int32), impl=impl)
    if dmid_a.shape[1]:  # nb == 1: jnp.take squeezes empty index arrays
        Dc = Dc + _lrlr_dense_sum(
            jnp.take(Ua, dmid_a, axis=0), jnp.take(Va, dmid_a, axis=0),
            jnp.take(Ub, dmid_b, axis=0), jnp.take(Vb, dmid_b, axis=0),
            jnp.take(ranks_a, dmid_a), impl)
    if no == 0:
        z = jnp.zeros((0, b, r_out), Da.dtype)
        return Dc, z, z, jnp.zeros((0,), jnp.int32)

    # off-diagonal C(i, j), dense-accumulated from its nb inner products:
    #   k == i : D_A(i) B(i,j)           k == j : A(i,j) D_B(j)
    #   else   : A(i,k) B(k,j) low-rank chains, concatenated K-reduction
    Udl = ops.batched_gemm(jnp.take(Da, oi, axis=0),
                           jnp.take(Ub, own, axis=0), fullb, impl=impl)
    Vld = ops.batched_gemm(
        jnp.swapaxes(jnp.take(Db, oj, axis=0), 1, 2),
        jnp.take(Va, own, axis=0), fullb, impl=impl)
    C = ops.batched_gemm(
        jnp.concatenate([Udl, jnp.take(Ua, own, axis=0)], axis=-1),
        jnp.swapaxes(
            jnp.concatenate([jnp.take(Vb, own, axis=0), Vld], axis=-1), 1, 2),
        jnp.full((no,), Udl.shape[-1] + Ua.shape[-1], jnp.int32), impl=impl)
    if mid_a.shape[1]:  # nb == 2: no middle terms
        C = C + _lrlr_dense_sum(
            jnp.take(Ua, mid_a, axis=0), jnp.take(Va, mid_a, axis=0),
            jnp.take(Ub, mid_b, axis=0), jnp.take(Vb, mid_b, axis=0),
            jnp.take(ranks_a, mid_a), impl)
    U, V, ranks, _ = _compress_dense_tiles(C, eps, r_out=r_out, rel=rel,
                                           impl=impl)
    return Dc, U, V, ranks


def _as_tiles(X) -> TLRTiles:
    if isinstance(X, TLRTiles):
        return X
    if isinstance(X, TLRMatrix):
        return generalize(X)
    A = getattr(X, "A", None)  # TLROperator facade
    if isinstance(A, TLRMatrix):
        return generalize(A)
    raise TypeError(f"expected TLRMatrix / TLRTiles / TLROperator, "
                    f"got {type(X).__name__}")


@obs.traced("algebra.gemm", cat="algebra")
def tlr_gemm(A, B, eps, r_max_out=None, *, rel: bool = False,
             impl=None, batching: str = "flat") -> TLRTiles:
    """C = A @ B for TLR operands, compressed at ``eps``.

    ``A`` / ``B`` are ``TLRMatrix`` (mirrored onto the general grid),
    ``TLRTiles``, or ``TLROperator``. Every output tile accumulates its
    ``nb`` inner products as batched low-rank chains inside one jitted
    core, then a single rounding pass compresses all ``nb*(nb-1)`` output
    tiles -- no per-tile host loop; ``algebra_trace_count()`` counts the
    compiled variants (one per (nb, b, r) shape family).

    ``batching="ranked"``: each operand's factor stacks are sliced to the
    rank-ladder width covering its *actual* ranks before entering the core
    (exact -- columns past each rank are zero), so every accumulation chain
    and the concatenated K-reduction run at the bucketed width instead of
    ``r_max``. With an installed tile mesh the operand stacks shard their
    output-tile batch axis (``core/batching.py``).
    """
    Ga, Gb = _as_tiles(A), _as_tiles(B)
    if Ga.nb != Gb.nb or Ga.b != Gb.b:
        raise ValueError(f"tlr_gemm needs matching grids, got "
                         f"(nb={Ga.nb}, b={Ga.b}) and (nb={Gb.nb}, b={Gb.b})")
    ops.resolve_impl(impl)  # validate; each op resolves its own default
    batching = resolve_batching(
        batching, np.concatenate([np.asarray(Ga.ranks).reshape(-1),
                                  np.asarray(Gb.ranks).reshape(-1)]),
        max(Ga.r_max, Gb.r_max))
    r_out = r_max_out or min(max(Ga.r_max, Gb.r_max), Ga.b)
    Ua, Va, Ub, Vb = Ga.U, Ga.V, Gb.U, Gb.V
    if batching == "ranked" and Ua.shape[0]:
        wa = bucket_width(Ga.ranks, Ga.r_max)
        wb = bucket_width(Gb.ranks, Gb.r_max)
        Ua, Va = Ua[:, :, :wa], Va[:, :, :wa]
        Ub, Vb = Ub[:, :, :wb], Vb[:, :, :wb]
    if Ua.shape[0]:
        Ua, Va, Ub, Vb = shard_tile_batch(Ua, Va, Ub, Vb)
    Dc, U, V, ranks = _gemm_core(
        Ga.D, Ua, Va, Ga.ranks, Gb.D, Ub, Vb,
        jnp.asarray(eps, Ga.dtype), nb=Ga.nb, r_out=r_out, rel=rel,
        impl=impl)
    return TLRTiles(D=Dc, U=U, V=V, ranks=ranks)


# -- symmetric SYRK update  C = A - L L^T -------------------------------------


@lru_cache(maxsize=None)
def _syrk_buckets(nb: int):
    """Bucket the symmetric-update accumulation on the power-of-two ladder.

    Output tiles are all (i, j) with i >= j (packed lower first, then the
    nb diagonal slots appended at offset nt). Tile (i, j) sums ``j``
    low-rank inner products L(i,k) L(j,k)^T, k < j -- a term count that
    varies per tile, exactly the shape instability the bucket ladder
    exists for: tiles are grouped by ``bucket_up(j)`` so only ~log2(nb)
    accumulation variants compile. Returns a list of
    (out_slots, a_idx (N, Kb), b_idx (N, Kb), valid (N, Kb)) groups.
    """
    nt = nb * (nb - 1) // 2
    outs = [(int(i), int(j)) for i, j in tril_pairs(nb)]
    outs += [(i, i) for i in range(nb)]
    slots = list(range(nt)) + [nt + i for i in range(nb)]
    ladder = _bucket_ladder(nb - 1)
    groups = {}
    for slot, (i, j) in zip(slots, outs):
        if j == 0:
            continue  # no k < j terms; handled by the uniform parts
        Kb = _bucket_up(j, ladder)
        groups.setdefault(Kb, []).append((slot, i, j))
    out = []
    for Kb, members in sorted(groups.items()):
        N = len(members)
        sl = np.asarray([m[0] for m in members], np.int32)
        a_idx = np.zeros((N, Kb), np.int32)
        b_idx = np.zeros((N, Kb), np.int32)
        valid = np.zeros((N, Kb), bool)
        for t, (_, i, j) in enumerate(members):
            for k in range(j):
                a_idx[t, k] = tril_index(i, k)
                b_idx[t, k] = tril_index(j, k) if j > k else 0
            valid[t, :j] = True
        out.append((sl, a_idx, b_idx, valid))
    return out


@partial(jax.jit, static_argnames=("Kb", "impl"))
def _syrk_bucket(UL, VL, ranks_L, a_idx, b_idx, valid, *, Kb: int, impl: str):
    """Dense sum_{k<j} L(i,k) L(j,k)^T for one bucket's output tiles."""
    trace_event("algebra")
    Ua = jnp.take(UL, a_idx, axis=0) * valid[:, :, None, None]
    Va = jnp.take(VL, a_idx, axis=0)
    Ub = jnp.take(VL, b_idx, axis=0)   # term = U_ik (V_ik^T V_jk) U_jk^T
    Vb = jnp.take(UL, b_idx, axis=0)
    return _lrlr_dense_sum(Ua, Va, Ub, Vb, jnp.take(ranks_L, a_idx), impl)


@obs.traced("algebra.syrk", cat="algebra")
def tlr_syrk(A: TLRMatrix, L: TLRMatrix, eps, r_max_out=None, *,
             rel: bool = False, impl=None,
             batching: str = "flat") -> TLRMatrix:
    """Symmetric Schur update ``C = A - L L^T`` (lower-triangular TLR L).

    The right-looking counterpart of the factorization's left-looking
    column update: each output tile (i, j), i >= j, subtracts ``j``
    low-rank inner products plus the ``k == j`` diagonal-block term. Term
    counts ride the bucket ladder (~log2(nb) compiled accumulation
    variants); all nt off-diagonal results are compressed in one rounding
    pass. ``L.D`` holds the dense diagonal blocks L(k, k).

    ``batching="ranked"``: L's factor stacks are sliced to the rank-ladder
    width covering its actual ranks (exact), so every bucketed accumulation
    chain runs at the bucketed width instead of ``r_max``.
    """
    if A.nb != L.nb or A.b != L.b:
        raise ValueError(f"tlr_syrk needs matching grids, got "
                         f"(nb={A.nb}, b={A.b}) and (nb={L.nb}, b={L.b})")
    ops.resolve_impl(impl)  # validate; each op resolves its own default
    batching = resolve_batching(
        batching, np.concatenate([np.asarray(A.ranks).reshape(-1),
                                  np.asarray(L.ranks).reshape(-1)]),
        max(A.r_max, L.r_max))
    nb, b = A.nb, A.b
    nt = nb * (nb - 1) // 2
    r_out = r_max_out or min(max(A.r_max, L.r_max), b)
    dtype = A.dtype
    UL, VL = L.U, L.V
    if batching == "ranked" and nt:
        wl = bucket_width(L.ranks, L.r_max)
        UL, VL = UL[:, :, :wl], VL[:, :, :wl]

    # dense accumulation buffer: packed lower tiles, then the nb diagonals
    acc = jnp.zeros((nt + nb, b, b), dtype)
    if nt:
        acc = acc.at[:nt].set(
            ops.batched_gemm(A.U, jnp.swapaxes(A.V, 1, 2), A.ranks,
                             impl=impl))
    acc = acc.at[nt:].set(A.D)

    # k == j terms, uniform across outputs: off-diag L(i,j) D_j^T (one
    # batched chain over all nt lower tiles), diagonal D_i D_i^T
    if nt:
        pairs = tril_pairs(nb)
        jj = jnp.asarray(pairs[:, 1], jnp.int32)
        DV = ops.batched_gemm(jnp.take(L.D, jj, axis=0), VL,
                              jnp.full((nt,), b, jnp.int32), impl=impl)
        acc = acc.at[:nt].add(-ops.batched_gemm(
            UL, jnp.swapaxes(DV, 1, 2), L.ranks, impl=impl))
    acc = acc.at[nt:].add(-ops.batched_gemm(
        L.D, jnp.swapaxes(L.D, 1, 2), jnp.full((nb,), b, jnp.int32),
        impl=impl))

    # k < j terms: bucket-laddered batched accumulation (~log2(nb) shapes)
    for sl, a_idx, b_idx, valid in _syrk_buckets(nb):
        S = _syrk_bucket(UL, VL, L.ranks, jnp.asarray(a_idx),
                         jnp.asarray(b_idx), jnp.asarray(valid),
                         Kb=a_idx.shape[1], impl=impl)
        acc = acc.at[jnp.asarray(sl)].add(-S)

    if nt:
        U, V, ranks, _ = _compress_dense_tiles(
            acc[:nt], jnp.asarray(eps, dtype), r_out=r_out, rel=rel,
            impl=impl)
    else:
        U = V = jnp.zeros((0, b, r_out), dtype)
        ranks = jnp.zeros((0,), jnp.int32)
    return TLRMatrix(D=acc[nt:], U=U, V=V, ranks=ranks)


# -- column-scoped SYRK: the right-looking trailing update ---------------------


def _syrk_column_indices(nb: int, k: int, Tb: int):
    """Host gather grids for column ``k``'s trailing update, padded to the
    ``Tb``-row bucket. Slots map local trailing-row pairs ``(a, c)`` (rows
    ``k+1+a`` and ``k+1+c`` of the matrix) to packed-lower tile indices;
    padded slots carry ``valid=False`` and point at tile / block 0, where
    the core adds exact zeros. Vectorized on the (lru-cached) per-bucket
    pair grid -- no per-column Python loop, nothing retained per column.
    """
    T = nb - 1 - k
    pairs = tril_pairs(Tb)
    a = pairs[:, 0]
    c = pairs[:, 1]
    valid = a < T
    i, j = k + 1 + a, k + 1 + c
    oidx = np.where(valid, i * (i - 1) // 2 + j, 0).astype(np.int32)
    ar = np.arange(Tb)
    didx = np.where(ar < T, k + 1 + ar, 0).astype(np.int32)
    return (oidx, a.astype(np.int32), c.astype(np.int32), valid, didx,
            ar < T)


def _syrk_column_body(accU, accV, offsets, D, Up, Vn, ranks, dk,
                      oidx, aidx, cidx, valid, didx, dvalid, *,
                      ldl: bool, impl: str):
    """One column's eager trailing Schur update, fully batched.

    Per trailing tile (i, j), i > j > k, the single rank-``r_p`` term
    ``-L(i,k) D_k L(j,k)^T = -U_i (Vn_i^T D_k Vn_j) U_j^T`` is appended as
    a factor pair at that tile's write offset ``offsets[tile]`` of the
    accumulation buffers (the columns past the offset are zero, so a
    windowed scatter-add lands the block exactly; duplicate padded slots
    add zeros).
    ``offsets`` is a per-tile (nt,) vector -- uniform under flat batching,
    per-tile content widths under ranked batching, where each tile's
    concatenation stays compact instead of advancing in lockstep. Trailing
    diagonal tiles subtract their dense ``L(j,k) D_k L(j,k)^T`` product.
    """
    trace_event("algebra")
    Ui = jnp.take(Up, aidx, axis=0)
    Vi = jnp.take(Vn, aidx, axis=0)
    Uj = jnp.take(Up, cidx, axis=0)
    Vj = jnp.take(Vn, cidx, axis=0)
    if ldl:
        G = einsum("tbr,b,tbq->trq", Vi, dk, Vj)
    else:
        G = einsum("tbr,tbq->trq", Vi, Vj)
    left = -ops.batched_gemm(Ui, G, jnp.take(ranks, aidx), impl=impl)
    m = valid[:, None, None]
    left = jnp.where(m, left, jnp.zeros_like(left))
    right = jnp.where(m, Uj, jnp.zeros_like(Uj))
    off = jnp.take(offsets, oidx)
    accU = _append_at(accU, oidx, off, left)
    accV = _append_at(accV, oidx, off, right)
    if ldl:
        Gd = einsum("tbr,b,tbq->trq", Vn, dk, Vn)
    else:
        Gd = einsum("tbr,tbq->trq", Vn, Vn)
    upd = einsum("tbr,trq,tcq->tbc", Up, Gd, Up)
    upd = jnp.where(dvalid[:, None, None], upd, jnp.zeros_like(upd))
    D = D.at[didx].add(-upd)
    return accU, accV, D


# Two compiled families of the same body: the drivers rebind their
# accumulation buffers after every call, so they use the donating variant
# (XLA aliases accU/accV/D input->output: no per-column copy of the widest
# arrays in the factorization); external callers that reuse their arrays
# (timing loops, tests) get the copying default via ``donate=False``.
_syrk_column_core = jax.jit(_syrk_column_body,
                            static_argnames=("ldl", "impl"))
_syrk_column_core_donated = jax.jit(_syrk_column_body,
                                    static_argnames=("ldl", "impl"),
                                    donate_argnums=(0, 1, 3))


def _append_at(acc, oidx, off, blk):
    """``acc[oidx[s], :, off[s]:off[s] + r] += blk[s]`` for every slot
    ``s`` of the ``(S, b, r)`` block stack: one windowed scatter-add, in
    place on a donated ``acc``, with no full-width copy of the blocks.
    The columns past each tile's offset are zero, so the add lands each
    block exactly; a window that would leave the buffer is dropped (only
    padding slots, which carry zeros, can point there)."""
    dnums = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(1, 2), inserted_window_dims=(0,),
        scatter_dims_to_operand_dims=(0, 2))
    idx = jnp.stack([oidx, off], axis=-1).astype(jnp.int32)
    return jax.lax.scatter_add(acc, idx, blk, dnums,
                               mode=jax.lax.GatherScatterMode.FILL_OR_DROP)


def _syrk_head_body(accU, accV, offsets, D, Up, Vn, ranks, dk,
                    oidx, valid, didx, dvalid, *, ldl: bool, impl: str):
    """The *head* of a column's trailing update: tiles ``(i, k+1)`` for
    ``i > k+1`` plus the next diagonal ``D[k+1]`` -- everything column
    ``k+1`` needs before its own panel can factor.

    Slot ``s`` of the row-bucketed batch handles tile ``(k+1+s, k+1)``
    (``left = -U_i (V_i^T D_k V_{k+1})``, ``right = U_{k+1}``), a linear
    batch over the ``Tb`` row ladder instead of the full pair grid -- the
    lookahead schedule dispatches this narrow core eagerly and defers the
    wide pair-grid remainder (``_syrk_column_body`` masked to ``c >= 1``)
    until after the next panel is in flight.
    """
    trace_event("algebra")
    V0 = Vn[0]
    if ldl:
        G = einsum("tbr,b,bq->trq", Vn, dk, V0)
    else:
        G = einsum("tbr,bq->trq", Vn, V0)
    left = -ops.batched_gemm(Up, G, ranks, impl=impl)
    m = valid[:, None, None]
    left = jnp.where(m, left, jnp.zeros_like(left))
    right = jnp.where(m, jnp.broadcast_to(Up[0][None], Up.shape),
                      jnp.zeros_like(Up))
    off = jnp.take(offsets, oidx)
    accU = _append_at(accU, oidx, off, left)
    accV = _append_at(accV, oidx, off, right)
    if ldl:
        Gd = einsum("br,b,bq->rq", V0, dk, V0)
    else:
        Gd = einsum("br,bq->rq", V0, V0)
    upd = matmul(matmul(Up[0], Gd), Up[0].T)
    upd = jnp.where(dvalid, upd, jnp.zeros_like(upd))
    D = D.at[didx].add(-upd)
    return accU, accV, D


_syrk_head_core = jax.jit(_syrk_head_body, static_argnames=("ldl", "impl"))
_syrk_head_core_donated = jax.jit(_syrk_head_body,
                                  static_argnames=("ldl", "impl"),
                                  donate_argnums=(0, 1, 3))


@obs.traced("algebra.syrk_column", cat="algebra")
def tlr_syrk_column(accU, accV, used, D, Up, Vn, ranks, dk, k: int, *,
                    impl=None, part: str = "all", donate: bool = False):
    """Column-scoped SYRK: eagerly apply factor column ``k``'s trailing
    Schur update ``A(i,j) -= L(i,k) D_k L(j,k)^T`` for all i >= j > k.

    The right-looking driver's per-column counterpart of :func:`tlr_syrk`:
    instead of summing ``j`` inner products per output tile after the fact,
    each trailing tile receives column ``k``'s *single* rank-``r_p`` outer
    product the moment the column panel is factored. Off-diagonal trailing
    tiles get the term appended as a concatenated factor pair at column
    ``used`` of the ``(nt, b, W)`` accumulation buffers (growing factors
    between rounding passes -- see ``tlr_round_tiles``); trailing diagonal
    tiles ``D(j)`` subtract the dense product. The trailing-row batch is
    padded up the power-of-two bucket ladder, so only ~log2(nb) compiled
    accumulation variants serve all columns (trace-counted via
    ``algebra_trace_count``, the same contract as the rest of the algebra).

    Args: ``accU`` / ``accV``: (nt, b, W) accumulation buffers; ``used``:
    the write offset -- either a scalar first-free column (flat batching:
    uniform across live trailing tiles, every tile (i, j) with j > k has
    received exactly one term per factored column) or a per-tile (nt,)
    content-width vector (ranked batching: each tile's concatenation stays
    compact, appends land at its own width); ``D``: (nb, b, b) trailing
    diagonal tiles; ``Up`` / ``Vn`` / ``ranks``: column k's factored panel,
    row i at slot ``i - k - 1``; ``dk``: (b,) LDL^T diagonal of column k,
    or None for Cholesky.

    ``part`` splits the update for the lookahead schedule (DESIGN.md
    section 12): ``"head"`` applies only the tiles of column ``k+1`` plus
    ``D[k+1]`` (the narrow row-batched core), ``"tail"`` the pair-grid
    remainder (``c >= 1`` / trailing diagonals past ``k+1``), and
    ``"head"`` then ``"tail"`` is exactly equivalent to one ``"all"``
    call -- each trailing tile receives its single term from exactly one
    of the two, at the same offset, computed by the same formula.

    ``donate=True`` dispatches the donating compiled variant: the
    ``accU`` / ``accV`` / ``D`` buffers are invalidated and aliased into
    the outputs (zero-copy append). Callers must rebind -- i.e. use the
    returned arrays and never touch the arguments again.

    Returns the updated ``(accU, accV, D)``.
    """
    if part not in ("all", "head", "tail"):
        raise ValueError(f"part must be 'all', 'head' or 'tail', got "
                         f"{part!r}")
    nb = D.shape[0]
    T = nb - 1 - k
    if T <= 0:
        return accU, accV, D
    r_p = Up.shape[-1]
    ops.resolve_impl(impl)  # validate; each op resolves its own default
    ladder = _bucket_ladder(nb - 1)
    Tb = _bucket_up(T, ladder)
    w_acc = accU.shape[-1]
    # Gather grids first, masked down to the requested ``part``, so the
    # overflow check below only sees the tiles this call actually appends
    # to (after a "head" call bumped its tiles' widths, the full-grid max
    # would spuriously overflow for the following "tail").
    if part == "head":
        ar = np.arange(Tb)
        validh = (ar >= 1) & (ar < T)
        i = k + 1 + ar
        oidxh = np.where(validh, i * (i - 1) // 2 + (k + 1), 0)
        live = oidxh[validh]
    else:
        oidx, aidx, cidx, valid, didx, dvalid = _syrk_column_indices(
            nb, k, Tb)
        if part == "tail":
            valid = valid & (cidx >= 1)
            dvalid = dvalid & (np.arange(Tb) >= 1)
        live = oidx[valid]
    if np.ndim(used) == 0:
        high = int(used)
        offsets = jnp.full((accU.shape[0],), int(used), jnp.int32)
    else:
        u = np.asarray(used)
        high = int(u[live].max()) if live.size else 0
        offsets = jnp.asarray(u, jnp.int32)
    if high + r_p > w_acc:
        raise ValueError(
            f"no room for a rank-{r_p} append at column {high} of the "
            f"width-{w_acc} accumulation buffers; round first "
            f"(tlr_round_tiles)")
    accU, accV = shard_tile_batch(accU, accV, preserve_shape=True)
    ldl = dk is not None
    Upp = _pad_axis(Up, Tb)
    Vnp = _pad_axis(Vn, Tb)
    rkp = _pad_axis(ranks, Tb)
    if part == "head":
        core = _syrk_head_core_donated if donate else _syrk_head_core
        return core(accU, accV, offsets, D, Upp, Vnp, rkp, dk,
                    jnp.asarray(oidxh.astype(np.int32)),
                    jnp.asarray(validh),
                    jnp.asarray(k + 1, jnp.int32), jnp.asarray(True),
                    ldl=ldl, impl=impl)
    core = _syrk_column_core_donated if donate else _syrk_column_core
    return core(
        accU, accV, offsets, D, Upp, Vnp, rkp, dk,
        *(jnp.asarray(x) for x in (oidx, aidx, cidx, valid, didx, dvalid)),
        ldl=ldl, impl=impl)
