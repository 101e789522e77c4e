"""Operator-first public API: ``TLROperator`` and ``TLRFactorization``.

The paper's end-to-end workflow (compress -> factor -> solve/logdet/sample,
section 6) is exposed as two pytree-registered handles:

* ``TLROperator`` wraps the ``TLRMatrix`` representation with construction
  and algebra: classmethod constructors (``compress`` / ``from_dense`` /
  ``from_kernel``) that route through the *batched* compression path (one
  batched SVD or batched ARA over all nt tiles, no per-tile host loop),
  ``.matvec`` / ``@``, ``.to_dense``, ``.memory_stats``, and
  ``.cholesky(opts)`` / ``.ldlt(opts)`` returning a factorization handle.
  Shape/dtype follow the ``scipy.sparse.linalg.LinearOperator`` convention.
* ``TLRFactorization`` is the active result handle of the left-looking
  factorizations: ``.solve(y)`` (single or batched right-hand sides through
  the jitted bucketed TRSM), ``.logdet()``, ``.sample(key, num)``,
  ``.tri_matvec(x, trans=...)``, and ``.serve()`` (a continuous-batching
  inference server with this handle resident; ``repro.serve``, DESIGN.md
  section 10). As a *preconditioner* its operator action
  is ``A^{-1}``, so ``.matvec`` aliases ``.solve`` -- anything with a
  ``.matvec`` plugs into ``pcg`` directly.

Both handles are registered pytrees: factor/tile arrays are data leaves,
the tile permutation and host-side stats are static aux data, so handles
pass transparently through ``jax.tree`` utilities.

Every read path (``matvec``, ``tri_matvec``, the TRSM solves, ``sample``)
and every batched algebra method takes a ``batching`` knob defaulting to
``"auto"``: the memoized :func:`~.batching.tile_plan` of the operator's
ranks decides flat vs rank-bucketed dispatch (DESIGN.md section 9). The
pre-PR-2 free function ``from_dense`` survives as a deprecated shim; the
``tlr_factor_solve`` / ``tlr_logdet`` / ``mvn_sample`` shims were removed
in PR 6 (DESIGN.md section 5).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from .ara import ARAParams, ara_compress_dense
from .tlr import TLRMatrix, tril_pairs
from . import solve as _solve


# -- batched tile compression (construction hot path) --------------------------


@partial(jax.jit, static_argnames=("nb", "b"))
def _split_tiles(A: jax.Array, nb: int, b: int):
    """One reshape-based gather of all tiles: diag (nb,b,b) + lower (nt,b,b)
    (jitted, so the gather reads A in place instead of a transposed copy)."""
    Ab = A.reshape(nb, b, nb, b).transpose(0, 2, 1, 3)
    diag = jnp.arange(nb)
    D = Ab[diag, diag]
    pairs = tril_pairs(nb)
    if len(pairs):
        tiles = Ab[pairs[:, 0], pairs[:, 1]]
    else:
        tiles = jnp.zeros((0, b, b), A.dtype)
    return D, tiles


@partial(jax.jit, static_argnames=("r_max", "rel"))
def _svd_compress_tiles(tiles, eps, *, r_max: int, rel: bool):
    """Batched truncated SVD of (nt, b, b) tiles at the ``from_dense``
    truncation semantics: keep singular values > eps (absolute) or
    > eps * s_max (relative), 0 <= rank <= r_max, columns past the rank
    zeroed (the layout's load-bearing invariant, DESIGN.md section 1).
    A numerically-zero tile compresses to rank 0 (all-zero factors) --
    the same floor the algebra's rounding pass uses, so compression and
    ``tlr_round`` agree on what a zero tile is (a rank-1 phantom factor
    would skew ``memory_stats`` and every rank-masked GEMM)."""
    b = tiles.shape[1]
    k = min(r_max, b)
    Ub, s, Vt = jnp.linalg.svd(tiles, full_matrices=False)
    cut = eps * (s[:, :1] if rel else jnp.ones_like(s[:, :1]))
    ranks = jnp.clip(jnp.sum(s > cut, axis=1), 0, r_max).astype(jnp.int32)
    mask = (jnp.arange(k)[None, :] < ranks[:, None]).astype(tiles.dtype)
    U = Ub[:, :, :k] * (s[:, None, :k] * mask[:, None, :])
    V = jnp.swapaxes(Vt, 1, 2)[:, :, :k] * mask[:, None, :]
    if r_max > k:
        pad = ((0, 0), (0, 0), (0, r_max - k))
        U, V = jnp.pad(U, pad), jnp.pad(V, pad)
    return U, V, ranks


# -- the operator handle -------------------------------------------------------


@dataclasses.dataclass
class TLROperator:
    """Symmetric TLR operator handle wrapping a ``TLRMatrix`` (pytree)."""

    A: TLRMatrix

    # -- scipy.sparse.linalg-style introspection --------------------------

    @property
    def shape(self) -> tuple[int, int]:
        return (self.A.n, self.A.n)

    @property
    def dtype(self):
        return self.A.dtype

    @property
    def nb(self) -> int:
        return self.A.nb

    @property
    def b(self) -> int:
        return self.A.b

    @property
    def n(self) -> int:
        return self.A.n

    @property
    def r_max(self) -> int:
        return self.A.r_max

    @property
    def ranks(self) -> jax.Array:
        return self.A.ranks

    # -- construction -----------------------------------------------------

    @classmethod
    def compress(
        cls,
        dense: Union[jax.Array, np.ndarray],
        tile: int,
        r_max: Optional[int] = None,
        eps: float = 1e-6,
        *,
        rel: bool = False,
        method: str = "svd",
        store_dtype=None,
        bs: int = 16,
        key: Optional[jax.Array] = None,
    ) -> "TLROperator":
        """Compress a dense symmetric matrix into TLR form, batched.

        All nt off-diagonal tiles are gathered with one reshape and
        compressed in a single batched call -- a batched (vmapped) SVD
        (``method="svd"``, rank oracle) or the batched ARA of Algorithm 1
        (``method="ara"``, the paper's sampling-based compressor) -- instead
        of the O(nb^2) per-tile host SVD loop of the old ``from_dense``.

        ``store_dtype``: optional lower precision for the off-diagonal U/V
        factors (the paper's section 7 mixed-precision proposal); diagonal
        tiles stay in the working precision.
        """
        host_dtype = np.asarray(dense).dtype if method == "svd" else None
        A = jnp.asarray(dense)
        n = A.shape[0]
        if n % tile:
            raise ValueError(f"n={n} must be a multiple of tile size b={tile}")
        nb = n // tile
        r_max = r_max or tile
        if host_dtype is not None and host_dtype != A.dtype:
            # jnp.asarray narrowed the input (f64 input, jax_enable_x64 off).
            # Truncating at eps against narrowed SVD noise would destroy the
            # compression (f32 singular-value noise ~1e-7*s_max swamps tight
            # thresholds), so rank detection runs host-side at the input
            # precision -- one *batched* numpy SVD, still no per-tile loop --
            # and only the resulting factors narrow on device, exactly the
            # old from_dense behavior.
            return cls._compress_host(np.asarray(dense), nb, tile, r_max,
                                      eps, rel=rel, store_dtype=store_dtype)
        D, tiles = _split_tiles(A, nb, tile)
        nt = tiles.shape[0]
        if nt == 0:
            U = jnp.zeros((0, tile, r_max), A.dtype)
            V = jnp.zeros((0, tile, r_max), A.dtype)
            ranks = jnp.zeros((0,), jnp.int32)
        elif method == "svd":
            U, V, ranks = _svd_compress_tiles(
                tiles, jnp.asarray(eps, A.dtype), r_max=r_max, rel=rel)
        elif method == "ara":
            if rel:
                raise ValueError("rel thresholds are SVD-only; ARA uses the "
                                 "absolute 2-norm residual estimate")
            p = ARAParams(bs=min(bs, r_max), r_max=r_max, eps=eps)
            key = key if key is not None else jax.random.PRNGKey(0)
            U, B, ranks, _ = ara_compress_dense(tiles, key, p)
            V = B  # tile ~= Q B^T  =>  U=Q, V=B
        else:
            raise ValueError(f"method must be 'svd' or 'ara', got {method!r}")
        if store_dtype is not None:
            sdt = jnp.dtype(store_dtype)
            U, V = U.astype(sdt), V.astype(sdt)
        return cls(TLRMatrix(D=D, U=U, V=V, ranks=ranks))

    @classmethod
    def _compress_host(cls, A: np.ndarray, nb: int, tile: int, r_max: int,
                       eps: float, *, rel: bool, store_dtype) -> "TLROperator":
        """Batched-SVD compression at full host precision (numpy), for f64
        inputs when the device dtype would narrow them. Same truncation
        semantics as ``_svd_compress_tiles``; one batched ``np.linalg.svd``
        call over all nt tiles, no per-tile loop."""
        b = tile
        k = min(r_max, b)
        Ab = A.reshape(nb, b, nb, b).transpose(0, 2, 1, 3)
        D = Ab[np.arange(nb), np.arange(nb)]
        pairs = tril_pairs(nb)
        tiles = (Ab[pairs[:, 0], pairs[:, 1]] if len(pairs)
                 else np.zeros((0, b, b), A.dtype))
        nt = tiles.shape[0]
        U = np.zeros((nt, b, r_max), A.dtype)
        V = np.zeros((nt, b, r_max), A.dtype)
        if nt:
            Ub, s, Vt = np.linalg.svd(tiles, full_matrices=False)
            cut = eps * (s[:, :1] if rel else 1.0)
            # rank floor 0, matching _svd_compress_tiles / tlr_round
            ranks = np.clip((s > cut).sum(axis=1), 0, r_max).astype(np.int32)
            mask = (np.arange(k)[None, :] < ranks[:, None]).astype(A.dtype)
            U[:, :, :k] = Ub[:, :, :k] * (s[:, None, :k] * mask[:, None, :])
            V[:, :, :k] = np.swapaxes(Vt, 1, 2)[:, :, :k] * mask[:, None, :]
        else:
            ranks = np.zeros((0,), np.int32)
        sdt = np.dtype(store_dtype) if store_dtype is not None else A.dtype
        return cls(TLRMatrix(
            D=jnp.asarray(D), U=jnp.asarray(U.astype(sdt)),
            V=jnp.asarray(V.astype(sdt)), ranks=jnp.asarray(ranks)))

    @classmethod
    def from_dense(cls, dense, tile: int, r_max: Optional[int] = None,
                   eps: float = 1e-6, **kw) -> "TLROperator":
        """Alias of :meth:`compress` (scipy-style constructor name)."""
        return cls.compress(dense, tile, r_max, eps, **kw)

    @classmethod
    def from_kernel(
        cls,
        points: np.ndarray,
        kernel: Union[str, Callable[[np.ndarray], np.ndarray]] = "exp",
        *,
        tile: int,
        eps: float = 1e-8,
        ell: Optional[float] = None,
        nugget: float = 1e-8,
        r_max: Optional[int] = None,
        **kw,
    ) -> "TLROperator":
        """Build a covariance operator from a point cloud and a kernel.

        ``kernel`` is ``"exp"`` / ``"matern32"`` (paper section 6.1 kernels,
        with the paper's default correlation lengths per dimension) or any
        callable ``points -> dense (n, n)``. ``points`` must already be in
        tile order (apply ``kd_tree_ordering`` first, or use
        ``covariance_problem``, which returns ordered points) -- the
        operator's rows follow the point order, so reordering internally
        would silently misalign every vector the caller passes later.
        """
        from .generators import exp_covariance, matern32_covariance

        pts = np.asarray(points)
        if callable(kernel):
            K = kernel(pts)
        else:
            ell = ell if ell is not None else (0.1 if pts.shape[1] == 2 else 0.2)
            if kernel == "exp":
                K = exp_covariance(pts, ell, nugget)
            elif kernel == "matern32":
                K = matern32_covariance(pts, ell, nugget)
            else:
                raise ValueError(f"unknown kernel {kernel!r}")
        return cls.compress(jnp.asarray(K), tile, r_max, eps, **kw)

    # -- algebra ----------------------------------------------------------

    def matvec(self, x: jax.Array, *,
               batching: str | None = "auto") -> jax.Array:
        """y = A @ x; x is (n,) or batched (n, m). ``batching`` picks flat
        vs rank-bucketed dispatch (``"auto"`` lets the plan decide)."""
        return _solve.tlr_matvec(self.A, x, batching=batching)

    def plan(self):
        """The memoized :class:`~.batching.TilePlan` for this operator's
        rank distribution (rank buckets, ladder widths, FLOP estimates) --
        the execution plan every batched path dispatches through."""
        from .batching import tile_plan

        return tile_plan(self.A.ranks, self.A.r_max)

    def __matmul__(self, x):
        if isinstance(x, (jax.Array, np.ndarray)):
            return self.matvec(jnp.asarray(x))
        return NotImplemented

    def to_dense(self) -> jax.Array:
        return self.A.to_dense()

    def memory_stats(self) -> dict:
        return self.A.memory_stats()

    def diagonal_tiles(self) -> jax.Array:
        return self.A.D

    def trace(self) -> jax.Array:
        """tr(A): sum of the dense diagonal tiles' diagonals (the
        Newton-Schulz scaling ``alpha = 1/trace``, core/precond.py)."""
        return jnp.einsum("kbb->", self.A.D)

    def diagonal(self) -> jax.Array:
        """diag(A) as an (n,) vector, from the dense diagonal tiles."""
        return jnp.einsum("kbb->kb", self.A.D).reshape(self.n)

    # -- tile-algebra arithmetic (core/algebra.py; DESIGN.md section 6) ----

    def __add__(self, other):
        """A + B, exact low-rank concatenation (ranks add; call
        :meth:`round` to recompress)."""
        from .algebra import tlr_axpy

        if isinstance(other, TLROperator):
            return TLROperator(tlr_axpy(1.0, self.A, other.A))
        return NotImplemented

    def __sub__(self, other):
        from .algebra import tlr_axpy

        if isinstance(other, TLROperator):
            return TLROperator(tlr_axpy(-1.0, other.A, self.A))
        return NotImplemented

    def __mul__(self, alpha):
        from .algebra import tlr_scale

        if isinstance(alpha, (int, float, np.number)) or (
                isinstance(alpha, (jax.Array, np.ndarray))
                and jnp.ndim(alpha) == 0):
            return TLROperator(tlr_scale(alpha, self.A))
        return NotImplemented

    __rmul__ = __mul__

    def __neg__(self):
        return self * -1.0

    def compose(self, other, eps: float = 0.0, r_max_out=None, *, impl=None,
                batching: str = "auto"):
        """C = A @ other as a general (nonsymmetric) ``TLRTiles`` grid,
        compressed at ``eps`` (0.0 keeps everything up to the rank cap;
        pass a real threshold to bound ranks). ``other`` is a
        ``TLROperator``, ``TLRMatrix``, or ``TLRTiles``.
        ``batching="ranked"`` runs the accumulation chains at the
        rank-bucketed widths (core/batching.py); ``"auto"`` (default)
        lets the rank histogram decide."""
        from .algebra import tlr_gemm

        return tlr_gemm(self.A, other, eps, r_max_out, impl=impl,
                        batching=batching)

    def round(self, eps: float, r_max_out=None, *, impl=None,
              batching: str = "auto") -> "TLROperator":
        """Recompress every off-diagonal tile at ``eps`` (one batched
        QR + small-SVD pass, ``core/algebra.py``; ``batching="ranked"``
        dispatches rank-homogeneous buckets instead of one r_max-wide
        batch, DESIGN.md section 8; ``"auto"`` lets the rank histogram
        decide)."""
        from .algebra import tlr_round

        return TLROperator(tlr_round(self.A, eps, r_max_out, impl=impl,
                                     batching=batching))

    # -- factorization ----------------------------------------------------

    def cholesky(self, opts=None) -> "TLRFactorization":
        """TLR Cholesky; returns the factorization handle.

        ``opts.algo`` picks the driver: ``"left"`` (default) is the paper's
        left-looking sampling-chain factorization (Algorithm 6 / 9),
        ``"right"`` the right-looking variant that eagerly applies trailing
        Schur updates through the batched tile algebra (DESIGN.md
        section 7) -- better batch width at small nb, and the layout
        multi-device sharding wants.
        """
        from .cholesky import CholOptions, tlr_cholesky

        return tlr_cholesky(self.A, opts or CholOptions())

    def ldlt(self, opts=None) -> "TLRFactorization":
        """TLR LDL^T (Algorithm 10); returns the handle. ``opts.algo``
        selects left- vs right-looking, as in :meth:`cholesky`."""
        from .cholesky import CholOptions, tlr_ldlt

        return tlr_ldlt(self.A, opts or CholOptions())


jax.tree_util.register_dataclass(
    TLROperator, data_fields=("A",), meta_fields=())


# -- the factorization handle --------------------------------------------------


@dataclasses.dataclass
class TLRFactorization:
    """Active handle for a TLR factorization  P A P^T = L L^T  (or L D L^T).

    ``L.D`` holds the dense diagonal blocks L(k,k) (unit-lower for LDL^T),
    ``d`` the LDL diagonal (None for Cholesky), ``perm`` the tile-level
    pivot permutation (logical -> original), ``stats`` the driver's
    per-column instrumentation. Solves run through the jitted bucketed TRSM
    (``core/solve.py``) and accept single or batched right-hand sides.
    """

    L: TLRMatrix
    d: Optional[jax.Array]
    perm: np.ndarray
    stats: dict

    @property
    def nb(self) -> int:
        return self.L.nb

    @property
    def b(self) -> int:
        return self.L.b

    @property
    def n(self) -> int:
        return self.L.n

    @property
    def shape(self) -> tuple[int, int]:
        return (self.L.n, self.L.n)

    @property
    def dtype(self):
        return self.L.dtype

    @property
    def is_ldlt(self) -> bool:
        return self.d is not None

    def solve(self, y: jax.Array) -> jax.Array:
        """x = A^{-1} y through the factorization; y is (n,) or (n, m)."""
        return _solve._factor_solve_impl(self, y)

    def matvec(self, y: jax.Array) -> jax.Array:
        """Preconditioner action: the operator a factorization applies is
        M^{-1} ~= A^{-1}, so ``matvec`` aliases :meth:`solve` (this is what
        lets a factorization plug into ``pcg`` anywhere an operator fits)."""
        return self.solve(y)

    def tri_matvec(self, x: jax.Array, *, trans: bool = False,
                   batching: str | None = "auto") -> jax.Array:
        """y = L @ x (or L^T @ x)."""
        return _solve.tlr_tri_matvec(self.L, x, trans=trans,
                                     batching=batching)

    def tri_solve(self, y: jax.Array, *, trans: bool = False,
                  batching: str | None = "auto") -> jax.Array:
        """x = L^{-1} y (or L^{-T} y) via the jitted bucketed TRSM
        (``batching`` picks flat vs plan-width column steps)."""
        return _solve.tlr_trsv(self.L, y, trans=trans, batching=batching)

    def plan(self):
        """The memoized :class:`~.batching.TilePlan` of the factor's rank
        distribution (what the TRSM / tri_matvec read paths dispatch on)."""
        from .batching import tile_plan

        return tile_plan(self.L.ranks, self.L.r_max)

    def logdet(self) -> jax.Array:
        """log |det A| from the factorization diagonals."""
        return _solve._logdet_impl(self)

    def sample(self, key: jax.Array, num: int = 1) -> jax.Array:
        """x ~ N(0, A) via x = P^T L z (Cholesky factorizations only)."""
        return _solve._mvn_sample_impl(self, key, num)

    def serve(self, *, operator=None, slots: int = 8, check_every: int = 4,
              seed: int = 0, warmup: bool = True):
        """A :class:`~repro.serve.TLRServer` with this factorization
        resident (fid ``"default"``): continuous-batching solve / logdet /
        sample / pcg_solve through fixed ``(n, slots)`` RHS blocks.

        Pass ``operator`` (the compressed A this handle factors) to enable
        ``pcg_solve`` requests -- the server builds a width-``slots``
        batched PCG engine over it preconditioned by this factorization.
        ``warmup=True`` compiles the serve path before returning, so the
        first tick is already recompile-free (DESIGN.md section 10).
        """
        from ..serve import TLRServer

        srv = TLRServer(slots, check_every=check_every, seed=seed)
        srv.register("default", self, operator=operator)
        if warmup:
            srv.warmup()
        return srv


jax.tree_util.register_dataclass(
    TLRFactorization, data_fields=("L", "d"), meta_fields=("perm", "stats"))
