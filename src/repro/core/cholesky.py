"""TLR Cholesky / LDL^T drivers: left-looking batched ARA (Algorithms 4-6,
9, 10) and a right-looking variant built on the PR-3 tile algebra.

``CholOptions.algo`` selects the driver; both share the stats schema and
the bucket-ladder shape discipline.

LEFT-LOOKING (``algo="left"``, the paper's driver). Per block column ``k``
(host-driven, like the paper's CUDA host orchestration):

  1. dense diagonal update  A(k,k) -= sum_j L(k,j) L(k,j)^T
     (optionally Schur-compensated, section 5.1.1),
  2. dense Cholesky (or LDL^T) of the diagonal tile, with a modified-Cholesky
     fallback (section 5.1.2),
  3. ARA compression of every updated tile in the column: the matrix
     expression ``A(i,k) - sum_j L(i,j) L(k,j)^T`` is sampled through the
     4-product chain (Eq. 2; 5-product for LDL^T, Eq. 3) -- compression
     happens ONCE per output tile, ab initio,
  4. batched triangular solve  V(i,k) = L(k,k)^{-1} B_i  (+ D^{-1} scaling
     for LDL^T).

Column ARA (Algorithm 5's batch, DESIGN.md section 2): the whole column is
one batch at a *stable shape*; one device loop (``lax.while_loop``) steps
it until every tile converges, converged tiles masked where the paper
evicts them, and the host reads the outcome once. The dead slots this
leaves are counted (``column_events[k]["tile_iters"]``, ``["slots"]``).

Shape-stable column pipeline (DESIGN.md sections 2-3): the row-batch size
``T = nb-k-1`` and prior-column count ``J = k`` change every column, which
would retrace the jitted ARA step ``nb`` times. Instead each column is
zero-padded up to a (T, J) *bucket pair* drawn from a power-of-two ladder
(``_bucket_ladder``), with a per-slot validity mask making padded slots
numerically inert, so ~log2(nb) compiled variants serve all columns. All
sampling / projection GEMMs route through the ``repro.kernels.ops`` dispatch
layer, selected by ``CholOptions.impl``.

RIGHT-LOOKING (``algo="right"``; DESIGN.md section 7). No sampling chain:
every tile of the trailing matrix is kept *materialized* as an accumulated
low-rank concatenation. Per column ``k``:

  1. dense factor of the diagonal tile -- already fully updated, because
     every earlier column applied its Schur update eagerly,
  2. one batched rounding pass (QR + small-SVD, ``tlr_round_tiles``)
     recompresses the column panel's accumulated factors,
  3. batched TRSM into the panel bases,
  4. the trailing matrix receives column ``k``'s rank-r_k outer product via
     the column-scoped ``tlr_syrk_column`` (core/algebra.py): off-diagonal
     trailing tiles append a concatenated factor pair, diagonal tiles
     subtract the dense product. Appends accumulate for
     ``CholOptions.right_flush`` columns between full rounding passes.

The eager trailing update is embarrassingly parallel over output tiles --
the batch layout the multi-device sharding item in ROADMAP.md wants -- and
trades the left-looking sampling chain for wider batches at small nb.
Inter-tile pivoting (Algorithm 9) is left-looking only.
"""

from __future__ import annotations

import dataclasses
import time
import warnings
from functools import lru_cache, partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

import types

from . import ara as ara_mod
from .algebra import (algebra_trace_count, tlr_round_tiles, tlr_syrk_column)
from .ara import ARAParams, ara_iteration, init_state
from .batching import (batching_trace_count, bucket_width,
                       bucketed_round_tiles, pad_tile_batch, resolve_policy,
                       shard_tile_batch, tile_mesh, tile_plan)
from .buckets import _bucket_ladder, _bucket_up, _column_buckets, _pad_axis
from .health import (FactorizationBreakdown, HealthMonitor,  # noqa: F401
                     RetryPolicy, column_flags)
from .operator import TLRFactorization
from .stages import (LookaheadSchedule, SequentialSchedule, Stage, run_graph)
from .tlr import (TLRMatrix, num_tiles, tril_index, tril_pairs,
                  zeros_like_structure)
from ..kernels import ops
from .. import faults, obs, precision
from ..precision import einsum, matmul


@dataclasses.dataclass(frozen=True)
class CholOptions:
    eps: float = 1e-6
    bs: int = 16
    r_max_out: int = 0            # 0 => A.r_max
    algo: str = "left"            # "left" (ARA sampling) | "right" (eager updates)
    share_omega: bool = True      # share Omega across the column (beyond-paper)
    schur: Optional[str] = "diag" # None | "diag" | "full"
    modified_chol: bool = True
    pivot: Optional[str] = None   # None | "frobenius" | "power"
    ldl: bool = False
    calib: float = 1.0
    gs_passes: int = 2
    max_iters: int = 0            # ARA iteration cap; 0 => r_max // bs
    right_flush: int = 0          # algo="right": columns of rank-r appends
                                  # accumulated between trailing rounding
                                  # passes; 0 => the auto policy picks the
                                  # cadence from the rank histogram
    batching: str = "auto"        # "auto" (rank-histogram policy, DESIGN.md
                                  # section 9) | "flat" (r_max-wide batches,
                                  # compatibility) | "ranked" (rank-bucketed
                                  # dynamic batching, DESIGN.md section 8)
    seed: int = 0
    impl: Optional[str] = None    # None => backend default; "ref" | "interpret" | "pallas"
    lookahead: bool = False       # algo="right": schedule column k+1's
                                  # diag+panel between the head and tail of
                                  # column k's trailing update (DESIGN.md
                                  # section 12); the sequential schedule
                                  # stays the exact-parity default. Ignored
                                  # by algo="left" (its column graph is a
                                  # serial chain).
    check: bool = False           # breakdown detection + bounded recovery
                                  # at stage boundaries (DESIGN.md section
                                  # 13). Off (the default) costs nothing
                                  # and reproduces factors bitwise; on, a
                                  # clean run is also bitwise identical
                                  # (checks only read) at <= a few % wall
                                  # time.
    retry: RetryPolicy = RetryPolicy()
                                  # remedy escalation schedule used when
                                  # ``check`` is on: diagonal jitter on SPD
                                  # breakdown, eps-loosened ARA re-pass +
                                  # per-tile densify on rank overflow.

    def ara_params(self, r_max: int) -> ARAParams:
        return ARAParams(bs=self.bs, r_max=r_max, eps=self.eps,
                         calib=self.calib, gs_passes=self.gs_passes,
                         max_iters=self.max_iters)


# TLRFactorization (the active result handle) lives in core/operator.py;
# the bucket-ladder helpers (DESIGN.md section 2) in core/buckets.py, shared
# with the bucketed TRSM in core/solve.py. Both are re-exported here for the
# existing import sites (tests reach _bucket_ladder through this module).


# -- tile gathers -------------------------------------------------------------


def _row_indices(i: int, k: int) -> list[int]:
    """Packed indices of tiles (i, j) for j < k (requires i >= k)."""
    return [tril_index(i, j) for j in range(k)]


def _L_index(rows, k: int, Tb: int, Jb: int):
    """Packed indices of the L tiles (i, j), i in ``rows``, j < k, padded
    to (Tb, Jb), with the mask of the real slots."""
    idx = np.zeros((Tb, Jb), np.int32)
    for t, i in enumerate(rows):
        idx[t, :k] = _row_indices(int(i), k)
    valid = ((np.arange(Tb) < len(rows))[:, None]
             & (np.arange(Jb) < k)[None, :])
    return idx, valid


def _A_index(rows, k: int, perm: np.ndarray, Tb: int):
    """Packed indices of the original-A tiles of logical (i, k), i in
    ``rows``, padded to Tb, resolving the pivot perm: a logical tile maps
    to original (perm[i], perm[k]), and when perm[i] < perm[k] the stored
    tile is its transpose, so the U/V roles swap (``flip``)."""
    idx = np.zeros(Tb, np.int32)
    flip = np.zeros(Tb, bool)
    ok = int(perm[k])
    for t, i in enumerate(rows):
        oi = int(perm[i])
        idx[t] = tril_index(max(oi, ok), min(oi, ok))
        flip[t] = oi < ok
    return idx, np.arange(Tb) < len(rows), flip


@partial(jax.jit, static_argnames=("w",))
def _gather_tiles(U, V, ranks, idx, valid, flip=None, *, w=None):
    """Tiles ``U[idx]``, ``V[idx]`` (first ``w`` columns) and their ranks
    (None without ``ranks``); slots where ``valid`` is False are zero, and
    where ``flip`` is True the U/V roles swap. The host pads ``idx`` up to
    the bucket sizes, so a factorization compiles one gather per bucket
    shape, not a gather and its pads per column."""
    if w is not None:
        U, V = U[..., :w], V[..., :w]
    Ug, Vg = jnp.take(U, idx, axis=0), jnp.take(V, idx, axis=0)
    if flip is not None:
        f = flip[..., None, None]
        Ug, Vg = jnp.where(f, Vg, Ug), jnp.where(f, Ug, Vg)
    m = valid[..., None, None]
    zero = jnp.zeros((), U.dtype)
    return (jnp.where(m, Ug, zero), jnp.where(m, Vg, zero),
            None if ranks is None
            else jnp.where(valid, jnp.take(ranks, idx), 0))


@jax.jit
def _masked_rows(x, valid):
    """The first ``len(valid)`` rows of ``x``, zero where ``valid`` is
    False."""
    rows = x[:valid.shape[0]]
    return jnp.where(valid.reshape(valid.shape + (1,) * (x.ndim - 1)),
                     rows, jnp.zeros((), x.dtype))


# -- sampling closures (Eq. 2 / Eq. 3) ----------------------------------------


def make_column_samplers(ldl: bool, impl: str | None = None):
    """Samplers for the column expression A(i,k) - sum_j L(i,j) D_j L(k,j)^T.

    data = dict(Uk, Vk: (J,b,r) row-k tiles of L;  Ui, Vi: (T,J,b,r) row-i
    tiles;  Ua, Va: (T,b,rA) original A(i,k);  ranksA: (T,) A-tile ranks;
    dk: (J,b) LDL diagonals or None). Omega is (b,s) when shared across the
    column, else (T,b,s). All axes may be zero-padded up to bucket sizes;
    padded tiles are zero, hence numerically inert in every product.

    Every GEMM routes through the ``repro.kernels.ops`` dispatch layer
    (DESIGN.md section 3): the A-term uses the rank-masked ``batched_gemm``,
    the per-j intermediate ``W2 = V(k,j) (U(k,j)^T Omega)`` uses
    ``tile_chain``, and the j-reduction uses the fused ``lr_sample`` kernel
    (shared-Omega path) or a flattened ``tile_chain`` (per-tile Omega).
    """

    def _dk_flat(dk, T, J, b):
        return jnp.broadcast_to(dk[None], (T, J, b)).reshape(T * J, b)

    def sample(data, Omega):
        Ua, Va, Uk, Vk, Ui, Vi = (
            data["Ua"], data["Va"], data["Uk"], data["Vk"],
            data["Ui"], data["Vi"],
        )
        T, b = Ua.shape[0], Ua.shape[1]
        J, r = Uk.shape[0], Uk.shape[2]
        s = Omega.shape[-1]
        shared = Omega.ndim == 2
        Om_t = jnp.broadcast_to(Omega, (T, b, s)) if shared else Omega
        # A-term: Ya[t] = Ua[t][:, :rank_t] @ (Va[t]^T Omega_t)
        VtOm = einsum("tbr,tbs->trs", Va, Om_t)
        Ya = ops.batched_gemm(Ua, VtOm, data["ranksA"], impl=impl)
        if shared:
            # Hoisted per-column intermediate, then the fused j-reduction.
            OmJ = jnp.broadcast_to(Omega, (J, b, s))
            W2 = ops.tile_chain(Vk, Uk, OmJ, impl=impl)          # (J, b, s)
            if ldl:
                W2 = W2 * data["dk"][:, :, None]
            Yu = ops.lr_sample(Ui, Vi, W2, impl=impl)
        else:
            Uk_r = jnp.broadcast_to(Uk[None], (T, J, b, r)).reshape(T * J, b, r)
            Vk_r = jnp.broadcast_to(Vk[None], (T, J, b, r)).reshape(T * J, b, r)
            Om_r = jnp.broadcast_to(
                Om_t[:, None], (T, J, b, s)).reshape(T * J, b, s)
            W2 = ops.tile_chain(Vk_r, Uk_r, Om_r, impl=impl)
            if ldl:
                W2 = W2 * _dk_flat(data["dk"], T, J, b)[:, :, None]
            Yu = ops.tile_chain(Ui.reshape(T * J, b, r),
                                Vi.reshape(T * J, b, r), W2, impl=impl)
            Yu = Yu.reshape(T, J, b, s).sum(axis=1)
        return Ya - Yu

    def sample_t(data, Q):
        Ua, Va, Uk, Vk, Ui, Vi = (
            data["Ua"], data["Va"], data["Uk"], data["Vk"],
            data["Ui"], data["Vi"],
        )
        T, b = Ua.shape[0], Ua.shape[1]
        J, r = Uk.shape[0], Uk.shape[2]
        R = Q.shape[-1]
        UtQ = einsum("tbr,tbq->trq", Ua, Q)
        Ba = ops.batched_gemm(Va, UtQ, data["ranksA"], impl=impl)
        # S2[t,j] = Vi[t,j] (Ui[t,j]^T Q[t]);  Bu[t] = sum_j Uk[j] (Vk[j]^T S2)
        Q_r = jnp.broadcast_to(Q[:, None], (T, J, b, R)).reshape(T * J, b, R)
        S2 = ops.tile_chain(Vi.reshape(T * J, b, r),
                            Ui.reshape(T * J, b, r), Q_r, impl=impl)
        if ldl:
            S2 = S2 * _dk_flat(data["dk"], T, J, b)[:, :, None]
        Uk_r = jnp.broadcast_to(Uk[None], (T, J, b, r)).reshape(T * J, b, r)
        Vk_r = jnp.broadcast_to(Vk[None], (T, J, b, r)).reshape(T * J, b, r)
        Bu = ops.tile_chain(Uk_r, Vk_r, S2, impl=impl)
        Bu = Bu.reshape(T, J, b, R).sum(axis=1)
        return Ba - Bu

    return sample, sample_t


# -- host reads ----------------------------------------------------------------


def _read(x, convert=None):
    """A device-to-host read, uncounted (the right driver's reads)."""
    return (convert or np.asarray)(x)


class _HostPulls:
    """The left driver's device-to-host reads, counted where they happen.

    Every read of a device value by the left driver goes through
    ``pull(x, convert)``, which returns ``convert(x)`` (``np.asarray`` by
    default; ``int``, or a function that blocks on a pytree and reads one
    leaf). It always counts the read, one integer add, and under
    telemetry wraps it in a ``chol.pull`` span, so a device trace names
    the host's wait. It adds no read of its own: a host array passes
    through uncounted. The stages take their share of ``total``: each
    panel into ``column_events[k]["syncs"]``, the diagonals into
    ``stats["diag_syncs"]``.
    """

    __slots__ = ("total",)

    def __init__(self):
        self.total = 0

    def __call__(self, x, convert=None):
        convert = convert or np.asarray
        if isinstance(x, np.ndarray):
            return convert(x)
        self.total += 1
        if not obs.enabled():
            return convert(x)
        with obs.span("chol.pull", cat="factor"):
            return convert(x)


def _ready_last(xs):
    """Block on a pytree of device arrays and read its last one."""
    jax.block_until_ready(xs)
    return np.asarray(xs[-1])


# -- diagonal machinery --------------------------------------------------------


def _diag_update_sum(Uk, Vk, dk=None):
    """sum_j L(k,j) D_j L(k,j)^T as a dense (b, b) block."""
    if dk is None:
        G = einsum("jbr,jbq->jrq", Vk, Vk)
    else:
        G = einsum("jbr,jb,jbq->jrq", Vk, dk, Vk)
    M = einsum("jbr,jrq->jbq", Uk, G)
    return einsum("jbq,jcq->bc", M, Uk)


@partial(jax.jit, static_argnames=("mode", "eps", "bs"))
def _schur_compensate(Akk, Dsum, mode: str, eps: float, bs: int, key):
    """Section 5.1.1: subtract a *compressed* update / diagonal-compensate."""
    b = Akk.shape[0]
    p = ARAParams(bs=min(bs, b), r_max=b, eps=eps)
    Q, B, rank, _ = ara_mod.ara_compress_dense(Dsum[None], key, p)
    Dbar = matmul(Q[0], B[0].T)
    Dbar = 0.5 * (Dbar + Dbar.T)
    if mode == "full":
        # A - Dbar  ==  A - D + (D - Dbar), the PSD-compensated update
        return Akk - Dbar
    # "diag": A - D + diag(rowsum |D - Dbar|)   (diagonal compensation [8])
    comp = jnp.sum(jnp.abs(Dsum - Dbar), axis=1)
    return Akk - Dsum + jnp.diag(comp)


@jax.jit
def robust_cholesky(Akk, delta):
    """Dense Cholesky with eigenvalue-clamp fallback (Algorithm 8 analogue).

    The paper repairs failing tiles with a Cheng-Higham modified Cholesky via
    LDL^T; with no pivoted LDL in JAX we use the spectral equivalent: clamp
    eigenvalues to ``delta`` (the minimal-norm symmetric E making A+E PD).
    Returns (L, modified?).
    """
    L = jnp.linalg.cholesky(Akk)
    bad = jnp.any(jnp.isnan(L))

    def fallback(_):
        w, W = jnp.linalg.eigh(Akk)
        w = jnp.maximum(w, delta)
        Amod = matmul(W * w, W.T)
        Amod = 0.5 * (Amod + Amod.T)
        return jnp.linalg.cholesky(Amod)

    Lout = jax.lax.cond(bad, fallback, lambda _: L, operand=None)
    return Lout, bad


@jax.jit
def dense_ldlt_tile(Akk):
    """Unpivoted dense LDL^T of one tile: returns unit-lower L and d (b,)."""
    b = Akk.shape[0]
    dtype = Akk.dtype
    eye = jnp.eye(b, dtype=dtype)
    ar = jnp.arange(b)

    def body(j, carry):
        L, d = carry
        w = jnp.where(ar < j, d * L[j, :], 0.0)
        c = Akk[:, j] - matmul(L, w)
        dj = c[j]
        tiny = jnp.asarray(1e-30, dtype)
        dj = jnp.where(jnp.abs(dj) < tiny, tiny, dj)
        col = jnp.where(ar > j, c / dj, 0.0)
        L = L.at[:, j].set(col + eye[:, j])
        d = d.at[j].set(dj)
        return L, d

    L0 = jnp.zeros((b, b), dtype)
    d0 = jnp.zeros((b,), dtype)
    return jax.lax.fori_loop(0, b, body, (L0, d0))


def _factor_diag_tile(Akk, opts: CholOptions, stats: dict, pull=_read):
    """Dense-factor one (fully updated) diagonal tile per the options.

    Shared by both drivers: LDL^T tile factor, or Cholesky with the
    eigenvalue-clamp fallback (``modified_chol`` accounting lands in
    ``stats``; its flag is read through ``pull``). Returns ``(Lkk, dk)``
    with ``dk`` None for Cholesky.
    """
    if opts.ldl:
        return dense_ldlt_tile(Akk)
    delta = opts.eps * jnp.maximum(jnp.max(jnp.abs(jnp.diag(Akk))), 1.0)
    if opts.modified_chol:
        Lkk, bad = robust_cholesky(Akk, delta)
        stats["modified_chol"] += pull(bad, int)
    else:
        Lkk = jnp.linalg.cholesky(Akk)
    return Lkk, None


def _jittered(Akk, shift: float):
    """``Akk + shift * scale * I`` -- the escalating-jitter remedy for an
    SPD breakdown (DESIGN.md section 13; the diagonal-shift recovery of
    Chen & Martinsson). ``scale`` is the tile's max |diag| entry (floored
    at 1) so the shift schedule is relative to the tile's magnitude."""
    b = Akk.shape[-1]
    scale = jnp.maximum(jnp.max(jnp.abs(jnp.diag(Akk))), 1.0)
    return Akk + shift * scale * jnp.eye(b, dtype=Akk.dtype)


def _spd_shift(Akk, rp, attempt: int, pull=_read) -> float:
    """Relative jitter for retry ``attempt``: enough to clear the tile's
    most negative eigenvalue (one b x b eigvalsh, failure path only), plus
    the policy's base shift, escalated by ``growth``. A non-finite tile
    gets the bare policy schedule -- no shift fixes a NaN, and the bounded
    ladder is what turns that into a structured breakdown."""
    finite = pull(jnp.all(jnp.isfinite(Akk)), bool)
    base = 0.0
    if finite:
        scale = pull(jnp.maximum(jnp.max(jnp.abs(jnp.diag(Akk))), 1.0),
                     float)
        lam = pull(jnp.min(jnp.linalg.eigvalsh(Akk)), float)
        base = max(0.0, -lam) / scale
    return (base + rp.shift(0)) * rp.growth ** attempt


def _diag_check_hook(k, st, opts, stats, health, pull=_read):
    """Check hook for a diag stage with no panel after it (the last
    column in either driver): the panel-boundary hook elsewhere owns the
    jitter ladder, so the trailing diagonal gets its own. Retries
    re-factor the stashed updated tile ``st.col[k]["Akk"]``; exhaustion
    raises with the column's full remedy history. Device values are read
    through ``pull``."""

    def check():
        c = st.col[k]
        rp = health.policy
        for attempt in range(rp.max_retries + 1):
            pivots = c["dk"] if opts.ldl else jnp.diag(c["Lkk"])
            flags = column_flags(pivots, read=pull)
            bad = flags[1] > 0 or (not opts.ldl and flags[2] <= 0.0)
            if not bad:
                break
            if attempt >= rp.max_retries:
                health.fail(k, "diag", "spd_breakdown",
                            pivot_index=int(flags[3]),
                            min_pivot=float(flags[2]),
                            nonfinite_pivots=int(flags[1]))
            shift = _spd_shift(c["Akk"], rp, attempt, pull)
            health.record("spd_breakdown", k, "diag", remedy="jitter",
                          attempt=attempt + 1, shift=shift)
            Lkk, dk_new = _factor_diag_tile(_jittered(c["Akk"], shift),
                                            opts, stats, pull)
            if opts.ldl:
                st.dvec = st.dvec.at[k].set(dk_new)
            st.LD = st.LD.at[k].set(Lkk)
            c.update(Lkk=Lkk, dk=dk_new)
        health.columns_checked += 1

    return check


def _final_gate(st, opts, health, b, pull=_read):
    """The returned-factors guarantee: one fused scan over every factor
    array and every pivot before the driver returns. Nothing that reaches
    the caller is non-finite (or non-positive, for Cholesky) -- a failure
    here is a breakdown, never a silently poisoned factorization."""
    if opts.ldl:
        pivots = st.dvec.reshape(-1)
        arrays = (st.LD, st.LU, st.LV)
    else:
        pivots = jnp.diagonal(st.LD, axis1=1, axis2=2).reshape(-1)
        arrays = (st.LU, st.LV)
    flags = column_flags(pivots, arrays, read=pull)
    if flags[0] > 0 or flags[1] > 0:
        health.fail(-1, "final", "nonfinite_factor",
                    nonfinite=int(flags[0]),
                    nonfinite_pivots=int(flags[1]))
    if not opts.ldl and flags[2] <= 0.0:
        health.fail(int(flags[3]) // b, "final", "spd_breakdown",
                    pivot_index=int(flags[3]) % b,
                    min_pivot=float(flags[2]))


# -- column processing ---------------------------------------------------------


def _build_column_data(A, Lout, rows, k, perm, dvec, ldl,
                       Tb: int | None = None, Jb: int | None = None,
                       wA: int | None = None, wL: int | None = None):
    """Operand gather for one column, zero-padded up to bucket sizes.

    Padding rows/columns are all-zero tiles: every product against them is
    zero, so they are numerically inert; ``valid`` marks the real row slots
    (used to pre-converge the padding in the ARA state).

    ``wA`` / ``wL`` (ranked batching) slice the A-tile and L-tile factor
    stacks to the rank-ladder widths covering their actual ranks -- exact,
    since factor columns past each tile's rank are zero -- so the sampling
    chains run at the bucketed width instead of ``r_max``.
    """
    T = len(rows)
    Tb = T if Tb is None else Tb
    Jb = max(1, k) if Jb is None else Jb
    li, lv = _L_index(rows, k, Tb, Jb)
    Ui, Vi, _ = _gather_tiles(Lout.U, Lout.V, None, li, lv,
                              w=wL)                          # (Tb, Jb, b, r)
    ki, kv = _L_index([k], k, 1, Jb)
    Uk, Vk, _ = _gather_tiles(Lout.U, Lout.V, None, ki[0], kv[0],
                              w=wL)                          # (Jb, b, r)
    Ua, Va, ra = _gather_tiles(A.U, A.V, A.ranks,
                               *_A_index(rows, k, perm, Tb), w=wA)
    data = {
        "Ua": Ua, "Va": Va, "ranksA": ra, "Uk": Uk, "Vk": Vk,
        "Ui": Ui, "Vi": Vi,
        "valid": jnp.arange(Tb) < T,
        "dk": _masked_rows(dvec, kv[0]) if ldl else None,
    }
    return data


def _trsm(Lkk, dk_new, B, ldl: bool):
    """V(i,k) = L(k,k)^{-1} B_i (paper: batchTrsm); LDL adds D^{-1}."""
    Vnew = jax.vmap(
        lambda Bi: jax.scipy.linalg.solve_triangular(Lkk, Bi, lower=True)
    )(B)
    if ldl:
        # L(i,k) = Q B^T (L D)^{-T}  =>  V(i,k) = D^{-1} L^{-1} B
        Vnew = Vnew / dk_new[None, :, None]
    return Vnew


_SCATTER_TRACES = 0


def _panel_scatter_body(U, V, R, idx, valid, Qn, Vw, rn):
    """Body of the donated ``Lout`` writer both pipelines share.

    One fused executable per row bucket scatters a factored panel (bases,
    scaled factors, ranks) into the output factor's packed-lower stacks.
    ``donate_argnums=(0, 1, 2)`` aliases the three stacks input->output,
    so the per-column write is in-place instead of copying the three
    widest persistent arrays of the factorization (the eager ``at[].set``
    it replaces could never alias: the caller's reference kept the old
    buffer alive). Add-scatter with a masked payload is exact: every
    packed-lower slot is written exactly once across the factorization
    (pivot swaps only permute already-written slots), so targets are
    zero, and padded slots add zero to slot 0. Sharding (when a tile
    mesh placed the stacks) survives the aliasing untouched.

    Jitted once at module scope (below) rather than per pipeline: the
    body is pure, so the compiled variants are shared by every
    factorization in the process -- per-factorization jits here would
    recompile the widest write of the driver on every call.
    """
    global _SCATTER_TRACES
    _SCATTER_TRACES += 1
    m = valid[:, None, None]
    U = U.at[idx].add(jnp.where(m, Qn, jnp.zeros_like(Qn)))
    V = V.at[idx].add(jnp.where(m, Vw, jnp.zeros_like(Vw)))
    R = R.at[idx].add(jnp.where(valid, rn, jnp.zeros_like(rn)))
    return U, V, R


_panel_scatter = jax.jit(_panel_scatter_body, donate_argnums=(0, 1, 2))


def scatter_trace_count() -> int:
    """Process-wide compile count of the shared panel scatter."""
    return _SCATTER_TRACES


# Process-wide trace counts of the left driver's column steps, one per role;
# each bumps when jax traces a fresh variant of a step (once per compiled
# executable, since the jitted bodies below run only while tracing).
_STEP_TRACES = {"column": 0, "project": 0, "diag": 0}

# Bound on the distinct static configurations whose compiled column steps
# stay cached: a factorization uses one, plus one per rank-overflow retry
# level it escalates to.
_COLUMN_STEP_CONFIGS = 32


class _ColumnSteps(NamedTuple):
    sample: Callable
    dyn_loop: Callable
    project: Callable
    diag_update: Callable


@lru_cache(maxsize=_COLUMN_STEP_CONFIGS)
def _column_steps(ldl: bool, impl: str, share: bool, p: ARAParams,
                  mesh, matmul_precision) -> _ColumnSteps:
    """The left driver's jitted column steps for one static configuration.

    Built once per ``(ldl, impl, share_omega, ARAParams, tile mesh, matmul
    precision)`` and shared by every factorization in the process, like
    ``_panel_scatter``: per-factorization jits would trace, lower and load
    every step variant again on every call. The key is everything the
    traced bodies read besides their arguments (``impl`` as
    ``ops.resolve_impl`` gives it; the tile mesh decides at trace time
    whether a kernel runs under ``shard_map``; ``precision.MATMUL_PRECISION``
    is read when a contraction is traced). The bodies capture nothing else:
    arrays, keys and per-factorization state arrive as arguments.
    """
    sample, sample_t = make_column_samplers(ldl, impl)

    def dyn_loop(data, key):
        # The column's ARA as one device loop: ``ara_iteration`` stepped
        # until every tile converged, or the column's budget of
        # ``p.iters`` steps per row tile passed (the safety valve). A tile
        # that fills its buffer is flagged in one more step, which records
        # its full-rank error for the rank-overflow check.
        # ``tile_iters[t]`` counts the steps slot ``t`` entered
        # unconverged; padding slots start converged and count none.
        _STEP_TRACES["column"] += 1
        Tb, b = data["Ua"].shape[0], data["Ua"].shape[1]
        valid = data["valid"]
        budget = p.iters * jnp.maximum(1, jnp.sum(valid))

        def cond(carry):
            state, _ = carry
            return ~jnp.all(state.converged) & (state.it <= budget)

        def body(carry):
            state, tile_iters = carry
            tile_iters = tile_iters + (~state.converged).astype(jnp.int32)
            return (ara_iteration(sample, data, state, key, p,
                                  share_omega=share, T=Tb, b=b),
                    tile_iters)

        # The state takes the dtype of L, A's working dtype (A's U and V
        # may be stored narrower).
        state0 = init_state(Tb, b, p, data["Uk"].dtype, valid=valid)
        return jax.lax.while_loop(
            cond, body, (state0, jnp.zeros((Tb,), jnp.int32)))

    def project(data, Q, Lkk, dk_new):
        _STEP_TRACES["project"] += 1
        return _trsm(Lkk, dk_new, sample_t(data, Q), ldl)

    def diag_update(Uk, Vk, dk):
        _STEP_TRACES["diag"] += 1
        return _diag_update_sum(Uk, Vk, dk)

    return _ColumnSteps(sample, jax.jit(dyn_loop), jax.jit(project),
                        jax.jit(diag_update))


class _ColumnPipeline:
    """Per-factorization handle on the shape-stable jitted column steps.

    One jitted callable per role (the ARA loop, projection, diagonal
    update), taken from the process-wide ``_column_steps`` cache; jax's
    shape-keyed jit cache plus the bucket ladder keeps the number of
    compiled variants at ~log2(nb), and a later factorization with the
    same statics traces and lowers none of them again. The handle keeps
    the options, the ARA parameters and the trace-count baselines, so
    ``traces`` and ``scatter_traces`` report the fresh traces made since
    it was built (a rank-overflow retry's included): ~log2(nb) in the
    first factorization of a configuration, 0 in a warm one.
    """

    def __init__(self, opts: CholOptions, p: ARAParams):
        self.opts = opts
        self.p = p
        (self.sample, self.dyn_loop, self.project,
         self.diag_update) = _column_steps(
            opts.ldl, ops.resolve_impl(opts.impl), opts.share_omega, p,
            tile_mesh(), precision.MATMUL_PRECISION)
        self.scatter = _panel_scatter
        self._traces_t0 = dict(_STEP_TRACES)
        self._column_t0 = _STEP_TRACES["column"]
        self._scatter_t0 = _SCATTER_TRACES

    @property
    def traces(self) -> dict[str, int]:
        """Fresh traces of each step role since this pipeline was built."""
        return {kind: n - self._traces_t0[kind]
                for kind, n in _STEP_TRACES.items()}

    def begin_column(self) -> None:
        self._column_t0 = _STEP_TRACES["column"]

    @property
    def scatter_traces(self) -> int:
        """Fresh compiles of the shared scatter during this factorization
        (0 in the steady state -- the executable cache is process-wide)."""
        return _SCATTER_TRACES - self._scatter_t0

    @property
    def column_traced(self) -> bool:
        """Did the current column trigger a fresh trace of the ARA step?"""
        return _STEP_TRACES["column"] > self._column_t0


def _column_ara(pipe: _ColumnPipeline, A, Lout, rows, k, perm, dvec,
                Lkk, dk_new, key, ladder, widths, pull):
    """Column ``k``'s ARA over ``rows``, projected into the panel.

    The operands are built once at the column's row bucket, the ARA runs
    as one device loop (``dyn_loop``) and its outcome comes back in one
    read; the projection then runs at the rank-ladder width covering the
    detected ranks (ranked batching) or at ``r_max`` (flat).

    Returns the bases, the projected panel and the ranks, zero-padded to
    the row bucket, and ``info`` for the ``T`` real rows: ``iters``,
    ``err``, ``safety_valve``, the bucket pair, ``wQ``, and the batch's
    occupancy: ``tile_iters[t]``, the steps row ``t`` entered
    unconverged, and ``slots``, the slots dispatched over the column's
    steps."""
    opts, p = pipe.opts, pipe.p
    T = len(rows)
    Tb, Jb = _column_buckets(A.nb, k, ladder)
    wA, wL = widths
    data = _build_column_data(A, Lout, rows, k, perm, dvec, opts.ldl,
                              Tb=Tb, Jb=Jb, wA=wA, wL=wL)
    state, tile_iters = pipe.dyn_loop(data, key)
    rank, err, conv, it, tile_iters = pull(
        (state.rank, state.err, state.converged, state.it, tile_iters),
        jax.device_get)
    iters, valve = int(it), not conv.all()
    if valve:
        warnings.warn(
            f"TLR column {k}: ARA safety valve tripped after {iters} "
            f"iterations; {int(np.sum(~conv))} tile(s) kept their partial "
            f"bases -- the factorization is degraded (raise max_iters/r_max "
            f"or loosen eps; see stats['safety_valve'])", RuntimeWarning,
            stacklevel=4)
    with obs.span("chol.project", cat="factor", k=k):
        if opts.batching == "ranked":
            # Project at the rank-ladder width covering the detected ranks
            # (exact: columns of Q past each tile's rank are zero).
            wq = bucket_width(rank[:T], p.r_max)
            Vnew = pipe.project(data, state.Q[:, :, :wq], Lkk, dk_new)
            Vnew = _pad_axis(Vnew, p.r_max, axis=2)
        else:
            wq = None
            Vnew = pipe.project(data, state.Q, Lkk, dk_new)
    info = {"iters": iters, "err": np.asarray(err[:T], float),
            "tile_iters": tile_iters[:T].astype(np.int64),
            "slots": Tb * iters, "safety_valve": valve,
            "T": T, "Tb": Tb, "Jb": Jb, "wQ": wq}
    return state.Q, Vnew, state.rank, info


# -- main drivers ---------------------------------------------------------------


def _dispatch(A: TLRMatrix, opts: CholOptions) -> TLRFactorization:
    if opts.algo == "right":
        driver = _factorize_right
    elif opts.algo == "left":
        driver = _factorize
    else:
        raise ValueError(f"algo must be 'left' or 'right', got {opts.algo!r}")
    if not obs.enabled():
        return driver(A, opts)
    # Telemetry: one root span per factorization; its subtree becomes the
    # ``stats["telemetry"]`` metrics snapshot (per-phase seconds and FLOPs,
    # padded-vs-useful ratios, the JIT work of the subtree), with the
    # plan-level analytic ratio from ``stats["policy"]`` copied alongside
    # for parity checks, and the compile-count registry folded in as a
    # counter sample.
    mesh = tile_mesh()
    sched = "lookahead" if (opts.lookahead and opts.algo == "right") \
        else "sequential"
    with obs.span("chol.factorize", cat="factor", algo=opts.algo,
                  nb=A.nb, b=A.b, schedule=sched,
                  devices=(mesh.devices.size if mesh is not None else 1),
                  mesh=(str(dict(mesh.shape)) if mesh is not None else "")
                  ) as root:
        fact = driver(A, opts)
    obs.record_retraces()
    snap = obs.metrics_snapshot(root=root)
    snap["padded_flop_ratio_plan"] = fact.stats["policy"]["padded_flop_ratio"]
    fact.stats["telemetry"] = snap
    return fact


def tlr_cholesky(A: TLRMatrix, opts: CholOptions) -> TLRFactorization:
    """TLR Cholesky: left-looking (Algorithm 6; Algorithm 9 when pivoting)
    or right-looking on the tile algebra, per ``opts.algo``."""
    return _dispatch(A, dataclasses.replace(opts, ldl=False))


def tlr_ldlt(A: TLRMatrix, opts: CholOptions) -> TLRFactorization:
    """TLR LDL^T (Algorithm 10; right-looking variant per ``opts.algo``).
    Pivoting unsupported (paper 5.3)."""
    if opts.pivot is not None:
        raise ValueError("inter-tile pivoting is not defined for LDL^T (section 5.3)")
    return _dispatch(A, dataclasses.replace(opts, ldl=True, schur=None))


def _factorize(A: TLRMatrix, opts: CholOptions) -> TLRFactorization:
    nb, b = A.nb, A.b
    r_out = opts.r_max_out or A.r_max
    p = opts.ara_params(r_out)
    impl = ops.resolve_impl(opts.impl)  # validate the knob up front
    pull = _HostPulls()
    # A's ranks on the host, read once: the batching plan and the A-tile
    # gather width.
    a_ranks = pull(A.ranks)
    policy = resolve_policy(opts.batching,
                            tile_plan(A.ranks, A.r_max,
                                      read=lambda _: a_ranks),
                            b=b, dtype=A.dtype,
                            right_flush=opts.right_flush)
    batching = policy["batching"]
    key = jax.random.PRNGKey(opts.seed)

    Lout = zeros_like_structure(nb, b, r_out, A.dtype)
    dvec = jnp.zeros((nb, b), A.dtype) if opts.ldl else None
    perm = np.arange(nb)
    ladder = _bucket_ladder(nb - 1)
    jd = max(1, nb - 1)  # static pad width for the diagonal-update gather
    pipe = _ColumnPipeline(opts, p)
    # Ranked batching: the A-tile gather width is fixed by A's ranks; the
    # L-tile gather width follows the running max of the written factor
    # ranks (monotone up the ladder, so it changes at most ~log2(r_max)
    # times over the whole factorization -- the compile count stays
    # O(log nb + log r_max) instead of multiplying).
    wA = bucket_width(a_ranks, A.r_max) if batching == "ranked" else None
    wL = 1 if batching == "ranked" else None
    stats = {
        "column_iters": [], "column_ranks": [], "modified_chol": 0,
        "pivots": [], "impl": impl, "algo": "left",
        "bucket_ladder": list(ladder), "column_events": [],
        "column_traces": 0, "project_traces": 0, "diag_traces": 0,
        "safety_valve": False, "batching": batching, "policy": policy,
        "syncs": 0, "diag_syncs": 0,
    }
    health = HealthMonitor(opts.retry, "left", nb) if opts.check else None
    # Rank-overflow remedies re-run the failing rows' ARA pass at a
    # loosened eps. ARAParams.eps is static in the traced step, so each
    # escalation level gets its own (cached, rarely built) pipeline; the
    # re-pass runs the column's ARA (flat) over just the overflowing rows.
    retry_pipes: dict[int, _ColumnPipeline] = {}

    def _retry_pipe(attempt: int) -> _ColumnPipeline:
        if attempt not in retry_pipes:
            o2 = dataclasses.replace(
                opts, eps=opts.retry.eps_at(opts.eps, attempt),
                batching="flat", check=False)
            retry_pipes[attempt] = _ColumnPipeline(o2, o2.ara_params(r_out))
        return retry_pipes[attempt]

    # Mutable factorization state the stage closures share. The left
    # driver's column graph is a serial chain -- diag(k) and panel(k) both
    # gather every previously written L column -- so only the sequential
    # schedule is legal (``opts.lookahead`` is recorded but has nothing to
    # overlap here; the right-looking driver is the lookahead target).
    st = types.SimpleNamespace(
        LD=Lout.D, LU=Lout.U, LV=Lout.V, LR=Lout.ranks, dvec=dvec,
        perm=perm, wL=wL, col=[{} for _ in range(nb)],
        # Pivoted mode keeps running diagonal-update sums (section 5.2).
        Dsum_all=jnp.zeros((nb, b, b), A.dtype) if opts.pivot else None,
    )
    if tile_mesh() is not None:
        st.LU, st.LV, st.LR = shard_tile_batch(st.LU, st.LV, st.LR,
                                               preserve_shape=True)

    def _Lmat() -> TLRMatrix:
        return TLRMatrix(D=st.LD, U=st.LU, V=st.LV, ranks=st.LR)

    def _diag_stage(k: int):
        kkey = jax.random.fold_in(key, k)

        def fn():
            n0 = pull.total
            # ---- pivot selection & swap (Algorithm 9 lines 11-14) ----------
            if opts.pivot:
                diag_orig = jnp.take(A.D, jnp.asarray(st.perm[k:], np.int32),
                                     axis=0)
                cand = diag_orig - st.Dsum_all[k:]
                if opts.pivot == "frobenius":
                    norms = jnp.sqrt(jnp.sum(cand * cand, axis=(1, 2)))
                elif opts.pivot == "power":
                    norms = _power_norms(cand, iters=10, key=kkey)
                else:
                    raise ValueError(opts.pivot)
                pidx = k + pull(jnp.argmax(norms), int)
                stats["pivots"].append(pidx)
                if pidx != k:
                    st.perm[[k, pidx]] = st.perm[[pidx, k]]
                    st.Dsum_all = _swap_rows(st.Dsum_all, k, pidx)
                    L = _swap_L_rows(_Lmat(), k, pidx)
                    st.LU, st.LV, st.LR = L.U, L.V, L.ranks

            # ---- diagonal tile: update, compensate, factor -----------------
            with obs.span("chol.diag", cat="factor", k=k):
                Akk = A.D[st.perm[k]]
                if k > 0:
                    ki, kv = _L_index([k], k, 1, jd)
                    Uk, Vk, _ = _gather_tiles(st.LU, st.LV, None, ki[0],
                                              kv[0], w=st.wL)
                    dk = _masked_rows(st.dvec, kv[0]) if opts.ldl else None
                    Dsum = pipe.diag_update(Uk, Vk, dk)
                    if opts.schur and not opts.ldl:
                        Akk = _schur_compensate(Akk, Dsum, opts.schur,
                                                opts.eps, opts.bs, kkey)
                    else:
                        Akk = Akk - Dsum
                if faults.active():
                    Akk = faults.corrupt_diag(Akk, k)
                mc0 = stats["modified_chol"]
                Lkk, dk_new = _factor_diag_tile(Akk, opts, stats, pull)
                if opts.ldl:
                    st.dvec = st.dvec.at[k].set(dk_new)
                st.LD = st.LD.at[k].set(Lkk)
                st.col[k].update(Lkk=Lkk, dk=dk_new)
                if health is not None:
                    # Keep the updated (unfactored) tile for jitter retries;
                    # an eigenvalue-clamp repair is itself a health event.
                    st.col[k]["Akk"] = Akk
                    if stats["modified_chol"] > mc0:
                        health.record("spd_breakdown", k, "diag",
                                      remedy="clamp")
            stats["diag_syncs"] += pull.total - n0

        return fn

    def _densify_rows(rows_bad, k, Lkk, dk_new):
        """Last-resort rank-overflow remedy: exact tile expressions via an
        identity probe through the sampling chain, then the *optimal*
        rank-``r_out`` truncation (batched SVD). Factor columns past each
        tile's detected rank are zeroed (the storage invariant)."""
        Tb, Jb = _column_buckets(A.nb, k, ladder)
        Tb = _bucket_up(len(rows_bad), ladder)
        data = _build_column_data(A, _Lmat(), rows_bad, k, st.perm, st.dvec,
                                  opts.ldl, Tb=Tb, Jb=Jb, wA=wA, wL=st.wL)
        E = pipe.sample(data, jnp.eye(b, dtype=A.dtype))[:len(rows_bad)]
        Us, S, Vt = jnp.linalg.svd(E, full_matrices=False)
        keep = min(r_out, b)
        Qd = Us[:, :, :keep]
        Bd = jnp.swapaxes(Vt[:, :keep, :], 1, 2) * S[:, None, :keep]
        tol = S[:, :1] * np.finfo(np.dtype(A.dtype)).eps * b
        rd = jnp.minimum(jnp.sum(S > tol, axis=1), keep).astype(jnp.int32)
        mask = (jnp.arange(keep)[None, None, :] < rd[:, None, None])
        Qd = jnp.where(mask, Qd, 0.0)
        Bd = jnp.where(mask, Bd, 0.0)
        Vd = _trsm(Lkk, dk_new, Bd, opts.ldl)
        ed = pull(S[:, keep]).astype(float) if keep < b \
            else np.zeros(len(rows_bad))
        return (_pad_axis(Qd, r_out, axis=2), _pad_axis(Vd, r_out, axis=2),
                rd, ed)

    def _repair_column(k, rows, compute, kkey, Q, Vnew, ranks, ranks_h,
                       info):
        """The panel-boundary decision tree (DESIGN.md section 13): jitter
        escalation on SPD breakdown, hard failure on non-finite panel
        output, eps-loosen + densify on rank overflow."""
        rp = health.policy
        c = st.col[k]
        Tbs = _bucket_up(len(rows), ladder)
        # -- SPD breakdown: escalate diagonal jitter, redo diag + panel --
        for attempt in range(rp.max_retries + 1):
            pivots = c["dk"] if opts.ldl else jnp.diag(c["Lkk"])
            # Bucket-pad the scanned panel (padding is zero => finite and
            # inert) so the flags reduction compiles on the ladder.
            flags = column_flags(pivots, (_pad_axis(Q, Tbs),
                                          _pad_axis(Vnew, Tbs)), read=pull)
            bad_piv = flags[1] > 0 or (not opts.ldl and flags[2] <= 0.0)
            if not bad_piv:
                break
            if attempt >= rp.max_retries:
                health.fail(k, "panel", "spd_breakdown",
                            pivot_index=int(flags[3]),
                            min_pivot=float(flags[2]),
                            nonfinite_pivots=int(flags[1]))
            shift = _spd_shift(c["Akk"], rp, attempt, pull)
            health.record("spd_breakdown", k, "panel", remedy="jitter",
                          attempt=attempt + 1, shift=shift)
            Lkk, dk_new = _factor_diag_tile(_jittered(c["Akk"], shift),
                                            opts, stats, pull)
            if opts.ldl:
                st.dvec = st.dvec.at[k].set(dk_new)
            st.LD = st.LD.at[k].set(Lkk)
            c.update(Lkk=Lkk, dk=dk_new)
            Q, Vnew, ranks, ranks_h, info = compute()
        # -- non-finite panel output with healthy pivots: unrecoverable --
        if flags[0] > 0:
            health.fail(k, "panel", "nonfinite_panel",
                        nonfinite=int(flags[0]))
        # -- rank overflow: eps-loosened re-pass, then densify -----------
        err_h = np.asarray(info["err"], float).copy()
        over = ara_mod.rank_overflow(ranks_h, err_h, p)
        for attempt in range(1, rp.max_retries + 1):
            if not over.any():
                break
            eps_a = rp.eps_at(opts.eps, attempt)
            pos = np.nonzero(over)[0]
            health.record("rank_overflow", k, "panel",
                          remedy="eps_loosen", attempt=attempt,
                          rows=[int(rows[i]) for i in pos], eps=eps_a)
            Qb, Vb, rb, ib = _column_ara(
                _retry_pipe(attempt), A, _Lmat(), rows[pos], k, st.perm,
                st.dvec, c["Lkk"], c["dk"],
                jax.random.fold_in(kkey, 7000 + attempt), ladder,
                widths=(wA, st.wL), pull=pull)
            posj, n = jnp.asarray(pos), len(pos)
            Q = Q.at[posj].set(Qb[:n])
            Vnew = Vnew.at[posj].set(Vb[:n])
            ranks = ranks.at[posj].set(rb[:n])
            ranks_h = pull(ranks)[:len(rows)]
            err_h[pos] = np.asarray(ib["err"], float)
            over[:] = False
            over[pos] = ara_mod.rank_overflow(
                ranks_h[pos], err_h[pos],
                dataclasses.replace(p, eps=eps_a))
        if over.any() and rp.densify:
            pos = np.nonzero(over)[0]
            health.record("rank_overflow", k, "panel", remedy="densify",
                          rows=[int(rows[i]) for i in pos])
            Qd, Vd, rd, ed = _densify_rows(rows[pos], k, c["Lkk"], c["dk"])
            posj = jnp.asarray(pos)
            Q = Q.at[posj].set(Qd)
            Vnew = Vnew.at[posj].set(Vd)
            ranks = ranks.at[posj].set(rd)
            ranks_h = pull(ranks)[:len(rows)]
            err_h[pos] = ed
            over[:] = False
            over[pos] = ~(ed <= rp.eps_floor(opts.eps))
        if over.any():
            pos = np.nonzero(over)[0]
            health.fail(k, "panel", "rank_overflow",
                        rows=[int(rows[i]) for i in pos],
                        err=[float(err_h[i]) for i in pos],
                        eps_floor=rp.eps_floor(opts.eps))
        info = dict(info)
        info["err"] = err_h
        return Q, Vnew, ranks, ranks_h, info

    def _panel_stage(k: int):
        kkey = jax.random.fold_in(key, k)
        rows = np.arange(k + 1, nb)
        T = len(rows)
        Tbs = _bucket_up(T, ladder)

        def compute():
            Lkk, dk_new = st.col[k]["Lkk"], st.col[k]["dk"]
            pipe.begin_column()
            with obs.span("chol.panel", cat="factor", k=k) as _psp:
                Q, Vnew, ranks, info = _column_ara(
                    pipe, A, _Lmat(), rows, k, st.perm, st.dvec, Lkk, dk_new,
                    kkey, ladder, widths=(wA, st.wL), pull=pull)
                if faults.active():
                    Q = faults.corrupt_panel(Q[:T], k)
                # one pull: wait for the panel, read its (padded) ranks
                ranks_h = pull((Q, Vnew, ranks), _ready_last)[:T]
                if obs.enabled():
                    _psp.set(T=info["T"], Tb=info["Tb"], Jb=info["Jb"],
                             iters=info["iters"],
                             rank_hist=obs.rank_hist(ranks_h, r_out))
            return Q, Vnew, ranks, ranks_h, info

        def commit(Q, Vnew, ranks, ranks_h, info, t0, n0):
            dt = time.perf_counter() - t0
            if batching == "ranked":
                st.wL = max(st.wL, bucket_width(ranks_h, r_out))
            stats["column_iters"].append(info["iters"])
            stats["column_ranks"].append(ranks_h)
            stats["safety_valve"] |= info["safety_valve"]
            stats["column_events"].append({
                "k": k, "T": info["T"], "Tb": info["Tb"], "Jb": info["Jb"],
                "seconds": dt, "traced": pipe.column_traced,
                "err": np.asarray(info["err"]), "wQ": info["wQ"],
                "syncs": pull.total - n0,
                "tile_iters": info["tile_iters"], "slots": info["slots"],
            })

            with obs.span("chol.commit", cat="factor", k=k):
                idxp = np.zeros(Tbs, np.int64)
                idxp[:T] = [tril_index(int(i), k) for i in rows]
                st.LU, st.LV, st.LR = pipe.scatter(
                    st.LU, st.LV, st.LR, jnp.asarray(idxp, jnp.int32),
                    jnp.asarray(np.arange(Tbs) < T), _pad_axis(Q, Tbs),
                    _pad_axis(Vnew, Tbs), _pad_axis(ranks, Tbs))
                if opts.pivot:
                    # Dsum_all[i] += L(i,k) L(i,k)^T for the remaining rows.
                    G = einsum("tbr,tbq->trq", Vnew[:T], Vnew[:T])
                    upd = einsum("tbr,trq,tcq->tbc", Q[:T], G, Q[:T])
                    st.Dsum_all = st.Dsum_all.at[k + 1 :].add(upd)

        def fn():
            t0, n0 = time.perf_counter(), pull.total
            out = compute()
            if health is None:
                commit(*out, t0, n0)
            else:
                # Defer the commit to the stage's check hook: the scatter
                # is a donated *add*, so it must happen exactly once --
                # after validation has settled the panel's final content.
                st.col[k]["pending"] = (out, t0, n0)

        def check():
            out, t0, n0 = st.col[k].pop("pending")
            out = _repair_column(k, rows, compute, kkey, *out)
            commit(*out, t0, n0)
            health.columns_checked += 1

        return fn, (check if health is not None else None)

    stages = []
    for k in range(nb):
        # The last column has no panel stage, so its pivots get their own
        # boundary check; every other diag is validated by the following
        # panel's hook (which owns the jitter + recompute ladder).
        dcheck = _diag_check_hook(k, st, opts, stats, health, pull) \
            if health is not None and k + 1 >= nb else None
        stages.append(Stage(
            name=f"diag:{k}", kind="diag", k=k, fn=_diag_stage(k),
            check=dcheck,
            reads=(("L", k - 1),) if k else (), writes=(("Lkk", k),),
            seq=len(stages)))
        if k + 1 < nb:
            pfn, pcheck = _panel_stage(k)
            stages.append(Stage(
                name=f"panel:{k}", kind="panel", k=k, fn=pfn, check=pcheck,
                reads=(("L", k - 1), ("Lkk", k)), writes=(("L", k),),
                seq=len(stages)))
    sched = run_graph(stages, SequentialSchedule())
    sched["requested_lookahead"] = bool(opts.lookahead)
    stats["schedule"] = sched
    stats["column_traces"] = pipe.traces["column"]
    stats["project_traces"] = pipe.traces["project"]
    stats["diag_traces"] = pipe.traces["diag"]
    stats["scatter_traces"] = pipe.scatter_traces
    if health is not None:
        _final_gate(st, opts, health, b, pull)
        stats["health"] = health.summary()
    stats["syncs"] = pull.total
    return TLRFactorization(L=_Lmat(), d=st.dvec, perm=st.perm, stats=stats)


# -- right-looking driver (DESIGN.md section 7) --------------------------------


@partial(jax.jit, static_argnames=("rows", "width"))
def _widen(U, V, *, rows: int, width: int):
    """``U``, ``V`` zero-padded to ``rows`` tiles of ``width`` columns, in
    one program: the accumulation buffers are the widest arrays of the
    right driver, and building them as zeros plus an update would hold a
    second copy of each."""
    def pad(x):
        return jnp.pad(x, ((0, rows - x.shape[0]), (0, 0),
                           (0, width - x.shape[2])))

    return pad(U), pad(V)


class _RightPipeline:
    """Per-factorization cache of the jitted right-looking panel step.

    The panel step (densify the accumulated column, one rounding pass,
    batched TRSM) is the only driver-owned executable; the trailing update
    and the flush rounding live in ``core/algebra.py`` behind their own
    trace counter (``algebra_trace_count``). Bucket padding keeps both at
    ~log2(nb) compiled variants, mirroring the left driver's contract.
    """

    def __init__(self, opts: CholOptions, r_p: int, impl: str | None):
        self.traces = {"column": 0}
        self._column_traced = False
        self._scatter_t0 = _SCATTER_TRACES
        ldl = opts.ldl

        def panel_step(aU, aV, Lkk, dk_new, eps):
            self._mark()
            # One rounding pass over the accumulated panel; ``err`` is the
            # per-tile norm of the discarded singular values -- the
            # right-looking analogue of the ARA error estimate the left
            # driver reports per column, for free from the truncation.
            Q, B, ranks, err = tlr_round_tiles(aU, aV, eps, r_out=r_p,
                                               impl=impl)
            return Q, _trsm(Lkk, dk_new, B, ldl), ranks, err

        def trsm_step(B, Lkk, dk_new):
            # Ranked batching: the panel rounding runs through the rank
            # buckets of core/batching.py (its compiles are counted by
            # batching_trace_count), so only the TRSM remains driver-owned.
            self._mark()
            return _trsm(Lkk, dk_new, B, ldl)

        self.panel_step = jax.jit(panel_step)
        self.trsm = jax.jit(trsm_step)
        self.scatter = _panel_scatter

    def _mark(self, kind: str = "column") -> None:
        self.traces[kind] += 1
        if kind == "column":
            self._column_traced = True

    def begin_column(self) -> None:
        self._column_traced = False

    @property
    def scatter_traces(self) -> int:
        """Fresh compiles of the shared scatter during this factorization
        (0 in the steady state -- the executable cache is process-wide)."""
        return _SCATTER_TRACES - self._scatter_t0

    @property
    def column_traced(self) -> bool:
        return self._column_traced


def _factorize_right(A: TLRMatrix, opts: CholOptions) -> TLRFactorization:
    """Right-looking TLR Cholesky / LDL^T on the batched tile algebra.

    Per column: factor the (already fully-updated) dense diagonal tile,
    round + TRSM the materialized column panel, then eagerly push the
    column's rank-r_k Schur update onto the trailing matrix through
    ``tlr_syrk_column``. Trailing tiles carry growing concatenated factors;
    every ``opts.right_flush`` columns a full rounding pass
    (``tlr_round_tiles``) compacts them. No sampling chain, no ARA --
    ``bs`` / ``share_omega`` / ``schur`` are left-looking
    knobs and are ignored here.
    """
    if opts.pivot is not None:
        raise ValueError(
            "inter-tile pivoting (Algorithm 9) needs the left-looking "
            "driver's running diagonal-update sums and is not supported "
            f"with algo='right'; use algo='left' (got pivot={opts.pivot!r})")
    nb, b = A.nb, A.b
    nt = num_tiles(nb)
    r_p = opts.r_max_out or A.r_max
    impl = opts.impl  # None: each op resolves its own default
    policy = resolve_policy(opts.batching, tile_plan(A.ranks, A.r_max),
                            b=b, dtype=A.dtype,
                            right_flush=opts.right_flush)
    batching = policy["batching"]
    ranked = batching == "ranked"
    dtype = A.dtype
    flush_cols = policy["right_flush"]
    w_acc = max(b, A.r_max) + flush_cols * r_p
    # Ranked batching flushes when a trailing tile's content would pass
    # ``flush_cols`` appends beyond the ranks a rounded tile normally has
    # (r_max), not beyond the buffer, which keeps room for a rounded rank
    # up to b: the rounding cores then stay at most (1 + flush_cols) * r_p
    # wide, instead of b + r_p wide (an n = b SVD per tile) when r_p < b.
    w_flush = min(w_acc, max(A.r_max, r_p) + flush_cols * r_p)

    # Accumulation buffers: every off-diagonal tile's running low-rank
    # concatenation, seeded with A's factors. Flat batching tracks one
    # uniform first-free column ``used`` (every tile (i, j) with j > k
    # receives exactly one rank-r_p append per factored column); ranked
    # batching tracks a per-tile content width ``tile_w`` instead -- each
    # trailing tile's concatenation stays compact (appends land at its own
    # width, at the *bucketed panel rank* wk <= r_p), so the accumulation
    # window fills ~r_max/wk times slower and the rounding passes run at
    # each tile's rank-bucket width (core/batching.py). The tile-batch
    # axis is sized to the mesh's sharding quantum (``pad_tile_batch``):
    # trailing pad tiles are zero with width 0 and no gather ever indexes
    # them, so every sharded dispatch divides the data axes exactly.
    mesh = tile_mesh()
    lookahead = bool(opts.lookahead) and nb > 1
    nt_p = pad_tile_batch(nt)
    accU, accV = _widen(A.U, A.V, rows=nt_p, width=w_acc)
    if ranked:
        tile_w = np.zeros(nt_p, np.int64)
        tile_w[:nt] = np.asarray(A.ranks, np.int64)
    else:
        tile_w = None
    pairs_np = tril_pairs(nb)
    Lout = zeros_like_structure(nb, b, r_p, dtype)
    ladder = _bucket_ladder(nb - 1)
    pipe = _RightPipeline(opts, r_p, impl)
    alg0 = algebra_trace_count()
    stats = {
        "column_iters": [], "column_ranks": [], "modified_chol": 0,
        "pivots": [], "impl": ops.resolve_impl(impl),
        "algo": "right",
        "bucket_ladder": list(ladder), "column_events": [],
        "column_traces": 0, "project_traces": 0, "diag_traces": 0,
        "safety_valve": False, "flushes": 0, "acc_width": w_acc,
        "batching": batching, "policy": policy, "append_widths": [],
        # host reads: left driver only
        "syncs": None, "diag_syncs": None,
    }
    eps = jnp.asarray(opts.eps, dtype)
    health = HealthMonitor(opts.retry, "right", nb) if opts.check else None

    # Mutable factorization state shared by the stage closures. ``D`` is
    # copied up front: the trailing update donates it (zero-copy diagonal
    # subtraction), and donating ``A.D`` itself would invalidate the
    # caller's operator.
    st = types.SimpleNamespace(
        accU=accU, accV=accV, used=A.r_max, tile_w=tile_w, D=jnp.array(A.D),
        LD=Lout.D, LU=Lout.U, LV=Lout.V, LR=Lout.ranks,
        dvec=jnp.zeros((nb, b), dtype) if opts.ldl else None,
        col=[{} for _ in range(nb)],
    )
    if mesh is not None:
        st.accU, st.accV = shard_tile_batch(st.accU, st.accV)
        st.D, st.LU, st.LV, st.LR = shard_tile_batch(
            st.D, st.LU, st.LV, st.LR, preserve_shape=True)

    def _diag_stage(k: int):
        # ---- diagonal tile: fully updated by the eager trailing updates ----
        def fn():
            with obs.span("chol.diag", cat="factor", k=k):
                Dk = st.D[k]
                if faults.active():
                    Dk = faults.corrupt_diag(Dk, k)
                mc0 = stats["modified_chol"]
                Lkk, dk_new = _factor_diag_tile(Dk, opts, stats)
                if opts.ldl:
                    st.dvec = st.dvec.at[k].set(dk_new)
                st.LD = st.LD.at[k].set(Lkk)
                st.col[k].update(Lkk=Lkk, dk=dk_new)
                if health is not None:
                    # Keep the updated (unfactored) tile for jitter retries;
                    # an eigenvalue-clamp repair is itself a health event.
                    st.col[k]["Akk"] = Dk
                    if stats["modified_chol"] > mc0:
                        health.record("spd_breakdown", k, "diag",
                                      remedy="clamp")

        return fn

    def _panel_stage(k: int):
        # ---- column panel: one rounding pass + batched TRSM ----------------
        rows = np.arange(k + 1, nb)
        T = len(rows)
        Tb = _bucket_up(T, ladder)
        tidx_np = np.asarray([tril_index(int(i), k) for i in rows], np.int64)
        # the panel's tiles, padded to the row bucket (zero tiles past T)
        pidx = np.zeros(Tb, np.int32)
        pidx[:T] = tidx_np
        pvalid = np.arange(Tb) < T
        c = st.col[k]

        def compute():
            Lkk, dk_new = c["Lkk"], c["dk"]
            with obs.span("chol.panel", cat="factor", k=k, T=T,
                          Tb=Tb) as _psp:
                aU, aV, _ = _gather_tiles(st.accU, st.accV, None, pidx,
                                          pvalid)
                if ranked:
                    # Rank-bucketed panel recompression: each panel tile
                    # rounds at the ladder width covering its tracked
                    # content width (the pad tiles sit at width 0), then
                    # one jitted TRSM (bucket-padded row batch) scales the
                    # bases.
                    Q, B, ranks, err = bucketed_round_tiles(
                        aU, aV, np.where(pvalid, st.tile_w[pidx], 0), eps,
                        r_out=r_p, impl=impl)
                    Vn = pipe.trsm(B, Lkk, dk_new)
                else:
                    Q, Vn, ranks, err = pipe.panel_step(aU, aV, Lkk,
                                                        dk_new, eps)
                # the panel stays padded to the row bucket, zero past T
                Qs, Vns = _masked_rows(Q, pvalid), _masked_rows(Vn, pvalid)
                ranks = jnp.where(pvalid, ranks, 0)
                if faults.active():
                    Qs = faults.corrupt_panel(Qs, k)
                ranks_h = np.asarray(ranks)[:T]
                if obs.enabled():
                    _psp.set(rank_hist=obs.rank_hist(ranks_h, r_p))
            return Qs, Vns, ranks, ranks_h, err

        def commit(Qs, Vns, ranks, ranks_h, err):
            # Donated scatter of the factored panel into Lout's stacks
            # (in-place on the three persistent output arrays; sharding
            # survives the aliasing).
            st.LU, st.LV, st.LR = pipe.scatter(
                st.LU, st.LV, st.LR, jnp.asarray(pidx), jnp.asarray(pvalid),
                Qs, Vns, ranks)
            if ranked:
                # A rank-0 panel column contributes an exactly-zero Schur
                # update, so the trailing update skips it outright -- no
                # append, no content growth, no eventual flush over
                # unchanged buffers (the rank-floor semantics of the zero
                # bucket, extended to the trailing update).
                wk = bucket_width(ranks_h, r_p) \
                    if int(ranks_h.max(initial=0)) else 0
            else:
                wk = r_p
            c.update(Qs=Qs, Vns=Vns, ranks=ranks, ranks_h=ranks_h, err=err,
                     wk=wk, T=T, Tb=Tb, panel_traced=pipe.column_traced)

        def repair(Qs, Vns, ranks, ranks_h, err):
            rp = health.policy
            # -- SPD breakdown: jitter the stashed diagonal, redo the
            # panel (safe: the panel gathers from the acc buffers and no
            # update stage has donated them yet -- the check hook runs
            # before update_tail(k-1) under lookahead).
            for attempt in range(rp.max_retries + 1):
                pivots = c["dk"] if opts.ldl else jnp.diag(c["Lkk"])
                flags = column_flags(
                    pivots, (_pad_axis(Qs, Tb), _pad_axis(Vns, Tb)),
                    ranks=_pad_axis(ranks[:T], Tb),
                    err=_pad_axis(err[:T], Tb), r_cap=r_p, eps=opts.eps)
                bad_piv = flags[1] > 0 or (not opts.ldl
                                           and flags[2] <= 0.0)
                if not bad_piv:
                    break
                if attempt >= rp.max_retries:
                    health.fail(k, "panel", "spd_breakdown",
                                pivot_index=int(flags[3]),
                                min_pivot=float(flags[2]),
                                nonfinite_pivots=int(flags[1]))
                shift = _spd_shift(c["Akk"], rp, attempt)
                health.record("spd_breakdown", k, "panel", remedy="jitter",
                              attempt=attempt + 1, shift=shift)
                Lkk, dk_new = _factor_diag_tile(
                    _jittered(c["Akk"], shift), opts, stats)
                if opts.ldl:
                    st.dvec = st.dvec.at[k].set(dk_new)
                st.LD = st.LD.at[k].set(Lkk)
                c.update(Lkk=Lkk, dk=dk_new)
                Qs, Vns, ranks, ranks_h, err = compute()
            if flags[0] > 0:
                health.fail(k, "panel", "nonfinite_panel",
                            nonfinite=int(flags[0]))
            if flags[4] > 0:
                # Rank overflow. Unlike the left driver there is no
                # looser re-pass worth making: the rounding pass *is* the
                # optimal rank-r_p truncation of the accumulated column
                # (batched SVD), so a tile over the cap is accepted at
                # its achieved error if that error clears the policy's
                # eps floor, and is a breakdown otherwise.
                err_h = np.asarray(err[:T], float)
                pa = ARAParams(r_max=r_p, eps=opts.eps)
                over = ara_mod.rank_overflow(ranks_h, err_h, pa)
                pos = np.nonzero(over)[0]
                floor = rp.eps_floor(opts.eps)
                health.record("rank_overflow", k, "panel", remedy="accept",
                              rows=[int(rows[i]) for i in pos],
                              err=[float(err_h[i]) for i in pos])
                hard = [i for i in pos if not (err_h[i] <= floor)]
                if hard:
                    health.fail(k, "panel", "rank_overflow",
                                rows=[int(rows[i]) for i in hard],
                                err=[float(err_h[i]) for i in hard],
                                eps_floor=floor)
            return Qs, Vns, ranks, ranks_h, err

        def fn():
            pipe.begin_column()
            c["bt0"] = batching_trace_count()
            c["t0"] = time.perf_counter()
            out = compute()
            if health is None:
                commit(*out)
            else:
                # Defer the donated scatter to the check hook so it runs
                # exactly once, on the panel's settled content.
                c["pending"] = out

        def check():
            out = repair(*c.pop("pending"))
            commit(*out)
            health.columns_checked += 1

        return fn, (check if health is not None else None)

    def _update_stage(k: int, part: str):
        # ---- eager trailing update (column-scoped SYRK) --------------------
        # ``part="all"`` is the sequential driver's single node;
        # ``"head"`` / ``"tail"`` split it for the lookahead schedule
        # (head: column k+1's tiles + D[k+1]; tail: the pair-grid rest).
        trail = np.nonzero(pairs_np[:, 1] > k)[0]
        bump = {"all": trail,
                "head": np.nonzero(pairs_np[:, 1] == k + 1)[0],
                "tail": np.nonzero(pairs_np[:, 1] > k + 1)[0]}[part]
        c = st.col[k]

        def fn():
            Qs, Vns, ranks, dk_new = c["Qs"], c["Vns"], c["ranks"], c["dk"]
            T, wk = c["T"], c["wk"]
            if ranked:
                if wk and part != "tail":
                    # Flush before the column's first append when the next
                    # append would overflow: recompress the trailing tiles
                    # at their rank-bucket widths. The single check
                    # covers head+tail -- they append wk to disjoint tile
                    # sets, so the max content width grows by wk once.
                    high = int(st.tile_w[trail].max()) if trail.size else 0
                    if high + wk > w_flush:
                        with obs.span("chol.flush", cat="factor", k=k):
                            # Round the trailing tiles in place (no second
                            # pair of buffers); the tiles of factored
                            # columns are never read again and are skipped.
                            live = np.zeros(nt_p, bool)
                            live[trail] = True
                            st.accU, st.accV, rc, _ = bucketed_round_tiles(
                                st.accU, st.accV,
                                np.where(live, st.tile_w, 0), eps, r_out=b,
                                impl=impl, inplace=True)
                            st.tile_w = np.asarray(rc, dtype=np.int64)
                            if mesh is not None:
                                st.accU, st.accV = shard_tile_batch(
                                    st.accU, st.accV)
                        stats["flushes"] += 1
                if wk:
                    with obs.span("chol.syrk", cat="factor", k=k, wk=wk,
                                  T=T, part=part):
                        st.accU, st.accV, st.D = tlr_syrk_column(
                            st.accU, st.accV, st.tile_w, st.D,
                            Qs[:, :, :wk], Vns[:, :, :wk], ranks,
                            dk_new, k, impl=impl, part=part, donate=True)
                    st.tile_w[bump] += wk
                if part != "head":
                    stats["append_widths"].append(wk)
            else:
                if part != "tail" and st.used + r_p > w_acc:
                    # Flush: recompress every tile's accumulated
                    # concatenation back to width b in one batched rounding
                    # pass over the whole grid. Rows of already-factored
                    # columns are dead (their panels were consumed into
                    # Lout) -- rounding them is wasted work, but one
                    # uniform shape keeps a single compiled flush variant.
                    with obs.span("chol.flush", cat="factor", k=k):
                        Uc, Vc, _, _ = tlr_round_tiles(
                            st.accU, st.accV, eps, r_out=b, impl=impl)
                        st.accU = st.accV = None
                        st.accU, st.accV = _widen(Uc, Vc, rows=nt_p,
                                                  width=w_acc)
                        st.used = b
                        if mesh is not None:
                            st.accU, st.accV = shard_tile_batch(
                                st.accU, st.accV)
                    stats["flushes"] += 1
                with obs.span("chol.syrk", cat="factor", k=k, wk=wk, T=T,
                              part=part):
                    st.accU, st.accV, st.D = tlr_syrk_column(
                        st.accU, st.accV, st.used, st.D, Qs, Vns,
                        ranks, dk_new, k, impl=impl, part=part,
                        donate=True)
                if part != "head":
                    st.used += r_p
            if part != "head":
                if part == "all":
                    # Sequential parity: drain the column's whole dispatch
                    # before timing it. The lookahead schedule skips this
                    # (one final sync after the graph); the span makes the
                    # host-sync gap visible to the bench harness.
                    with obs.span("chol.sync", cat="factor", k=k):
                        jax.block_until_ready((Qs, Vns, ranks, st.accU,
                                               st.D))
                dt = time.perf_counter() - c["t0"]
                stats["column_iters"].append(1)
                stats["column_ranks"].append(c["ranks_h"])
                stats["column_events"].append({
                    "k": k, "T": T, "Tb": c["Tb"], "Jb": 0, "seconds": dt,
                    "traced": c["panel_traced"]
                    or batching_trace_count() > c["bt0"],
                    "err": np.asarray(c["err"])[:T],
                    "wQ": wk if ranked else None,
                })
                c.pop("Qs", None)
                c.pop("Vns", None)

        return fn

    # Stage graph (DESIGN.md section 12). Tokens are versioned values:
    # ("acc", k) / ("Dv", k) is the accumulation / diagonal state after
    # column k's full trailing update, ("acch", k) / ("Dh", k) the
    # intermediate state after its head only. The donating update stages
    # ``destroy`` the buffers they consume, which orders them after every
    # other reader -- under lookahead that is exactly what lets
    # panel(k+1) gather from the pre-tail buffers before update_tail(k)
    # donates them.
    def _update_check_hook(k: int):
        # Sequential schedule only: the "all" update already drains the
        # column's dispatch (the parity sync), so the trailing-diagonal
        # scan rides that sync for free. Under lookahead the updates stay
        # un-checked to preserve the overlap -- the next panel's hook and
        # the final gate keep the no-NaN guarantee.
        def check():
            diag = jnp.diagonal(st.D, axis1=1, axis2=2).reshape(-1)
            flags = column_flags(diag)
            if flags[1] > 0:
                health.fail(k, "update", "nonfinite_update",
                            nonfinite=int(flags[1]))

        return check

    stages = []

    def add(name, kind, k, fn, reads=(), writes=(), destroys=(),
            check=None):
        stages.append(Stage(name=name, kind=kind, k=k, fn=fn, check=check,
                            reads=tuple(reads), writes=tuple(writes),
                            destroys=tuple(destroys), seq=len(stages)))

    for k in range(nb):
        dtok = ("Dh", k - 1) if lookahead else ("Dv", k - 1)
        add(f"diag:{k}", "diag", k, _diag_stage(k),
            reads=[dtok] if k > 0 else [], writes=[("Lkk", k)],
            check=_diag_check_hook(k, st, opts, stats, health)
            if health is not None and k + 1 >= nb else None)
        if k + 1 >= nb:
            continue
        atok = ("acch", k - 1) if lookahead else ("acc", k - 1)
        pfn, pcheck = _panel_stage(k)
        add(f"panel:{k}", "panel", k, pfn,
            reads=([atok] if k > 0 else []) + [("Lkk", k)],
            writes=[("panel", k)], check=pcheck)
        prev = ([("acc", k - 1), ("Dv", k - 1)] if k > 0 else [])
        if lookahead:
            add(f"update_head:{k}", "update_head", k,
                _update_stage(k, "head"), reads=[("panel", k)],
                destroys=prev, writes=[("acch", k), ("Dh", k)])
            add(f"update_tail:{k}", "update_tail", k,
                _update_stage(k, "tail"), reads=[("panel", k)],
                destroys=[("acch", k), ("Dh", k)],
                writes=[("acc", k), ("Dv", k)])
        else:
            add(f"update:{k}", "update", k, _update_stage(k, "all"),
                reads=[("panel", k)], destroys=prev,
                writes=[("acc", k), ("Dv", k)],
                check=_update_check_hook(k) if health is not None
                else None)

    sched = run_graph(stages,
                      LookaheadSchedule() if lookahead
                      else SequentialSchedule())
    if lookahead:
        with obs.span("chol.sync", cat="factor", k=nb - 1):
            jax.block_until_ready((st.LU, st.LV, st.LR, st.accU, st.accV,
                                   st.D))
    sched["requested_lookahead"] = bool(opts.lookahead)
    stats["schedule"] = sched
    stats["column_traces"] = pipe.traces["column"]
    stats["scatter_traces"] = pipe.scatter_traces
    stats["algebra_traces"] = algebra_trace_count() - alg0
    stats["batching_traces"] = batching_trace_count()
    if health is not None:
        _final_gate(st, opts, health, b)
        stats["health"] = health.summary()
    Lmat = TLRMatrix(D=st.LD, U=st.LU, V=st.LV, ranks=st.LR)
    return TLRFactorization(L=Lmat, d=st.dvec, perm=np.arange(nb),
                            stats=stats)


def _swap_rows(arr, i, j):
    ai, aj = arr[i], arr[j]
    return arr.at[i].set(aj).at[j].set(ai)


def _swap_L_rows(L: TLRMatrix, k: int, pidx: int) -> TLRMatrix:
    """Swap already-written L tiles of logical rows k <-> pidx (cols j < k)."""
    if k == 0:
        return L
    ik = np.asarray([tril_index(k, j) for j in range(k)], np.int32)
    ip = np.asarray([tril_index(pidx, j) for j in range(k)], np.int32)
    both = np.concatenate([ik, ip])
    swapped = np.concatenate([ip, ik])

    def sw(arr):
        return arr.at[both].set(arr[swapped])

    return TLRMatrix(D=L.D, U=sw(L.U), V=sw(L.V), ranks=sw(L.ranks))


def _power_norms(tiles, iters: int, key):
    """Batched power-iteration 2-norm estimates for (T, b, b) symmetric tiles."""
    T, b, _ = tiles.shape
    x = jax.random.normal(key, (T, b), tiles.dtype)
    x = x / jnp.linalg.norm(x, axis=1, keepdims=True)

    tiny = jnp.finfo(tiles.dtype).tiny  # a zero tile stays zero, not NaN

    def body(_, x):
        y = einsum("tbc,tc->tb", tiles, x)
        return y / jnp.maximum(jnp.linalg.norm(y, axis=1, keepdims=True), tiny)

    x = jax.lax.fori_loop(0, iters, body, x)
    y = einsum("tbc,tc->tb", tiles, x)
    return jnp.linalg.norm(y, axis=1)
