"""TLR matrix-vector products and triangular solves (section 4.4, Alg. 7),
preconditioned CG (section 6.2), log-determinant and MVN sampling.

Every read path here dispatches through the :class:`~.batching.TilePlan`
execution-plan layer (DESIGN.md section 9). The matvec marshals off-diagonal
tiles into batched two-product chains ``U (V^T x)`` plus a segment reduction
-- the paper's "independent sets of products stored in output buffers
followed by a reduction" -- either as one flat r_max-wide batch
(``batching="flat"``) or per rank bucket at each bucket's ladder width
(``batching="ranked"``); ``batching="auto"`` (the default) lets the plan's
rank histogram decide.

The triangular solve is a jitted, bucket-laddered blocked TRSM: each column
step (diagonal solve + batched low-rank update of the remaining blocks) runs
inside one jitted executable whose row-batch operands are zero-padded up to
the power-of-two bucket ladder of DESIGN.md section 2, so ~log2(nb) compiled
variants serve all nb columns -- the same shape-stable treatment the
factorization's column pipeline got in PR 1, now applied to the solve phase
(the HODLR GPU solvers of arXiv 2208.06290 batch their solves the same way).
Under ranked batching the column step additionally slices its U/V gathers to
the column's plan width: one ladder width per row-bucket interval, so the
jit cache still grows *additively* (ladder length per direction, exactly the
flat path's contract -- the same additive-cache discipline as the ranked
left-looking driver's running ``wL``). Right-hand sides may be single
vectors ``(n,)`` or batched ``(n, m)``.
"""

from __future__ import annotations

import warnings
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from .batching import resolve_batching, tile_plan
from .buckets import _bucket_ladder, _bucket_up, trace_count, trace_event
from .. import obs
from .tlr import TLRMatrix, tril_pairs, tril_index
from ..precision import einsum, vdot


# -- symmetric TLR matvec ------------------------------------------------------


@partial(jax.jit, static_argnums=(5,))
def _sym_matvec(D, U, V, ranks, xb, nb: int):
    pairs = tril_pairs(nb)
    rows = jnp.asarray(pairs[:, 0], jnp.int32)
    cols = jnp.asarray(pairs[:, 1], jnp.int32)
    yb = einsum("kbc,kc...->kb...", D, xb)
    xj = jnp.take(xb, cols, axis=0)
    xi = jnp.take(xb, rows, axis=0)
    # lower tiles: y_i += U (V^T x_j);   mirrored upper: y_j += V (U^T x_i)
    ylo = einsum("tbr,tr...->tb...", U, einsum("tbr,tb...->tr...", V, xj))
    yup = einsum("tbr,tr...->tb...", V, einsum("tbr,tb...->tr...", U, xi))
    yb = yb.at[rows].add(ylo)
    yb = yb.at[cols].add(yup)
    return yb


# -- rank-bucketed read-path cores (TilePlan consumers; DESIGN.md section 9) ---

# The per-bucket two-product chains compile one variant per (bucket-padded
# count, bucket width, rhs shape) -- both padded up their ladders, so the
# count stays O(log nt * log r_max) per shape family. Registered under the
# "plan" key of the unified trace registry (tests/test_plans.py pins it).


@partial(jax.jit, static_argnames=("w",))
def _plan_chain(U, V, xb, yb, idx, src, dst, valid, *, w: int):
    """One rank bucket of a one-sided product: ``y[dst] += U (V^T x[src])``
    at the bucket's ladder width ``w`` (exact: factor columns past each
    tile's rank are zero). Padded slots gather tile 0 / block 0 and are
    masked to an exact zero before the segment reduction."""
    trace_event("plan")
    Ut = jnp.take(U, idx, axis=0)[:, :, :w]
    Vt = jnp.take(V, idx, axis=0)[:, :, :w]
    xs = jnp.take(xb, src, axis=0)
    y = einsum("tbr,tr...->tb...", Ut,
                   einsum("tbr,tb...->tr...", Vt, xs))
    m = valid.reshape((-1,) + (1,) * (y.ndim - 1))
    return yb.at[dst].add(jnp.where(m, y, jnp.zeros_like(y)))


@partial(jax.jit, static_argnames=("w",))
def _plan_chain_sym(U, V, xb, yb, idx, rows, cols, valid, *, w: int):
    """One rank bucket of the symmetric product: both the lower chain
    ``y_i += U (V^T x_j)`` and its mirrored upper ``y_j += V (U^T x_i)``
    share a single gather of the bucket's factors."""
    trace_event("plan")
    Ut = jnp.take(U, idx, axis=0)[:, :, :w]
    Vt = jnp.take(V, idx, axis=0)[:, :, :w]
    xj = jnp.take(xb, cols, axis=0)
    xi = jnp.take(xb, rows, axis=0)
    ylo = einsum("tbr,tr...->tb...", Ut,
                     einsum("tbr,tb...->tr...", Vt, xj))
    yup = einsum("tbr,tr...->tb...", Vt,
                     einsum("tbr,tb...->tr...", Ut, xi))
    m = valid.reshape((-1,) + (1,) * (ylo.ndim - 1))
    yb = yb.at[rows].add(jnp.where(m, ylo, jnp.zeros_like(ylo)))
    return yb.at[cols].add(jnp.where(m, yup, jnp.zeros_like(yup)))


def _bucket_index_arrays(bk, *gathers):
    """Pad a bucket's gather/scatter index vectors to its count-ladder slot
    count, plus the valid mask (padded slots point at index 0, masked)."""
    out = []
    for g in gathers:
        full = np.zeros(bk.padded, np.int32)
        full[:bk.count] = g
        out.append(jnp.asarray(full))
    valid = np.zeros(bk.padded, bool)
    valid[:bk.count] = True
    out.append(jnp.asarray(valid))
    return out


def _plan_gathers(plan, nb: int):
    """Per-bucket padded ``(idx, rows, cols, valid)`` device arrays.

    Memoized on the plan object itself: plans are memoized on the ranks
    array (one per factor generation), so the index uploads and padding
    happen once, not once per matvec/tri_matvec call. Stable array
    identities also keep the jitted chain cores hitting the same donated
    buffers across calls."""
    cache = plan.__dict__.get("_gather_cache")
    if cache is None:
        pairs = tril_pairs(nb)
        cache = [tuple(_bucket_index_arrays(
                     bk, bk.idx, pairs[bk.idx, 0], pairs[bk.idx, 1]))
                 for bk in plan.buckets]
        object.__setattr__(plan, "_gather_cache", cache)
    return cache


def tlr_matvec(A: TLRMatrix, x: jax.Array, *,
               batching: str | None = "auto") -> jax.Array:
    """y = A @ x for symmetric TLR A; x is (n,) or (n, m).

    ``batching="ranked"`` runs the two-product chains per rank bucket of
    the memoized :func:`~.batching.tile_plan` (each bucket at its own
    ladder width, rank-0 tiles skipped); ``"flat"`` is the single
    r_max-wide batch; ``"auto"`` (default) applies the rank-histogram
    policy (DESIGN.md section 9).
    """
    nb, b = A.nb, A.b
    xb = x.reshape(nb, b, *x.shape[1:])
    mode = resolve_batching(batching, A.ranks, A.r_max)
    if mode == "ranked":
        plan = tile_plan(A.ranks, A.r_max)
        yb = einsum("kbc,kc...->kb...", A.D, xb)
        for bk, (idx, rows, cols, valid) in zip(plan.buckets,
                                                _plan_gathers(plan, nb)):
            attrs = {}
            if obs.enabled():
                # Symmetric chain: both orientations per tile, 2 GEMMs each.
                attrs = _chain_span_attrs(plan, bk, b, xb, sym=True)
            with obs.span("matvec.bucket", cat="solve", **attrs):
                yb = _plan_chain_sym(A.U, A.V, xb, yb, idx, rows, cols,
                                     valid, w=bk.width)
    else:
        yb = _sym_matvec(A.D, A.U, A.V, A.ranks, xb, nb)
    return yb.reshape(x.shape)


def _chain_span_attrs(plan, bk, b: int, xb, sym: bool) -> dict:
    """Telemetry attributes for one bucket of a two-product read chain
    (enabled mode only): ``2 * 2*b*w*m`` FLOPs per dispatched tile-product
    slot (V^T x then U y, ``m`` rhs columns; doubled again for the
    symmetric chain's mirrored product), useful scaled by true rank mass."""
    m = 1
    for d in xb.shape[2:]:
        m *= int(d)
    per_col = (8 if sym else 4) * b * m
    return {"width": bk.width, "count": bk.count, "padded": bk.padded,
            "flops": float(per_col) * float(plan.ranks_host[bk.idx].sum()),
            "flops_padded": float(per_col) * float(bk.padded * bk.width)}


# -- lower-triangular TLR products / solves -------------------------------------


def tlr_tri_matvec(L: TLRMatrix, x: jax.Array, *, trans: bool = False,
                   batching: str | None = "auto") -> jax.Array:
    """y = L @ x (or L^T @ x) for lower-triangular TLR L. Same ``batching``
    dispatch as :func:`tlr_matvec` (the transposed product swaps the U/V
    roles inside each bucket chain)."""
    nb, b = L.nb, L.b
    xb = x.reshape(nb, b, *x.shape[1:])
    pairs = tril_pairs(nb)
    mode = resolve_batching(batching, L.ranks, L.r_max)
    if mode == "ranked":
        plan = tile_plan(L.ranks, L.r_max)
        if not trans:
            yb = einsum("kbc,kc...->kb...", L.D, xb)
        else:
            yb = einsum("kcb,kc...->kb...", L.D, xb)
        for bk, (idx, rows, cols, valid) in zip(plan.buckets,
                                                _plan_gathers(plan, nb)):
            attrs = {}
            if obs.enabled():
                attrs = _chain_span_attrs(plan, bk, b, xb, sym=False)
            with obs.span("tri_matvec.bucket", cat="solve", **attrs):
                if not trans:
                    yb = _plan_chain(L.U, L.V, xb, yb, idx, cols, rows,
                                     valid, w=bk.width)
                else:
                    # (L^T)(j,i) = L(i,j)^T = V U^T: swap the factor roles.
                    yb = _plan_chain(L.V, L.U, xb, yb, idx, rows, cols,
                                     valid, w=bk.width)
        return yb.reshape(x.shape)
    rows = jnp.asarray(pairs[:, 0], jnp.int32)
    cols = jnp.asarray(pairs[:, 1], jnp.int32)
    if not trans:
        yb = einsum("kbc,kc...->kb...", L.D, xb)
        xj = jnp.take(xb, cols, axis=0)
        ylo = einsum("tbr,tr...->tb...", L.U,
                         einsum("tbr,tb...->tr...", L.V, xj))
        yb = yb.at[rows].add(ylo)
    else:
        yb = einsum("kcb,kc...->kb...", L.D, xb)
        xi = jnp.take(xb, rows, axis=0)
        yup = einsum("tbr,tr...->tb...", L.V,
                         einsum("tbr,tb...->tr...", L.U, xi))
        yb = yb.at[cols].add(yup)
    return yb.reshape(x.shape)


# -- jitted bucketed blocked TRSM ----------------------------------------------

# One entry per freshly compiled column-step variant, under the "trsm" key
# of the unified registry (core/buckets.py); the python body of the jitted
# step runs exactly once per compile, so this is a real compile count (the
# contract tests/test_trsm.py pins, mirroring ``stats["column_traces"]`` in
# the factorization).


def trsm_trace_count() -> int:
    """Compiled TRSM column-step variants so far (process-wide); a view of
    ``trace_count("trsm")`` in the unified registry."""
    return trace_count("trsm")


@partial(jax.jit, static_argnames=("trans", "w"))
def _trsm_step(D, U, V, xb, k, tidx, ridx, valid, *, trans: bool, w: int):
    """One blocked-TRSM column: solve the diagonal block, update the rest.

    Operands: the factor's full (static-shape) D/U/V buffers plus small
    per-column index vectors. ``tidx`` selects the Tb (bucket-padded) tiles
    of column k, ``ridx`` the block rows they update; padded slots carry
    ``valid=False`` and a zero update, so the scatter-add is inert there
    (padded ``ridx`` entries point at block 0 and add exact zeros).

    ``w`` is the column's plan width (a rank-ladder value covering every
    rank this step touches; ``r_max`` on the flat path): the U/V gathers
    slice to it, so XLA fuses a narrow gather and the update chain runs at
    the bucketed width -- exact, because factor columns past each tile's
    rank are zero. One width is shared per row-bucket interval, so the jit
    cache stays one variant per (Tb, direction): additive, never the
    T-ladder x width-ladder product.
    """
    trace_event("trsm")
    Dk = jax.lax.dynamic_index_in_dim(D, k, keepdims=False)
    yk = jax.lax.dynamic_index_in_dim(xb, k, keepdims=False)
    Ut = jnp.take(U, tidx, axis=0)[:, :, :w]
    Vt = jnp.take(V, tidx, axis=0)[:, :, :w]
    if trans:
        # (L^T)(j,k) = L(k,j)^T = V U^T: the U/V roles swap in the update.
        Dk = Dk.T
        Ut, Vt = Vt, Ut
    xk = jax.scipy.linalg.solve_triangular(Dk, yk, lower=not trans)
    upd = einsum("tbr,trm->tbm", Ut, einsum("tbr,bm->trm", Vt, xk))
    upd = jnp.where(valid[:, None, None], upd, jnp.zeros_like(upd))
    xb = jax.lax.dynamic_update_index_in_dim(xb, xk, k, axis=0)
    return xb.at[ridx].add(-upd)


def _trsv_column_tiles(nb: int, k: int, trans: bool):
    """Packed tile indices and target block rows of solve column ``k``."""
    if not trans:
        tgt = np.arange(k + 1, nb)
        tiles = tgt * (tgt - 1) // 2 + k              # tril_index(i, k)
    else:
        tgt = np.arange(k)
        tiles = k * (k - 1) // 2 + tgt                # tril_index(k, j)
    return tiles, tgt


def _trsv_bucket_widths(plan, nb: int, trans: bool, ladder) -> dict[int, int]:
    """One plan width per row-bucket interval: the ladder width covering
    every rank any column in that Tb bucket touches. Sharing one width per
    interval (instead of one per column) keeps the jit cache additive --
    at most one (Tb, w) executable per ladder entry and direction, the same
    contract as the flat path -- while narrow intervals (the trailing
    columns of the forward sweep, the leading ones of the backward) still
    run at their own narrow widths.

    Memoized on the plan object (like ``_plan_gathers``): the plan is one
    per factor generation, so a server solving against a resident
    factorization every tick pays the nb-column sweep once, not per call."""
    cache = plan.__dict__.get("_trsv_width_cache")
    if cache is None:
        cache = {}
        object.__setattr__(plan, "_trsv_width_cache", cache)
    hit = cache.get((nb, trans))
    if hit is not None:
        return hit
    widths: dict[int, int] = {}
    for k in range(nb):
        tiles, tgt = _trsv_column_tiles(nb, k, trans)
        Tb = _bucket_up(max(len(tgt), 1), ladder)
        cw = int(plan.widths[tiles].max(initial=0)) if len(tiles) else 0
        widths[Tb] = max(widths.get(Tb, 1), cw, 1)
    cap = max(int(plan.cap), 1)
    out = {Tb: min(w, cap) for Tb, w in widths.items()}
    cache[(nb, trans)] = out
    return out


@lru_cache(maxsize=64)
def _trsv_column_steps(nb: int, trans: bool):
    """Host marshaling of a whole TRSM sweep, memoized per (nb, direction):
    for each column k in sweep order, the bucket-padded index operands of
    its jitted step as *device* arrays -- ``(Tb, k_dev, tidx, ridx,
    valid)``. Uploading these once per (nb, trans) instead of per call
    removes the per-column host packing + transfer from the solve hot path
    (a serving tick runs four sweeps per batch), and the stable array
    identities keep the jitted steps hitting the same buffers."""
    ladder = _bucket_ladder(nb - 1)
    order = range(nb) if not trans else range(nb - 1, -1, -1)
    steps = []
    for k in order:
        tiles, tgt = _trsv_column_tiles(nb, k, trans)
        T = len(tgt)
        Tb = _bucket_up(max(T, 1), ladder)
        tidx = np.zeros(Tb, np.int32)
        ridx = np.zeros(Tb, np.int32)
        tidx[:T], ridx[:T] = tiles, tgt
        valid = np.zeros(Tb, bool)
        valid[:T] = True
        steps.append((Tb, jnp.asarray(k, jnp.int32), jnp.asarray(tidx),
                      jnp.asarray(ridx), jnp.asarray(valid)))
    return tuple(steps)


def tlr_trsv(L: TLRMatrix, y: jax.Array, *, trans: bool = False,
             batching: str | None = "auto") -> jax.Array:
    """Solve L x = y (trans=False) or L^T x = y (trans=True). Algorithm 7.

    Right-looking blocked TRSM: after each diagonal solve, the solution
    block updates all remaining blocks through the batched two-product
    chain, inside a jitted bucket-laddered column step (~log2(nb) compiled
    variants instead of a host loop over per-block lists). ``y`` is a single
    right-hand side ``(n,)`` or a batch ``(n, m)``.

    ``batching="ranked"`` slices each column step's U/V gathers to the
    column's plan width from the factor's memoized
    :func:`~.batching.tile_plan` (see :func:`_trsv_bucket_widths` for the
    additive jit-cache contract); ``"flat"`` runs every step r_max-wide;
    ``"auto"`` (default) applies the rank-histogram policy.
    """
    nb, b = L.nb, L.b
    xb = y.reshape(nb, b, -1)
    if nb == 1:
        Dk = L.D[0].T if trans else L.D[0]
        x = jax.scipy.linalg.solve_triangular(Dk, xb[0], lower=not trans)
        return x.reshape(y.shape)
    mode = resolve_batching(batching, L.ranks, L.r_max)
    ladder = _bucket_ladder(nb - 1)
    if mode == "ranked":
        plan = tile_plan(L.ranks, L.r_max)
        bucket_w = _trsv_bucket_widths(plan, nb, trans, ladder)
    else:
        bucket_w = None
    sweep_attrs = {"nb": nb, "trans": trans, "mode": mode} \
        if obs.enabled() else {}
    with obs.span("trsm.sweep", cat="solve", **sweep_attrs):
        for Tb, k_dev, tidx, ridx, valid in _trsv_column_steps(nb, trans):
            w = bucket_w[Tb] if bucket_w is not None else L.r_max
            # Column steps dispatch asynchronously, so each child span
            # times the launch, not the device work; the sweep span's
            # TraceAnnotation carries the device alignment.
            with obs.span("trsm.column", cat="solve", Tb=Tb, w=w):
                xb = _trsm_step(L.D, L.U, L.V, xb, k_dev, tidx, ridx,
                                valid, trans=trans, w=w)
    return xb.reshape(y.shape)


def tlr_trsv_reference(L: TLRMatrix, y: jax.Array, *,
                       trans: bool = False) -> jax.Array:
    """Pre-PR-2 host-loop TRSV, kept as the parity oracle for the jitted
    bucketed TRSM (tests/test_trsm.py; benchmarks/bench_tlr.py --suite
    solve). Same math, un-jitted python loop over per-block lists."""
    nb, b = L.nb, L.b
    xb = [y.reshape(nb, b, *y.shape[1:])[i] for i in range(nb)]
    order = range(nb) if not trans else range(nb - 1, -1, -1)
    for k in order:
        Dk = L.D[k] if not trans else L.D[k].T
        xk = jax.scipy.linalg.solve_triangular(Dk, xb[k], lower=not trans)
        xb[k] = xk
        if not trans:
            idx = [tril_index(i, k) for i in range(k + 1, nb)]
            if idx:
                ii = jnp.asarray(idx, jnp.int32)
                Ut, Vt = jnp.take(L.U, ii, axis=0), jnp.take(L.V, ii, axis=0)
                upd = einsum("tbr,tr...->tb...", Ut,
                                 einsum("tbr,b...->tr...", Vt, xk))
                for t, i in enumerate(range(k + 1, nb)):
                    xb[i] = xb[i] - upd[t]
        else:
            idx = [tril_index(k, j) for j in range(k)]
            if idx:
                ii = jnp.asarray(idx, jnp.int32)
                Ut, Vt = jnp.take(L.U, ii, axis=0), jnp.take(L.V, ii, axis=0)
                # (L^T)(j,k) = L(k,j)^T = V U^T
                upd = einsum("tbr,tr...->tb...", Vt,
                                 einsum("tbr,b...->tr...", Ut, xk))
                for t, j in enumerate(range(k)):
                    xb[j] = xb[j] - upd[t]
    return jnp.stack(xb).reshape(y.shape)


def tile_perm_to_element_perm(perm: np.ndarray, b: int) -> np.ndarray:
    return (np.asarray(perm)[:, None] * b + np.arange(b)[None, :]).reshape(-1)


# -- factorization application (implementations behind the handle methods) ----


def _permute_rows(x: jax.Array, eperm: np.ndarray) -> jax.Array:
    """Gather rows by the element permutation; one code path for single
    vectors (n,) and batched right-hand sides (n, m)."""
    return x[eperm]


def _unpermute_rows(x: jax.Array, eperm: np.ndarray) -> jax.Array:
    """Scatter rows back through the inverse permutation (the dual of
    :func:`_permute_rows`, same ndim-agnostic contract)."""
    return jnp.zeros_like(x).at[eperm].set(x)


def _factor_solve_impl(fact, y: jax.Array) -> jax.Array:
    """Solve A x = y given a TLRFactorization (handles perm and LDL)."""
    eperm = tile_perm_to_element_perm(fact.perm, fact.L.b)
    z = tlr_trsv(fact.L, _permute_rows(y, eperm), trans=False)
    if fact.d is not None:
        dflat = fact.d.reshape(-1)
        z = z / dflat.reshape((-1,) + (1,) * (z.ndim - 1))
    z = tlr_trsv(fact.L, z, trans=True)
    return _unpermute_rows(z, eperm)


def _logdet_impl(fact) -> jax.Array:
    """log |det A| from the factorization diagonals.

    One batched ``jnp.diagonal`` over the (nb, b, b) diagonal-tile stack --
    the per-tile ``jnp.diag`` host loop this replaces dispatched nb tiny
    ops per call.
    """
    if fact.d is not None:
        diag_ld = jnp.sum(jnp.log(jnp.abs(fact.d)))
        return diag_ld
    diags = jnp.diagonal(fact.L.D, axis1=1, axis2=2)
    return 2.0 * jnp.sum(jnp.log(jnp.abs(diags)))


def _mvn_sample_impl(fact, key, num: int = 1) -> jax.Array:
    """Sample x ~ N(0, A) via x = P^T L z (Cholesky factorizations only)."""
    if fact.d is not None:
        raise ValueError("MVN sampling requires a Cholesky factorization")
    n = fact.L.n
    z = jax.random.normal(key, (n, num), fact.L.dtype)
    x = tlr_tri_matvec(fact.L, z)
    eperm = tile_perm_to_element_perm(fact.perm, fact.L.b)
    out = _unpermute_rows(x, eperm)
    return out[:, 0] if num == 1 else out


def _deprecated(old: str, new: str) -> None:
    # FutureWarning, not DeprecationWarning: the default warning filters
    # silence DeprecationWarning outside __main__, and remaining shims
    # (``tlr.from_dense``) are the user-facing migration signal for the one
    # release they survive.
    warnings.warn(f"{old} is deprecated; use {new} (DESIGN.md section 5)",
                  FutureWarning, stacklevel=3)


# -- preconditioned conjugate gradients -----------------------------------------


def _as_matvec(op):
    """Coerce an operator argument to a matvec callable: a bare callable,
    or any object with a ``.matvec`` (TLROperator; TLRFactorization, whose
    operator action is A^{-1})."""
    if op is None:
        return None
    if callable(op) and not hasattr(op, "matvec"):
        return op
    mv = getattr(op, "matvec", None)
    if mv is not None:
        return mv
    raise TypeError(
        f"expected a callable or an object with .matvec, got {type(op)!r}")


class PCGHistory(list):
    """Relative-residual history: a plain ``list`` of floats (so existing
    ``hist[-1]`` / iteration callers keep working) carrying breakdown
    diagnostics. ``breakdown`` is None on a clean run, or the condition
    that stopped the iteration early:

    * ``"indefinite_curvature"``      -- p^T A p <= 0 (A not SPD),
    * ``"indefinite_preconditioner"`` -- r^T M^{-1} r <= 0 (M not SPD),
    * ``"nonfinite"``                 -- a NaN/Inf appeared in the recurrence.

    On breakdown PCG returns the last finite iterate instead of silently
    flooding x and the history with NaNs for the remaining iterations.

    Counters of a single-vector solve, kept with telemetry off too:
    ``iterations`` (accepted CG steps, the returned count) and
    ``host_reads`` (device-to-host reads of the convergence scalars, each
    one wait for the device). Under telemetry ``telemetry`` holds the
    metrics snapshot of the solve's ``algebra.pcg`` span (its
    ``algebra.pcg.check`` reads, ``trsm.*`` and ``matvec.*`` children).
    """

    def __init__(self, *args):
        super().__init__(*args)
        self.breakdown: str | None = None
        self.iterations = 0
        self.host_reads = 0
        self.telemetry: dict | None = None


def pcg(A, b_rhs: jax.Array, *, precond=None, tol=1e-6,
        maxiter: int = 300, check_every: int = 1):
    """PCG with relative residual ||Ax-b||/||b|| stopping (paper section 6.2).

    ``A`` and ``precond`` are callables ``v -> Av`` (resp. ``r -> M^{-1}r``)
    or any object with a ``.matvec`` -- a ``TLROperator``, or a
    ``TLRFactorization`` used directly as the preconditioner. Host-driven
    loop; returns (x, iterations, history), where ``history`` is a
    :class:`PCGHistory` whose ``breakdown`` attribute records an
    indefinite-operator / indefinite-preconditioner / non-finite breakdown
    (the iteration stops at the last finite iterate instead of spinning to
    ``maxiter`` on NaNs). A zero right-hand side returns x = 0 immediately
    with an empty history.

    A batched right-hand side ``(n, k)`` runs *per-column* CG through
    :class:`BatchedPCG`: every column carries its own alpha/beta recurrence,
    its own tolerance (``tol`` may be an ``(k,)`` array), and a per-column
    convergence mask, so one slow column never stalls the block -- converged
    columns freeze in place while the rest keep iterating (the serving-side
    mirror of the paper's Algorithm 5 eviction). The batched form returns
    ``(X, iters, histories)`` with ``iters`` an ``(k,)`` int array and
    ``histories`` a list of per-column :class:`PCGHistory`.

    ``check_every`` batches the convergence/breakdown checks: the recurrence
    runs ``check_every`` iterations on device, then one host sync pulls that
    window's scalars (``p^T A p``, ``||r||``, ``r^T z``) together instead of
    three blocking ``float(...)`` round trips per iteration. The device-side
    op sequence per iteration is identical for every ``check_every``, so the
    iterate history is bit-for-bit the same as ``check_every=1`` (pinned by
    tests/test_plans.py); a window that trips a check mid-way is replayed
    from its start up to the event, reproducing the exact per-iteration
    stopping semantics (at most one extra partial window of recompute, only
    on the final window). The window is always clamped to the iterations
    remaining, so ``maxiter`` need not be a multiple of ``check_every``.

    A single-vector solve counts its iterations and host reads on the
    returned history; under telemetry it runs in an ``algebra.pcg`` span
    (ending at its last read) with an ``algebra.pcg.check`` span around
    each read, and the history carries the span's metrics snapshot.
    """
    if jnp.ndim(b_rhs) >= 2:
        return _pcg_batched(A, jnp.asarray(b_rhs), precond=precond, tol=tol,
                            maxiter=maxiter, check_every=check_every)
    history = PCGHistory()
    args = (A, b_rhs, precond, float(tol), maxiter, max(1, int(check_every)),
            history)
    if not obs.enabled():
        return _pcg_single(*args), history.iterations, history
    with obs.span("algebra.pcg", cat="solve", n=int(b_rhs.shape[0]),
                  maxiter=maxiter, check_every=check_every) as root:
        x = _pcg_single(*args)
        root.set(iterations=history.iterations,
                 host_reads=history.host_reads)
    history.telemetry = obs.metrics_snapshot(root=root)
    return x, history.iterations, history


def _pull(history: PCGHistory, value) -> np.ndarray:
    """Read PCG's convergence scalars to the host: counted on
    ``history``, in an ``algebra.pcg.check`` span under telemetry."""
    history.host_reads += 1
    with obs.span("algebra.pcg.check", cat="solve"):
        return np.asarray(value)


def _pcg_single(A, b_rhs, precond, tol: float, maxiter: int,
                check_every: int, history: PCGHistory) -> jax.Array:
    """The single-vector PCG loop of :func:`pcg`: fills ``history`` (its
    residuals, breakdown and counters) and returns x."""
    matvec = _as_matvec(A)
    precond = _as_matvec(precond)
    bnorm = float(_pull(history, jnp.linalg.norm(b_rhs)))
    if bnorm == 0.0:
        return jnp.zeros_like(b_rhs)
    x = jnp.zeros_like(b_rhs)
    r = b_rhs - matvec(x)
    z = precond(r) if precond else r
    p_dir = z
    rz = vdot(r, z)
    rnorm0, rz_f = (float(v) for v in
                    _pull(history, jnp.stack([jnp.linalg.norm(r), rz])))
    history.append(rnorm0 / bnorm)
    if not np.isfinite(rz_f) or rz_f <= 0.0:
        history.breakdown = ("nonfinite" if not np.isfinite(rz_f)
                             else "indefinite_preconditioner")
        return x

    def step(x, r, p_dir, rz):
        """One CG iteration; returns the new state and the (lazy, device)
        check scalars. Same op order as the classic per-iteration loop, so
        every intermediate is bitwise independent of ``check_every``."""
        Ap = matvec(p_dir)
        pAp = vdot(p_dir, Ap)
        alpha = rz / pAp
        x_new = x + alpha * p_dir
        r_new = r - alpha * Ap
        rnorm = jnp.linalg.norm(r_new)
        z = precond(r_new) if precond else r_new
        rz_new = vdot(r_new, z)
        beta = rz_new / rz
        p_new = z + beta * p_dir
        return (x_new, r_new, p_new, rz_new), (pAp, rnorm, rz_new)

    it = 0
    state = (x, r, p_dir, rz)
    done = False
    while it < maxiter and not done:
        steps = min(check_every, maxiter - it)
        start = state
        scalars = []
        st = state
        for _ in range(steps):
            st, sc = step(*st)
            scalars.append(sc)
        # One host sync for the whole window.
        vals = _pull(history, jnp.stack([jnp.stack(sc) for sc in scalars]))
        accepted = 0
        for s in range(steps):
            pAp, rnorm_raw, rz_new = (float(v) for v in vals[s])
            if not np.isfinite(pAp) or pAp <= 0.0:
                history.breakdown = ("nonfinite" if not np.isfinite(pAp)
                                     else "indefinite_curvature")
                done = True
                break                       # iterate s discarded
            rnorm = rnorm_raw / bnorm
            if not np.isfinite(rnorm):
                history.breakdown = "nonfinite"
                done = True
                break                       # iterate s discarded
            accepted = s + 1
            it += 1
            history.append(rnorm)
            if rnorm < tol:
                done = True
                break
            if not np.isfinite(rz_new) or rz_new <= 0.0:
                history.breakdown = ("nonfinite" if not np.isfinite(rz_new)
                                     else "indefinite_preconditioner")
                done = True
                break                       # iterate s kept
        if accepted == steps:
            state = st
        else:
            # Replay the window up to the last accepted iterate: the same
            # jax ops from the same inputs reproduce it exactly.
            st = start
            for _ in range(accepted):
                st, _ = step(*st)
            state = st
    history.iterations = it
    return state[0]


# -- batched-RHS PCG with per-column convergence masks --------------------------


def _pcg_block_step(matvec, precond, X, R, P, RZ, act):
    """One batched CG iteration over an ``(n, k)`` block with per-column
    alpha/beta and a per-column active mask.

    Columns are fully independent: the matvec applies the operator to each
    column separately (matrix products mix rows, never columns), and every
    other op is columnwise, so masking a column freezes it *exactly* --
    active columns compute bit-for-bit the same values whether their
    neighbors are frozen or not. Frozen columns keep their old state through
    explicit ``where`` selects (their lanes may compute garbage, including
    NaN from a broken-down neighbor iterate; the select discards it)."""
    AP = matvec(P)
    pAp = jnp.sum(P * AP, axis=0)
    alpha = jnp.where(act, RZ / jnp.where(pAp != 0, pAp, 1.0), 0.0)
    Xn = jnp.where(act, X + alpha[None, :] * P, X)
    Rn = jnp.where(act, R - alpha[None, :] * AP, R)
    rnorm = jnp.linalg.norm(Rn, axis=0)
    Z = precond(Rn) if precond else Rn
    RZn = jnp.sum(Rn * Z, axis=0)
    beta = jnp.where(act, RZn / jnp.where(RZ != 0, RZ, 1.0), 0.0)
    Pn = jnp.where(act, Z + beta[None, :] * P, P)
    RZk = jnp.where(act, RZn, RZ)
    return (Xn, Rn, Pn, RZk), (pAp, rnorm, RZn)


class BatchedPCG:
    """Incremental batched-RHS PCG over a fixed-width column block.

    The engine holds ``width`` right-hand-side *slots* of length ``n``.
    Columns are loaded with :meth:`load` (each with its own tolerance and
    iteration budget), advanced together in windows of ``check_every``
    device iterations by :meth:`advance`, and leave the block the moment
    they converge, break down, or exhaust their budget -- a per-column
    convergence mask freezes finished columns in place while the rest keep
    iterating, so shapes never change and one slow column cannot stall the
    block. This is the iterative-solve mirror of the paper's Algorithm 5
    subset marshaling (and the engine the ``TLRServer`` ticks drive).

    Per-iteration stopping semantics are exact: after each window one host
    sync pulls the window's per-column scalars, each column's stopping
    iteration is located host-side, and if any column stopped mid-window
    the window is replayed with per-step masks -- columns that ran the full
    window reproduce their no-replay state bit-for-bit (column
    independence), stopped columns freeze at exactly their last accepted
    iterate, matching the scalar :func:`pcg` contract per column. The
    window length never depends on per-column budgets, so the compiled
    step-shape set is fixed after the first window (the serve-path
    no-recompile pin rides on this).

    Statuses: ``"idle"`` (slot empty), ``"active"`` (iterating), ``"done"``
    (finished, result waiting for :meth:`evict`).
    """

    def __init__(self, A, n: int, width: int, *, precond=None,
                 maxiter: int = 300, check_every: int = 8,
                 dtype=None):
        self.matvec = _as_matvec(A)
        self.precond = _as_matvec(precond)
        self.n, self.width = int(n), int(width)
        self.check_every = max(1, int(check_every))
        self.default_maxiter = int(maxiter)
        self.dtype = jnp.dtype(dtype) if dtype is not None else (
            jnp.float64 if jax.config.jax_enable_x64 else jnp.float32)
        self._reset_state()

    def _reset_state(self):
        n, w = self.n, self.width
        self.X = jnp.zeros((n, w), self.dtype)
        self.R = jnp.zeros((n, w), self.dtype)
        self.P = jnp.zeros((n, w), self.dtype)
        self.RZ = jnp.zeros((w,), self.dtype)
        self.act = np.zeros(w, bool)
        self.status = ["idle"] * w
        self.converged = np.zeros(w, bool)
        self.bnorm = np.zeros(w)
        self.tol = np.full(w, 1e-6)
        self.maxiter = np.full(w, self.default_maxiter, np.int64)
        self.iters = np.zeros(w, np.int64)
        self.hist: list[PCGHistory] = [PCGHistory() for _ in range(w)]
        self._pending: dict[int, np.ndarray] = {}

    def reset(self):
        """Clear every slot (used after a warmup pass -- the compiled
        executables survive, the state does not)."""
        self._reset_state()

    # -- slot lifecycle ----------------------------------------------------

    def load(self, j: int, b_col, *, tol: float = 1e-6,
             maxiter: int | None = None) -> None:
        """Stage right-hand side ``b_col`` into column ``j``. The device
        write happens at the next :meth:`advance` as one masked block
        update over all staged columns (no per-column-index executables)."""
        j = int(j)
        if self.status[j] == "active":
            raise ValueError(f"column {j} is still active; evict it first")
        col = np.asarray(b_col, np.dtype(self.dtype)).reshape(-1)
        if col.shape[0] != self.n:
            raise ValueError(
                f"rhs length {col.shape[0]} != operator size {self.n}")
        self.hist[j] = PCGHistory()
        self.iters[j] = 0
        self.converged[j] = False
        self.act[j] = False
        self.tol[j] = float(tol)
        self.maxiter[j] = int(maxiter if maxiter is not None
                              else self.default_maxiter)
        self.bnorm[j] = float(np.linalg.norm(col))
        if self.bnorm[j] == 0.0:
            # x = 0 exactly; empty history, converged (scalar-pcg contract)
            self._pending.pop(j, None)
            self.status[j] = "done"
            self.converged[j] = True
            return
        self.status[j] = "pending"
        self._pending[j] = col

    def evict(self, j: int) -> tuple[np.ndarray, int, PCGHistory, bool]:
        """Pull column ``j``'s result and free the slot. Returns
        ``(x, iterations, history, converged)``."""
        j = int(j)
        if self.status[j] != "done":
            raise ValueError(f"column {j} is {self.status[j]!r}, not done")
        x = np.asarray(self.X[:, j])
        out = (x, int(self.iters[j]), self.hist[j], bool(self.converged[j]))
        self.status[j] = "idle"
        self.act[j] = False
        return out

    def cancel(self, j: int) -> None:
        """Abandon column ``j`` in whatever state it is in and free the
        slot (timeout eviction: the serve loop drops a column whose
        deadline passed without waiting for convergence). The device
        iterate keeps running the stale column until the mask next
        rebuilds -- harmless, it is never read."""
        j = int(j)
        self.status[j] = "idle"
        self.act[j] = False
        self._pending.pop(j, None)

    def solution(self) -> jax.Array:
        """The current iterate block (device, ``(n, width)``)."""
        return self.X

    @property
    def active_columns(self) -> list[int]:
        return [j for j, s in enumerate(self.status)
                if s in ("active", "pending")]

    @property
    def done_columns(self) -> list[int]:
        return [j for j, s in enumerate(self.status) if s == "done"]

    # -- the window --------------------------------------------------------

    def _flush_pending(self) -> list[int]:
        """Materialize staged columns: one masked block write (x=0, r=b),
        one batched preconditioner application for p/rz, and the per-column
        initial-residual bookkeeping. Returns columns that finished at
        init (rz <= 0 / non-finite: immediate breakdown)."""
        if not self._pending:
            return []
        cols = sorted(self._pending)
        B = np.zeros((self.n, self.width), np.dtype(self.dtype))
        sel = np.zeros(self.width, bool)
        for j in cols:
            B[:, j] = self._pending[j]
            sel[j] = True
        Bj = jnp.asarray(B)
        mj = jnp.asarray(sel)
        zero = jnp.zeros((), self.dtype)
        self.R = jnp.where(mj[None, :], Bj, self.R)
        self.X = jnp.where(mj[None, :], zero, self.X)
        Z = self.precond(self.R) if self.precond else self.R
        RZ_all = jnp.sum(self.R * Z, axis=0)
        self.P = jnp.where(mj[None, :], Z, self.P)
        self.RZ = jnp.where(mj, RZ_all, self.RZ)
        rz_host = np.asarray(RZ_all)[cols]
        finished = []
        for j, rz in zip(cols, rz_host):
            rz = float(rz)
            self.hist[j].append(1.0)      # ||r||/||b|| = 1 at x = 0
            if not np.isfinite(rz) or rz <= 0.0:
                self.hist[j].breakdown = (
                    "nonfinite" if not np.isfinite(rz)
                    else "indefinite_preconditioner")
                self.status[j] = "done"
                finished.append(j)
            else:
                self.status[j] = "active"
                self.act[j] = True
        self._pending.clear()
        return finished

    def _scan_column(self, j: int, vals: np.ndarray, steps: int) -> int:
        """Walk column ``j`` through the window's pulled scalars, applying
        the scalar-pcg acceptance rules; returns the number of accepted
        iterates (== ``steps`` when the column ran the whole window)."""
        accepted = 0
        for s in range(steps):
            pAp, rnorm_raw, rz_new = (float(vals[s, i, j]) for i in range(3))
            if not np.isfinite(pAp) or pAp <= 0.0:
                self.hist[j].breakdown = (
                    "nonfinite" if not np.isfinite(pAp)
                    else "indefinite_curvature")
                return accepted               # iterate s discarded
            rel = rnorm_raw / self.bnorm[j]
            if not np.isfinite(rel):
                self.hist[j].breakdown = "nonfinite"
                return accepted               # iterate s discarded
            accepted = s + 1
            self.iters[j] += 1
            self.hist[j].append(rel)
            if rel < self.tol[j]:
                self.converged[j] = True
                return accepted               # iterate s kept
            if not np.isfinite(rz_new) or rz_new <= 0.0:
                self.hist[j].breakdown = (
                    "nonfinite" if not np.isfinite(rz_new)
                    else "indefinite_preconditioner")
                return accepted               # iterate s kept
            if self.iters[j] >= self.maxiter[j]:
                return accepted               # budget exhausted, no flag
        return accepted

    def advance(self, steps: int | None = None) -> list[int]:
        """Run one window of ``steps`` (default ``check_every``) batched
        iterations, then settle per-column outcomes; returns the columns
        that finished during this call (converged, broke down, or hit
        their iteration budget). Idle/done columns are inert."""
        finished = self._flush_pending()
        act_idx = np.nonzero(self.act)[0]
        if act_idx.size == 0:
            return finished
        steps = max(1, int(steps if steps is not None else self.check_every))
        start = (self.X, self.R, self.P, self.RZ)
        actj = jnp.asarray(self.act)
        st, scal = start, []
        for _ in range(steps):
            st, sc = _pcg_block_step(self.matvec, self.precond, *st, actj)
            scal.append(jnp.stack(sc))
        vals = np.asarray(jnp.stack(scal))    # (steps, 3, width): one sync
        stop_at = np.full(self.width, steps)
        for j in act_idx:
            stop_at[j] = self._scan_column(j, vals, steps)
            if (stop_at[j] < steps or self.converged[j]
                    or self.hist[j].breakdown is not None
                    or self.iters[j] >= self.maxiter[j]):
                self.act[j] = False
                self.status[j] = "done"
                finished.append(int(j))
        if np.all(stop_at[act_idx] == steps):
            # every column accepted the whole window (finishing exactly at
            # its last step is fine -- the state is the accepted iterate)
            self.X, self.R, self.P, self.RZ = st
            return finished
        # Replay with per-step masks: a column accepted ``stop_at[j]``
        # iterates, so it participates in steps 0..stop_at[j]-1 and is
        # frozen after -- the same jax ops from the same inputs reproduce
        # the accepted prefix exactly (column independence makes the
        # surviving columns bitwise identical to the first pass).
        base_act = np.zeros(self.width, bool)
        base_act[act_idx] = True
        st = start
        for s in range(steps):
            mask = jnp.asarray(base_act & (stop_at > s))
            st, _ = _pcg_block_step(self.matvec, self.precond, *st, mask)
        self.X, self.R, self.P, self.RZ = st
        return finished

    def run(self) -> None:
        """Advance until every loaded column is finished."""
        while self.active_columns:
            self.advance()


def _pcg_batched(A, B: jax.Array, *, precond=None, tol=1e-6,
                 maxiter: int = 300, check_every: int = 1):
    """Per-column PCG over an ``(n, k)`` block (the ``pcg`` 2-D path):
    loads every column into a :class:`BatchedPCG` of width k and drains it.
    ``tol`` may be scalar or ``(k,)``. Returns ``(X, iters, histories)``."""
    n, k = B.shape
    tols = np.broadcast_to(np.asarray(tol, np.float64), (k,))
    eng = BatchedPCG(A, n, k, precond=precond, maxiter=maxiter,
                     check_every=check_every, dtype=B.dtype)
    Bh = np.asarray(B)
    for j in range(k):
        eng.load(j, Bh[:, j], tol=float(tols[j]))
    eng.run()
    X = eng.solution()
    return X, eng.iters.copy(), list(eng.hist)
