"""Rank-bucketed dynamic batching for the TLR hot paths (DESIGN.md section 8).

Every batched compute path of the tile algebra stores its low-rank factors
zero-padded to a single global ``r_max``, so a matrix whose tile ranks range
4-64 pays QR/SVD/GEMM FLOPs and HBM traffic as if every tile were rank 64.
This module is the TPU-friendly analogue of the paper's *dynamic batching*
(and of MAGMA's pointer marshaling in Boukaram et al., arXiv:1902.01829):
tiles are gathered into rank-homogeneous batches on a power-of-two *rank
ladder*, each bucket runs the batched kernels at its own (much narrower)
bucket width, and the results scatter back into the padded storage layout.

Shape discipline (the same contract as ``core/buckets.py``): both the rank
axis and the batch-count axis of every bucket are padded up power-of-two
ladders (the rounding dispatches step by 8x, ``ROUND_COUNTS``), so
at most ``~log2(r_max) * log2(nt)`` executables compile per kernel family
-- never one per rank distribution. The compile count is a
real, process-wide counter (``batching_trace_count()``) pinned by
``tests/test_batching.py``, mirroring ``algebra_trace_count`` /
``trsm_trace_count``.

Soundness rests on one storage invariant: factor columns past each tile's
``ranks`` entry are exactly zero (DESIGN.md section 1), so slicing a tile's
factors to any width >= its rank is *exact*, not an approximation -- the
error model of every rounding pass is unchanged. Tiles in the rank-0 bucket
are skipped entirely (no QR, no SVD, no phantom rank-1 regrowth; the PR 4
rank-floor semantics extend to the bucketed path).

The module also hosts the tile-batch sharding hook (ROADMAP "sharded tile
algebra"): ``set_tile_mesh(mesh)`` makes the embarrassingly-parallel
accumulation batches of ``tlr_gemm`` / ``tlr_syrk_column`` place their
leading (output-tile) axis across the mesh's data axes, with a no-mesh /
single-device fallback that is the identity.
"""

from __future__ import annotations

import dataclasses
from collections import OrderedDict
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .buckets import (_bucket_ladder, _bucket_up, _pad_axis, trace_count,
                      trace_event)
from ..kernels import ops
from ..launch.sharding import (set_tile_mesh,  # noqa: F401 (re-exported)
                               tile_batch_sharding, tile_dp_size, tile_mesh,
                               tile_mesh_mode)
from .. import obs


BATCHINGS = ("flat", "ranked", "auto")


def resolve_batching(batching: str | None, ranks=None, cap: int = 0) -> str:
    """Validate and resolve a ``batching`` knob up front
    (``CholOptions.batching``, the algebra entry points).

    ``"flat"`` is the compatibility path: one r_max-wide batch, exactly the
    pre-bucketing behavior. ``"auto"`` asks the rank-histogram policy to
    decide (DESIGN.md section 9) and therefore needs the per-tile ``ranks``
    (and their ``cap``); entry points that carry no rank information reject
    it here rather than silently falling back.
    """
    batching = batching or "flat"
    if batching not in BATCHINGS:
        raise ValueError(
            f"batching must be one of {BATCHINGS}, got {batching!r}")
    if batching == "auto":
        if ranks is None:
            raise ValueError(
                "batching='auto' needs the per-tile ranks to inspect; this "
                "entry point has none -- pass 'flat' or 'ranked' explicitly")
        return choose_batching(tile_plan(ranks, cap))
    return batching


# -- trace accounting ----------------------------------------------------------

# One entry per freshly compiled bucket-core variant, recorded in the unified
# keyed registry of ``core/buckets.py`` under the "batching" key. The python
# body of a jitted core runs exactly once per compile, so this is a real
# compile count: it must stay O(log2(r_max) * log2(nt)) per shape family and
# *never* scale with the number of tiles or with the rank distribution (the
# contract tests/test_batching.py pins, mirroring ``algebra_trace_count``).


def batching_trace_count() -> int:
    """Compiled rank-bucket core variants so far (process-wide); a view of
    ``trace_count("batching")`` in the unified registry."""
    return trace_count("batching")


# -- bucket planning (host side) -----------------------------------------------


def rank_ladder(cap: int) -> list[int]:
    """The power-of-two rank ladder [1, 2, 4, ..., cap]."""
    return _bucket_ladder(int(cap))


def bucket_width(ranks, cap: int, floor: int = 1) -> int:
    """Smallest ladder width covering every rank in ``ranks`` (host side).

    The "slice the whole stack" form of rank bucketing: a batched chain whose
    operand stack holds ranks 3-23 inside width-64 storage can run at ladder
    width 32 exactly (columns past each rank are zero). ``floor`` keeps
    degenerate all-zero stacks at a 1-wide batch instead of a 0-width array.
    """
    if cap <= 0:
        return 0
    rk = np.asarray(ranks)
    m = int(rk.max()) if rk.size else 0
    m = min(max(m, floor), int(cap))
    return _bucket_up(m, rank_ladder(cap))


@dataclasses.dataclass(frozen=True)
class RankBucket:
    """One rank-homogeneous batch: ``idx`` (host gather indices) of the
    tiles whose rank buckets up to ``width``; the batch count is padded up
    the count ladder to ``padded`` slots (trailing slots are zero tiles)."""

    width: int
    idx: np.ndarray
    count: int
    padded: int


@dataclasses.dataclass(frozen=True)
class BatchPlan:
    """Host-side dispatch plan: rank buckets plus the skipped rank-0 set."""

    n: int
    cap: int
    buckets: tuple[RankBucket, ...]
    zero_idx: np.ndarray

    @property
    def zero_count(self) -> int:
        return int(self.zero_idx.shape[0])


@dataclasses.dataclass(frozen=True)
class TilePlan(BatchPlan):
    """The reusable execution plan every batched path dispatches through
    (DESIGN.md section 9).

    Extends the rounding-only :class:`BatchPlan` with the per-tile data the
    *read* paths (TRSM, matvec, tri_matvec, sampling) need: a host snapshot
    of the ranks, the per-tile ladder width each rank buckets up to, and
    rank-histogram summaries the auto policy decides from. Computed once per
    operator/factorization generation through :func:`tile_plan` (memoized on
    the ranks array; a new ranks array -- every functional update makes one
    -- gets a new plan).
    """

    ranks_host: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(0, np.int64))
    widths: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(0, np.int64))

    @property
    def max_rank(self) -> int:
        return int(self.ranks_host.max(initial=0))

    @property
    def median_rank(self) -> float:
        """Median over the *positive* ranks (rank-0 tiles never touch a
        kernel, so they say nothing about useful batch width)."""
        live = self.ranks_host[self.ranks_host > 0]
        return float(np.median(live)) if live.size else 0.0

    @property
    def rank_skew(self) -> float:
        """max/median rank -- the histogram statistic the auto policy
        thresholds on (>= 4 means the flat r_max-wide batch pads most
        tiles by 4x or worse)."""
        med = self.median_rank
        return float(self.max_rank) / med if med > 0 else 1.0

    @property
    def max_width(self) -> int:
        """Smallest ladder width covering every rank (0 for all-zero)."""
        return int(self.widths.max(initial=0))

    def padded_cols(self) -> int:
        """Factor columns the ranked dispatch touches: sum of bucket-padded
        count x bucket width (count-ladder zero tiles included)."""
        return sum(bk.padded * bk.width for bk in self.buckets)

    def useful_cols(self) -> int:
        """Factor columns that actually carry data: sum of the ranks."""
        return int(self.ranks_host.sum())

    def flat_cols(self) -> int:
        """Factor columns the flat r_max-wide dispatch touches."""
        return self.n * self.cap

    def padded_flop_ratio(self) -> float:
        """Padded-vs-useful work of the flat path relative to the ranked
        one, for any kernel whose arithmetic is linear in the dispatched
        factor columns (the two-product read chains; QR is superlinear, so
        this is a floor for the rounding cores). Recorded in ``stats`` by
        the auto policy; >= 1, with 1.0 meaning bucketing cannot help."""
        ranked = self.padded_cols()
        return float(self.flat_cols()) / float(ranked) if ranked else 1.0

    def bucket_flops(self, b: int, r_out: int | None = None, *,
                     dtype=np.float64, impl: str | None = None) -> list[float]:
        """Per-bucket XLA ``cost_analysis`` FLOPs of the rounding cores at
        each bucket's true dispatch shapes (``round_dispatches``;
        ``kernels/ops.py::flop_estimate`` lowers + compiles, nothing
        executes; cached process-wide by shape). One entry per
        ``self.buckets`` element."""
        return [_bucket_round_flops(bk, b, r_out or b, dtype, impl)
                for bk in self.buckets]

    def flat_flops(self, b: int, r_out: int | None = None, *,
                   dtype=np.float64, impl: str | None = None) -> float:
        """The flat path's rounding-core FLOPs at the full (n, b, cap)
        dispatch shape -- the denominator of the measured (not analytic)
        padded-vs-useful ratio ``flat_flops / sum(bucket_flops)``."""
        if self.n == 0 or self.cap == 0:
            return 0.0
        return _round_core_flops(self.n, b, self.cap, min(r_out or b, b),
                                 dtype, impl)


def _flops_cache_key(n, b, w, r_out, dtype, impl):
    return (int(n), int(b), int(w), int(r_out), np.dtype(dtype).str, impl)


_ROUND_FLOPS_CACHE: dict[tuple, float] = {}


def _round_core_flops(n, b, w, r_out, dtype, impl) -> float:
    """``flop_estimate`` of the rank-bucket rounding core at one dispatch
    shape, cached process-wide (lower+compile once per shape, like the jit
    cache itself)."""
    key = _flops_cache_key(n, b, w, r_out, dtype, impl)
    hit = _ROUND_FLOPS_CACHE.get(key)
    if hit is not None:
        return hit
    from .algebra import _round_factors_impl

    U = jax.ShapeDtypeStruct((int(n), int(b), int(w)), np.dtype(dtype))
    eps = jax.ShapeDtypeStruct((), np.dtype(dtype))
    fl = ops.flop_estimate(
        partial(_round_factors_impl, r_out=int(r_out), rel=False, impl=impl),
        U, U, eps)
    _ROUND_FLOPS_CACHE[key] = fl
    return fl


def plan_rank_buckets(ranks, cap: int) -> TilePlan:
    """Group tile indices by ``bucket_up(rank)`` on the rank ladder.

    Runs on the host (the per-tile ranks are pulled once per dispatch, as
    the paper's dynamic batching orchestrates from the host). Rank-0
    tiles land in ``zero_idx`` and never touch a kernel. Prefer
    :func:`tile_plan`, which memoizes the result on the ranks array.
    """
    rk = np.asarray(ranks).astype(np.int64).reshape(-1)
    n = int(rk.shape[0])
    ladder = np.asarray(rank_ladder(cap), np.int64)
    cladder = _bucket_ladder(n)
    zero = rk <= 0
    zero_idx = np.nonzero(zero)[0].astype(np.int32)
    buckets = []
    widths = np.zeros(n, np.int64)
    if n and ladder.size:
        pos = np.searchsorted(ladder, np.clip(rk, 1, int(ladder[-1])))
        pos = np.minimum(pos, ladder.size - 1)
        widths = np.where(zero, 0, ladder[pos])
        for p in sorted(set(pos[~zero].tolist())):
            idx = np.nonzero((pos == p) & ~zero)[0].astype(np.int32)
            cnt = int(idx.shape[0])
            buckets.append(RankBucket(width=int(ladder[p]), idx=idx,
                                      count=cnt,
                                      padded=_bucket_up(cnt, cladder)))
    return TilePlan(n=n, cap=int(cap), buckets=tuple(buckets),
                    zero_idx=zero_idx, ranks_host=rk, widths=widths)


# -- plan memoization (one plan per operator/factorization generation) ---------

_PLAN_CACHE: OrderedDict[tuple[int, int], tuple] = OrderedDict()
_PLAN_CACHE_SIZE = 32


def _ranks_fingerprint(ranks) -> tuple | None:
    """Cheap content checksum for *mutable* host rank arrays (the
    right-looking driver's ``tile_w`` is updated in place); device arrays
    are immutable, so identity alone is a sound cache key for them."""
    if isinstance(ranks, np.ndarray):
        rk = ranks.reshape(-1)
        return (int(rk.shape[0]), int(rk.sum()), int(rk.max(initial=0)))
    return None


def tile_plan(ranks, cap: int, read=np.asarray) -> TilePlan:
    """The memoized :class:`TilePlan` for this ranks array at this cap.

    Keyed on the *identity* of the ranks array (plus a content checksum for
    host arrays, which unlike device arrays can mutate in place): every
    functional update of a ``TLRMatrix`` builds a new ranks array, so a new
    operator/factorization generation invalidates its plan automatically,
    while repeated reads (every matvec of a PCG loop, every TRSM of a
    multi-solve) reuse the plan without re-pulling ranks to the host. The
    cache holds strong references to the last ``_PLAN_CACHE_SIZE`` rank
    arrays, so an entry's ``id`` can never be recycled while it is live.
    A miss reads the ranks to the host through ``read``.
    """
    key = (id(ranks), int(cap))
    hit = _PLAN_CACHE.get(key)
    if hit is not None:
        ref, fp, plan = hit
        if ref is ranks and fp == _ranks_fingerprint(ranks):
            _PLAN_CACHE.move_to_end(key)
            return plan
        del _PLAN_CACHE[key]
    plan = plan_rank_buckets(read(ranks), cap)
    _PLAN_CACHE[key] = (ranks, _ranks_fingerprint(ranks), plan)
    while len(_PLAN_CACHE) > _PLAN_CACHE_SIZE:
        _PLAN_CACHE.popitem(last=False)
    return plan


# -- the auto policy (cost-model-driven knobs; DESIGN.md section 9) ------------

# "ranked" pays off when the flat r_max-wide batch mostly multiplies zeros:
# the decision statistic is the rank histogram's max/median (the ROADMAP
# heuristic), with >= 4 meaning a typical tile wastes 4x its useful width.
RANK_SKEW_RANKED = 4.0


def choose_batching(plan: TilePlan) -> str:
    """Histogram rule: "ranked" when max/median rank >= 4 and there is
    anything to bucket; "flat" otherwise (uniform ranks gain nothing from
    bucketing and the flat path has no gather/scatter marshaling)."""
    if plan.n == 0 or plan.max_rank == 0:
        return "flat"
    return "ranked" if plan.rank_skew >= RANK_SKEW_RANKED else "flat"


def _flush_fit(plan: TilePlan, b: int, dtype) -> int:
    """The most accumulated columns the right driver's two ``(nt, b,
    max(b, cap) + flush * cap)`` accumulation buffers can hold within a
    third of the device's memory (at least 1; no cap where the device
    reports no limit, as on the CPU)."""
    limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
    if not limit or not plan.cap or not plan.n:
        return 8
    col_bytes = 2 * plan.n * b * np.dtype(dtype).itemsize
    return max(1, (limit // 3 // col_bytes - max(b, plan.cap)) // plan.cap)


def resolve_policy(batching: str | None, plan: TilePlan, *, b: int,
                   dtype=np.float64, right_flush: int = 0) -> dict:
    """Resolve the ``batching`` / ``right_flush`` knobs against a plan and
    return the decision record the drivers put in ``stats["policy"]``.

    ``batching="auto"`` applies :func:`choose_batching`; explicit values
    pass through (the record still carries the histogram so the choice is
    auditable). ``right_flush=0`` means auto: flat keeps the tuned default
    of 2 accumulated columns between flushes, while ranked appends land at
    each tile's own bucket width (~the median width, not r_max), so the
    same accumulation window absorbs ~cap/median_width columns -- the
    cost-model estimate below picks the flush cadence that fills it. The
    auto cadence is capped so that the accumulation buffers take at most
    a third of the device's memory (:func:`_flush_fit`).
    """
    requested = batching or "auto"
    if requested not in BATCHINGS:
        raise ValueError(
            f"batching must be one of {BATCHINGS}, got {requested!r}")
    decision = choose_batching(plan) if requested == "auto" else requested
    med_w = _bucket_up(max(int(np.ceil(plan.median_rank)), 1),
                       rank_ladder(plan.cap)) if plan.cap else 1
    if right_flush:
        flush = max(1, int(right_flush))
    else:
        flush = max(2, min(8, plan.cap // max(med_w, 1))) \
            if decision == "ranked" else 2
        flush = min(flush, _flush_fit(plan, b, dtype))
    from ..launch.costmodel import tile_batch_cost

    est = tile_batch_cost([(bk.padded, bk.width) for bk in plan.buckets],
                          n=plan.n, b=b, cap=plan.cap,
                          itemsize=np.dtype(dtype).itemsize)
    return {
        "requested": requested,
        "batching": decision,
        "right_flush": flush,
        "rank_max": plan.max_rank,
        "rank_median": plan.median_rank,
        "rank_skew": plan.rank_skew,
        "bucket_widths": [bk.width for bk in plan.buckets],
        "padded_flop_ratio": plan.padded_flop_ratio(),
        **est,
    }


# -- jitted bucket cores -------------------------------------------------------


@partial(jax.jit, static_argnames=("r_out", "rel", "impl"))
def _round_bucket(U, V, eps, *, r_out: int, rel: bool, impl: str):
    """One rank bucket's recompression at its own width (<= b): batched QR
    of both factor stacks + small-SVD of the width x width core."""
    trace_event("batching")
    from .algebra import _round_factors_impl

    return _round_factors_impl(U, V, eps, r_out=r_out, rel=rel, impl=impl)


@partial(jax.jit, static_argnames=("r_out", "rel", "impl"))
def _densify_round_bucket(U, V, ranks, eps, *, r_out: int, rel: bool,
                          impl: str):
    """Bucket whose accumulated width exceeds the tile size: densify at the
    bucket width (cheaper *and* exact for b x b tiles), then compress."""
    trace_event("batching")
    from .algebra import _compress_dense_impl

    dense = ops.batched_gemm(U, jnp.swapaxes(V, 1, 2),
                             ranks.astype(jnp.int32), impl=impl)
    return _compress_dense_impl(dense, eps, r_out=r_out, rel=rel, impl=impl)


def _pad_width(x: jax.Array, width: int) -> jax.Array:
    if x.shape[-1] == width:
        return x
    pad = [(0, 0)] * x.ndim
    pad[-1] = (0, width - x.shape[-1])
    return jnp.pad(x, pad)


# Tile counts a rank-bucket rounding dispatch may take. A rounding core
# (batched QR + an XLA SVD) costs seconds to compile on a TPU for every
# distinct (count, width), so the counts step by 8x instead of following
# the count ladder: at most three compiled cores per width and output
# rank, and at most 8x zero padding. A bucket goes in chunks of the
# largest count; each chunk takes the smallest count that holds it.
ROUND_COUNTS = (1, 8, 64)


def round_dispatches(count: int) -> list[int]:
    """The tile count of each rounding dispatch for a bucket of ``count``
    tiles (zero tiles pad each dispatch up to its count)."""
    step = ROUND_COUNTS[-1]
    return [_bucket_up(min(step, count - lo), list(ROUND_COUNTS))
            for lo in range(0, count, step)]


def bucketed_round_tiles(U, V, ranks, eps, r_out=None, *, rel: bool = False,
                         impl=None, inplace: bool = False):
    """Rank-bucketed rounding pass: the ``batching="ranked"`` counterpart of
    ``tlr_round_tiles`` / the core of ranked ``tlr_round``.

    ``U`` / ``V`` are ``(N, b, W)`` factor stacks whose per-tile meaningful
    width is bounded by ``ranks`` (columns past it are zero -- the layout
    invariant; accumulated concatenations use the axpy width convention).
    Tiles are gathered into rank buckets, each bucket recompresses at its
    ladder width (factored QR + core SVD when the width fits the tile size,
    densify-then-compress above it), and results scatter back into one
    ``(N, b, r_out)`` output. Rank-0 tiles are skipped outright: their
    output is the zero factor pair at rank 0 with zero rounding error.

    ``inplace=True`` donates ``U`` / ``V`` and writes each result back into
    its own tile at the full width ``W`` (zero past the new rank), so no
    second pair of stacks is allocated; rank-0 tiles keep their content.
    A bucket goes in chunks of at most ``ROUND_COUNTS[-1]`` tiles, each
    zero-padded up to the next of ``ROUND_COUNTS`` (:func:`round_dispatches`),
    which also bounds the gathered working set.

    Returns ``(U, V, ranks, err)`` with identical truncation semantics to
    the flat pass -- parity is exact up to floating-point reduction order.
    """
    ops.resolve_impl(impl)  # validate; each op resolves its own default
    N, b, w_in = U.shape
    r_out = r_out or min(w_in, b)
    dtype = U.dtype
    if inplace:
        if r_out > w_in:
            raise ValueError(f"inplace rounding needs r_out <= {w_in}, "
                             f"got {r_out}")
        outU, outV = U, V
    else:
        outU = jnp.zeros((N, b, r_out), dtype)
        outV = jnp.zeros((N, b, r_out), dtype)
    out_ranks = jnp.zeros((N,), jnp.int32)
    out_err = jnp.zeros((N,), dtype)
    if N == 0:
        return outU, outV, out_ranks, out_err
    eps = jnp.asarray(eps, dtype)
    plan = tile_plan(ranks, w_in)
    ranks_d = jnp.asarray(plan.ranks_host, jnp.int32)
    if tile_mesh() is not None and not inplace:
        # End-to-end sharding: place the scatter bases so every bucket's
        # results land sharded over the mesh (the drivers' panel / flush
        # outputs inherit this placement), and each bucket's gathered
        # stack so the rounding cores themselves run data-parallel.
        outU, outV = shard_tile_batch(outU, outV, preserve_shape=True)
    for bk in plan.buckets:
        attrs = {}
        if obs.enabled():
            attrs = bucket_span_attrs(plan, bk, b, r_out, dtype, impl)
        with obs.span("round.bucket", cat="algebra", **attrs):
            step = ROUND_COUNTS[-1]
            for lo, cnt in zip(range(0, bk.count, step),
                               round_dispatches(bk.count)):
                # the chunk's indices padded up to the dispatch count; the
                # out-of-range pad slots gather zero tiles and scatter
                # nowhere
                sub = bk.idx[lo:lo + step]
                idx = np.full(cnt, N, np.int32)
                idx[:sub.shape[0]] = sub
                # in place, gather from the latest (donated) outputs
                src = (outU, outV) if inplace else (U, V)
                Ug, Vg, rg = _bucket_gather(*src, ranks_d, idx,
                                            width=bk.width)
                if tile_mesh() is not None:
                    Ug, Vg = shard_tile_batch(Ug, Vg, preserve_shape=True)
                if bk.width <= b:
                    Ub, Vb, rb, eb = _round_bucket(
                        Ug, Vg, eps, r_out=min(r_out, bk.width), rel=rel,
                        impl=impl)
                else:
                    Ub, Vb, rb, eb = _densify_round_bucket(
                        Ug, Vg, rg, eps, r_out=min(r_out, b), rel=rel,
                        impl=impl)
                outU, outV, out_ranks, out_err = _bucket_scatter(
                    outU, outV, out_ranks, out_err, idx, Ub, Vb, rb, eb)
    return outU, outV, out_ranks, out_err


@partial(jax.jit, static_argnames=("width",))
def _bucket_gather(U, V, ranks, idx, *, width: int):
    """One bucket's tiles at its ladder width; out-of-range ``idx`` slots
    gather zero tiles of rank 0. One program per (stack, bucket) shape
    instead of a gather, a slice and two pads per call. The slice follows
    the gather, so no width-cut copy of the whole stack is made."""
    def take(x):
        return jnp.take(x, idx, axis=0, mode="fill", fill_value=0)

    return take(U)[:, :, :width], take(V)[:, :, :width], take(ranks)


@partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _bucket_scatter(outU, outV, out_ranks, out_err, idx, Ub, Vb, rb, eb):
    """Scatter one bucket's results into the donated outputs (in place);
    out-of-range ``idx`` slots are dropped."""
    return (outU.at[idx].set(_pad_width(Ub, outU.shape[-1]), mode="drop"),
            outV.at[idx].set(_pad_width(Vb, outV.shape[-1]), mode="drop"),
            out_ranks.at[idx].set(rb.astype(out_ranks.dtype), mode="drop"),
            out_err.at[idx].set(eb.astype(out_err.dtype), mode="drop"))


def bucket_span_attrs(plan: TilePlan, bk: RankBucket, b: int, r_out: int,
                      dtype, impl) -> dict:
    """Telemetry attributes for one rank-bucket rounding (enabled mode
    only): the dispatched (``flops_padded``, cost_analysis at each
    dispatch's true shape, :func:`round_dispatches` -- width > b uses the
    densify path's shape, a close proxy) vs. useful (scaled by the
    bucket's true rank mass over its dispatched ``count x width`` slots)
    FLOPs, plus the HBM traffic of the gather + scatter marshaling."""
    counts = round_dispatches(bk.count)
    fl_pad = _bucket_round_flops(bk, b, r_out, dtype, impl)
    slots = sum(counts)
    useful = float(plan.ranks_host[bk.idx].sum())
    fl = fl_pad * useful / float(slots * bk.width)
    itemsize = np.dtype(dtype).itemsize
    nbytes = 2 * (slots * b * bk.width + bk.count * b * r_out) * itemsize
    return {"width": bk.width, "count": bk.count, "padded": slots,
            "flops": fl, "flops_padded": fl_pad, "bytes": nbytes}


def _bucket_round_flops(bk: RankBucket, b: int, r_out: int, dtype,
                        impl) -> float:
    """cost_analysis FLOPs of one bucket's rounding dispatches."""
    return sum(_round_core_flops(cnt, b, min(bk.width, b),
                                 min(r_out, bk.width), dtype, impl)
               for cnt in round_dispatches(bk.count))


# -- tile-batch sharding hook (ROADMAP: sharded tile algebra) ------------------
# The installed mesh itself (set_tile_mesh / tile_mesh) is kept in
# launch/sharding.py, below this module and kernels/ops.py.


def pad_tile_batch(n: int) -> int:
    """Smallest batch count >= ``n`` divisible by the installed mesh's DP
    size (``n`` itself without a mesh). The drivers size their persistent
    tile-batch buffers with this so every sharded dispatch divides."""
    dp = tile_dp_size()
    return int(-(-n // dp) * dp) if n else n


def shard_tile_batch(*arrays, preserve_shape: bool = False):
    """Place each array's leading (tile-batch) axis across the installed
    mesh's data axes (``launch/sharding.py``); identity when no mesh is
    set -- the single-device fallback.

    The accumulation batches of ``tlr_gemm`` / ``tlr_syrk`` /
    ``tlr_syrk_column`` are embarrassingly parallel over output tiles, so
    sharding their inputs lets XLA keep the whole batched update local to
    each shard (one batched call per column, no cross-tile dependencies).

    When the axis does not divide the mesh's DP size, the installed
    ``on_indivisible`` mode decides (see :func:`set_tile_mesh`): ``"pad"``
    zero-pads the leading axis up to the next multiple (callers must be
    index-driven or slice back -- the tile algebra's gathers never touch
    the pad slots), ``"error"`` raises. ``preserve_shape=True`` marks call
    sites whose output shape must match the input (persistent driver
    state, scatter bases): they shard when divisible and replicate
    otherwise under ``"pad"``; ``"error"`` still raises.
    """
    mesh = tile_mesh()
    if mesh is None:
        return arrays[0] if len(arrays) == 1 else arrays
    dp = tile_dp_size()
    mode = tile_mesh_mode()
    out = []
    for x in arrays:
        n = int(x.shape[0])
        if dp > 1 and n % dp != 0:
            if mode == "error":
                raise ValueError(
                    f"tile-batch axis of size {n} does not divide the "
                    f"mesh's data-parallel size {dp} "
                    f"(mesh {dict(mesh.shape)}); pad the batch to a "
                    f"multiple of {dp} (see pad_tile_batch) or install "
                    f"the mesh with on_indivisible='pad'")
            if not preserve_shape:
                x = _pad_axis(x, pad_tile_batch(n))
        sh = tile_batch_sharding(mesh, int(x.shape[0]), x.ndim)
        out.append(x if sh is None else jax.device_put(x, sh))
    return out[0] if len(out) == 1 else tuple(out)
