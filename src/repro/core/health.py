"""Breakdown detection + bounded recovery for the TLR drivers (DESIGN.md
section 13; the failure-model layer of ISSUE 10).

The adaptive factorization is numerically live -- ranks, tolerances, and
diagonal conditioning interact at runtime -- so an indefinite diagonal
tile, a NaN produced mid-panel, or a rank overflow must surface as a
*policy decision* (retry, degrade, or raise), never as silent NaN factors.
H2OPUS-TLR leans on the same breakdown handling to factor ill-conditioned
covariance matrices at loose eps; the diagonal-shift escalation mirrors
the HODLR-GPU recovery of Chen & Martinsson (arXiv:2208.06290).

Three pieces live here, shared by both drivers:

* **Fused device-side flag reductions** (:func:`column_flags`): one jitted
  reduction per checked stage collapses "any non-finite panel entry",
  "any non-finite / non-positive pivot", and "any tile at the rank cap
  with err > eps" into a tiny vector, pulled to the host in a single
  transfer that rides the per-column sync the drivers already make.
  Inputs are bucket-padded (padding is zero, hence finite and inert), so
  the compiled-variant count stays O(log nb) -- the same shape discipline
  as the pipelines themselves. Zero-cost when ``CholOptions.check`` is
  off: the drivers never construct a monitor, exactly the ``obs``
  contract.

* **A bounded escalation policy** (:class:`RetryPolicy`, carried on
  ``CholOptions.retry``): diagonal jitter ``shift0 * growth**attempt`` on
  SPD breakdown, eps-loosening ``eps * eps_growth**attempt`` on rank
  overflow, per-tile densify as the last resort. The policy only *sizes*
  remedies; the drivers apply them (they own the pipelines).

* **Structured outcomes**: every remedy lands as a :class:`HealthEvent`
  in ``fact.stats["health"]`` (and, when telemetry records, as a
  cumulative ``obs.counter("health", ...)`` sample); exhausted retries
  raise :class:`FactorizationBreakdown` carrying a
  :class:`BreakdownReport` (column, stage, pivot index, every remedy
  attempted) instead of returning non-finite factors.
"""

from __future__ import annotations

import dataclasses
from typing import List, NoReturn, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs

__all__ = [
    "RetryPolicy", "HealthEvent", "BreakdownReport",
    "FactorizationBreakdown", "HealthMonitor", "column_flags",
]


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Bounded remedy schedule (frozen so ``CholOptions`` stays hashable).

    ``max_retries`` bounds *each* remedy ladder independently: up to
    ``max_retries`` jitter re-factors of a failing diagonal tile and up to
    ``max_retries`` eps-loosened ARA re-passes of an overflowing panel
    (then the densify fallback, if enabled). A tile whose truncation
    error still exceeds ``eps * eps_growth**max_retries`` after every
    remedy is a breakdown, not a silent degradation.
    """

    max_retries: int = 2
    shift0: float = 1e-8       # first jitter shift, relative to diag scale
    growth: float = 16.0       # jitter escalation per attempt
    eps_growth: float = 4.0    # eps loosening per rank-overflow retry
    densify: bool = True       # exact-sample + SVD fallback at the cap

    def shift(self, attempt: int) -> float:
        return self.shift0 * self.growth ** attempt

    def eps_at(self, eps: float, attempt: int) -> float:
        return eps * self.eps_growth ** attempt

    def eps_floor(self, eps: float) -> float:
        """The loosest tolerance any remedy is allowed to accept."""
        return eps * self.eps_growth ** self.max_retries


@dataclasses.dataclass
class HealthEvent:
    """One detection or remedy, as recorded in ``stats["health"]``."""

    kind: str                  # "spd_breakdown" | "nonfinite_panel" |
                               # "nonfinite_update" | "rank_overflow" | ...
    column: int
    stage: str                 # "diag" | "panel" | "update" | "final"
    remedy: str                # "jitter" | "eps_loosen" | "densify" |
                               # "clamp" | "accept" | "raise"
    attempt: int = 0
    detail: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class BreakdownReport:
    """What :class:`FactorizationBreakdown` carries instead of NaNs."""

    column: int
    stage: str
    reason: str
    pivot_index: Optional[int] = None
    remedies: List[str] = dataclasses.field(default_factory=list)
    events: List[HealthEvent] = dataclasses.field(default_factory=list)
    detail: dict = dataclasses.field(default_factory=dict)


class FactorizationBreakdown(RuntimeError):
    """Raised when every remedy in the :class:`RetryPolicy` is exhausted
    (or the failure is unrecoverable, e.g. non-finite panel output with
    healthy pivots). The factorization never returns partial or
    non-finite factors -- the report says what failed and what was tried.
    """

    def __init__(self, report: BreakdownReport):
        self.report = report
        where = f"column {report.column}" if report.column >= 0 \
            else "final scan"
        tried = ", ".join(report.remedies) if report.remedies else "none"
        super().__init__(
            f"factorization breakdown at {where} ({report.stage}): "
            f"{report.reason}; remedies attempted: {tried}")


# -- fused device-side flag reductions ----------------------------------------

# Flag vector layout (pulled host-side as one tiny transfer):
#   [0] non-finite entries across the scanned arrays (panel bases/factors)
#   [1] non-finite pivots
#   [2] min finite pivot (+inf when all pivots are non-finite)
#   [3] argmin of [2]
#   [4] tiles at the rank cap with err > eps (device-side overflow count;
#       0 when the caller computes overflow host-side instead)
N_FLAGS = 5


def _flags_body(pivots, tree, ranks, err, r_cap, eps):
    f64 = pivots.dtype
    leaves = jax.tree.leaves(tree)
    n_nonfinite = sum((jnp.sum(~jnp.isfinite(x)) for x in leaves),
                      jnp.zeros((), jnp.int32))
    pf = jnp.isfinite(pivots)
    n_bad_piv = jnp.sum(~pf)
    piv = jnp.where(pf, pivots, jnp.inf)
    if ranks is None:
        n_over = jnp.zeros((), jnp.int32)
    else:
        n_over = jnp.sum((ranks >= r_cap) & ~(err <= eps))
    return jnp.stack([
        n_nonfinite.astype(f64), n_bad_piv.astype(f64), jnp.min(piv),
        jnp.argmin(piv).astype(f64), n_over.astype(f64),
    ])


_flags_jit = jax.jit(_flags_body, static_argnames=())


def column_flags(pivots, arrays=(), *, ranks=None, err=None,
                 r_cap: int = 0, eps: float = 0.0,
                 read=np.asarray) -> np.ndarray:
    """One fused health reduction, pulled as a single (5,) host transfer
    (through ``read``, which a driver that counts its reads passes).

    ``pivots`` is the diagonal of the column's dense factor (Cholesky) or
    its LDL d-vector; ``arrays`` is a pytree of panel outputs to scan for
    non-finite entries (pass them bucket-padded so the compiled-variant
    count stays on the ladder). ``ranks`` / ``err`` (optional, device)
    enable the device-side rank-overflow count against ``r_cap`` /
    ``eps``; a NaN ``err`` counts as overflow (``~(err <= eps)``).
    """
    if ranks is None:
        flags = _flags_jit(pivots, tuple(jax.tree.leaves(arrays)),
                           None, None, 0, 0.0)
    else:
        flags = _flags_jit(pivots, tuple(jax.tree.leaves(arrays)),
                           ranks, err, jnp.asarray(r_cap),
                           jnp.asarray(eps, pivots.dtype))
    return read(flags)


# -- the monitor ---------------------------------------------------------------


class HealthMonitor:
    """Per-factorization event log + report builder.

    The drivers own the decisions (they hold the pipelines); the monitor
    records what happened, keeps cumulative counters (mirrored into
    ``obs.counter("health", ...)`` when telemetry records), and builds the
    :class:`BreakdownReport` when a driver gives up.
    """

    def __init__(self, policy: RetryPolicy, algo: str, nb: int):
        self.policy = policy
        self.algo = algo
        self.nb = nb
        self.events: List[HealthEvent] = []
        self.counters: dict[str, int] = {}
        self.columns_checked = 0

    def record(self, kind: str, column: int, stage: str, *, remedy: str,
               attempt: int = 0, **detail) -> HealthEvent:
        ev = HealthEvent(kind=kind, column=column, stage=stage,
                         remedy=remedy, attempt=attempt, detail=detail)
        self.events.append(ev)
        self.counters[kind] = self.counters.get(kind, 0) + 1
        if obs.enabled():
            # Cumulative samples: the last sample of the series is the
            # factorization's total (metrics_snapshot "counters" contract).
            obs.counter("health",
                        {k: float(v) for k, v in self.counters.items()})
        return ev

    def fail(self, column: int, stage: str, reason: str, *,
             pivot_index: Optional[int] = None, **detail) -> NoReturn:
        self.record(reason, column, stage, remedy="raise", **detail)
        col_events = [e for e in self.events if e.column == column]
        report = BreakdownReport(
            column=column, stage=stage, reason=reason,
            pivot_index=pivot_index,
            remedies=[e.remedy for e in col_events
                      if e.remedy not in ("raise", "accept")],
            events=col_events, detail=detail)
        raise FactorizationBreakdown(report)

    def summary(self) -> dict:
        """The ``stats["health"]`` record (DESIGN.md section 13)."""
        return {
            "events": [dataclasses.asdict(e) for e in self.events],
            "counters": dict(self.counters),
            "columns_checked": self.columns_checked,
            "policy": dataclasses.asdict(self.policy),
        }
