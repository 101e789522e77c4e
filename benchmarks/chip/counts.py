"""Operations and bytes the algorithm needs, and the chip's peaks.

The counts follow the algorithm, not an implementation: tiles at the
ranks they are stored with, not at padded ladder widths, so padding cut
by a later change raises a roofline share, and any implementation of the
same sampling is judged on the same work.
"""

from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np

PEAKS = Path(__file__).resolve().parent / "peaks.json"
F32 = 4
# An f32 contraction at HIGHEST precision takes six bf16 passes on the MXU.
HIGHEST_PASSES = 6


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a kind not in the table is an error."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def rank_grid(ranks, nb: int) -> np.ndarray:
    """The packed lower-tile ranks as an (nb, nb) grid, ``R[i, j]`` for
    ``i > j`` and 0 elsewhere (tile (i, j) is packed at
    ``i (i - 1) / 2 + j``)."""
    R = np.zeros((nb, nb), np.int64)
    ii, jj = np.tril_indices(nb, -1)
    R[ii, jj] = np.asarray(ranks, np.int64)[ii * (ii - 1) // 2 + jj]
    return R


def lr_sample_work(ranks, column_iters, *, nb: int, b: int, s: int
                   ) -> tuple[float, float]:
    """Lower bounds on the FLOPs and HBM bytes of the left driver's
    ``lr_sample`` calls in one factorization: ``Y[i] = sum_{j<k} U_ij
    (V_ij^T W2_j)`` per live row tile ``i`` of column ``k``, per ARA
    iteration.

    ``ranks``: the factor's packed tile ranks (tile (i, j) of ``L``, final
    when column ``k > j`` samples it). ``column_iters[k]``: the ARA
    iterations of column ``k``, one call each. The driver does not record
    which row tiles are live in each iteration, so each row tile ``i`` is
    counted live for ``max(1, ceil(r_ik / s))`` iterations -- the least
    that ARA needs to reach rank ``r_ik`` in blocks of ``s`` samples --
    which bounds the bytes from below. Per live tile and iteration: read
    ``U_ij``, ``V_ij`` at rank ``r_ij`` for every ``j < k``, write
    ``Y[i]`` (b x s); per call: read ``W2_j`` (b x s) for every ``j < k``.
    FLOPs: ``4 b s r_ij`` per tile read.
    """
    R = rank_grid(ranks, nb)
    prefix = np.cumsum(R, axis=1) - R          # sum_{j<k} R[i, j]
    flops = bytes_ = 0.0
    for k in range(1, nb):
        rows = np.arange(k + 1, nb)
        if rows.size == 0:
            continue
        live = np.maximum(1, np.ceil(R[rows, k] / s))
        rsum = prefix[rows, k]
        bytes_ += float(np.sum(live * (2 * b * rsum + b * s))) * F32
        bytes_ += float(column_iters[k]) * k * b * s * F32
        flops += float(np.sum(live * 4 * b * s * rsum))
    return flops, bytes_


def roofline_share(flops: float, bytes_: float, seconds: float,
                   peak: dict, passes: int = HIGHEST_PASSES
                   ) -> float | None:
    """Percent of the roofline: the least time the chip could take (the
    larger of FLOPs over the f32 peak and bytes over HBM bandwidth) over
    the measured kernel time; None without a measured time."""
    if not seconds or seconds <= 0 or not math.isfinite(seconds):
        return None
    least = max(flops / (peak["bf16_flops_per_s"] / passes),
                bytes_ / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds
