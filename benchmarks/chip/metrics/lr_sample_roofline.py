"""Share of its roofline that the ``lr_sample`` Pallas kernel reached in
the traced factorization, in percent: the least time the chip could take
for the work the algorithm needs (``counts.lr_sample_work``, a lower
bound at the stored tile ranks) over the kernel's device time in the
trace (ops named ``lr_sample_pallas``). Bandwidth bounds it: about
``s r / (2 r + s) <= 8`` FLOP per byte at ``s = 16``. Moves
``factor_s``."""

import counts

MOVES = "factor_s"


def read(r):
    if r.trace is None or r.factor_ranks is None or not r.factor_stats:
        return None
    seconds = r.trace.kernel_seconds("lr_sample_pallas")
    if seconds <= 0:
        return None
    shape = r.factor_shape
    flops, bytes_ = counts.lr_sample_work(
        r.factor_ranks, r.factor_stats[-1]["column_iters"],
        nb=shape["nb"], b=shape["b"], s=shape["bs"])
    return counts.roofline_share(flops, bytes_, seconds,
                                 counts.peaks(r.device_kind))
