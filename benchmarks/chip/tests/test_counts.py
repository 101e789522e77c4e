"""The ``lr_sample`` work count against a hand count, and the peaks
table."""

import pytest

import counts


def test_lr_sample_work_hand_count():
    # nb=3, b=2: tiles (1,0), (2,0), (2,1) packed at 0, 1, 2.
    ranks = [2, 1, 3]
    iters = [5, 4, 0]
    # s=1, column 1: row 2 is live ceil(3/1)=3 iterations and reads
    # U, V of tile (2,0) at rank 1 (2*2*1 floats) and writes Y (2*1):
    # 3 * 6 floats; 4 calls read W2 of one tile column (2*1): 8 floats.
    flops, bytes_ = counts.lr_sample_work(ranks, iters, nb=3, b=2, s=1)
    assert bytes_ == (3 * 6 + 8) * 4
    assert flops == 3 * 4 * 2 * 1 * 1
    # s=2: row 2 live ceil(3/2)=2 iterations of 2*2*1 + 2*2 floats; W2 is
    # 2*2 floats per call.
    flops, bytes_ = counts.lr_sample_work(ranks, iters, nb=3, b=2, s=2)
    assert bytes_ == (2 * 8 + 4 * 4) * 4
    assert flops == 2 * 4 * 2 * 2 * 1


def test_rank_zero_rows_count_one_pass():
    flops, bytes_ = counts.lr_sample_work([0, 0, 0], [1, 1, 1], nb=3, b=4,
                                          s=2)
    # column 1, row 2: one pass, no factor tiles to read, Y written once;
    # W2 of one tile column read by the one call.
    assert flops == 0
    assert bytes_ == (4 * 2 + 1 * 1 * 4 * 2) * 4


def test_peaks_known_and_unknown_kind():
    p = counts.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")


def test_roofline_share_takes_the_larger_bound():
    peak = {"bf16_flops_per_s": 6e12, "hbm_bytes_per_s": 1e12}
    # bytes bound: 1e9 bytes at 1e12 B/s = 1 ms against 1e6 FLOP at 1e12
    assert counts.roofline_share(1e6, 1e9, 2e-3, peak) == pytest.approx(50)
    # FLOP bound: 4e9 FLOP at 1e12 FLOP/s (six passes) = 4 ms
    assert counts.roofline_share(4e9, 1e6, 8e-3, peak) == pytest.approx(50)
    assert counts.roofline_share(1.0, 1.0, 0.0, peak) is None
