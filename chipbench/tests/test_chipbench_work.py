"""The required operations and bytes against hand counts."""

import pytest

from chipbench import peaks, work


def test_fwht_counts():
    # 4 columns of 8: 8 * log2(8) = 24 adds per column; 4*8 f32 in, out.
    w = work.fwht(4, 8)
    assert w.flops == 4 * 24
    assert w.bytes == 2 * 4 * 8 * 4


def test_wv_step_counts():
    # Per cell: agg, g, c2c, nmap, d2d (f32), streak (s32), frozen (1 byte)
    # read = 25; g, n_p, direction (f32), streak (s32), frozen written = 17.
    w = work.wv_step(2, 32)
    assert w.bytes == 64 * 42
    assert w.flops == 64 * 20
    # Magnitude schemes also read the deviation estimate (f32).
    assert work.wv_step(2, 32, magnitude=True).bytes == 64 * 46


def test_wv_iteration_counts():
    # g read+write 8, target 4, d2d 4, streak read+write 8, frozen 2.
    assert work.WV_ITERATION_BYTES_PER_CELL == 26
    w = work.wv_iteration(3, 32)
    assert w.bytes == 96 * 26
    assert w.flops == 2 * 3 * 32 * 5 + 96 * 20


def test_roofline_picks_the_larger_bound():
    w = work.Work(flops=2e12, bytes=1e9)
    t, bound = work.roofline_seconds(w, flops_per_s=1e12, bytes_per_s=1e9)
    assert (t, bound) == (2.0, "flops")
    t, bound = work.roofline_seconds(work.Work(1.0, 4e9), 1e12, 1e9)
    assert (t, bound) == (4.0, "bytes")
    assert work.roofline_share(w, 4.0, 1e12, 1e9) == pytest.approx(50.0)


def test_work_adds():
    assert work.fwht(1, 4) + work.ZERO == work.fwht(1, 4)
    assert (work.fwht(1, 4) + work.fwht(1, 4)).bytes == 2 * work.fwht(1, 4).bytes


def test_peaks_by_device_kind():
    v5e = peaks.chip_peaks("TPU v5 lite")
    assert (v5e.bf16_flops, v5e.hbm_bw) == (197e12, 819e9)
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.chip_peaks("TPU v9 imaginary")
