"""The arithmetic kept with the benchmark."""

import math

import pytest

from benchmark import arith, arith_grow


def test_peaks_of_the_v5e_and_no_default():
    p = arith.peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["ici_bits_per_s"] == 1600e9
    assert "source" in p
    with pytest.raises(RuntimeError, match="no published peaks"):
        arith.peaks_for("TPU v9 imaginary")


def test_histogram_messages_of_a_depth_6_tree():
    assert arith.hist_level_nodes(6) == [1, 1, 2, 4, 8, 16]
    sizes = arith.hist_message_bytes(6, 28, 256)
    assert sizes[0] == 28 * 256 * 2 * 4 == 57_344
    assert sizes[-1] == 917_504
    assert sum(sizes) == 1_835_008


def test_a_needed_rows_flops_and_bytes():
    # 4 operand rows a needed row (g, h x hi, lo) against 256 one-hot
    # columns of each of 28 features; 28 bin bytes and g and h
    want = 2.0 * 4 * 256 * 28 * 11_000_000
    assert arith_grow.grow_hist_mxu_flops(11_000_000, 28, 256) == want
    assert arith_grow.grow_hist_scanned_bytes(11_000_000, 28) == \
        11_000_000 * 36
    peaks = arith.peaks_for("TPU v5 lite")
    secs, bound = arith.roofline_seconds(
        want, arith_grow.grow_hist_scanned_bytes(11_000_000, 28), peaks)
    assert bound == "mxu"
    assert math.isclose(secs, want / 197e12)
    # a needed row of the two tables at the v5e's peak: 0.291 and 10.06 ns
    assert math.isclose(secs / 11_000_000, 0.2911e-9, rel_tol=1e-3)
    assert math.isclose(arith_grow.grow_hist_mxu_flops(1, 968, 256) / 197e12,
                        10.06e-9, rel_tol=1e-3)
    assert arith.roofline_seconds(1.0, 819e9, peaks) == (1.0, "hbm")
    assert not hasattr(arith, "gbdt_hist_mxu_flops")


def test_ffm_rows_touched_at_criteo_widths():
    assert arith.ffm_rows_touched(1, 39) == 1_521
    assert arith.ffm_rows_touched(2_048, 39) == 3_115_008


def test_busbw_is_nccl_tests_formula():
    gib = 2 ** 30
    assert arith.busbw_bytes_per_s(4, gib, 1.0) == 1.5 * gib
    assert arith.busbw_bytes_per_s(2, gib, 0.5) == 2.0 * gib
    assert arith.busbw_bytes_per_s(1, gib, 1.0) == 0.0   # no wire at all
