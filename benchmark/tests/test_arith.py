"""The arithmetic kept with the benchmark."""

import math

import pytest

from benchmark import arith


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


def test_hist_kernel_flops_and_bytes():
    # 32 node-histograms a tree, 4 operand columns a node (g, h x hi, lo)
    want = 2.0 * 11_000_000 * 4 * 32 * 256 * 28
    assert arith.gbdt_hist_mxu_flops(11_000_000, 28, 256, 6) == want
    assert arith.gbdt_hist_scanned_bytes(11_000_000, 28, 6) == \
        6 * 11_000_000 * 36
    peaks = arith.peaks_for("TPU v5 lite")
    secs, bound = arith.roofline_seconds(
        want, arith.gbdt_hist_scanned_bytes(11_000_000, 28, 6), peaks)
    assert bound == "mxu"
    assert math.isclose(secs, want / 197e12)
    assert arith.roofline_seconds(1.0, 819e9, peaks) == (1.0, "hbm")


def test_ffm_rows_touched_at_criteo_widths():
    assert arith.ffm_rows_touched(1, 39) == 1_521
    assert arith.ffm_rows_touched(2_048, 39) == 3_115_008


def test_busbw_is_nccl_tests_formula():
    gib = 2 ** 30
    assert arith.busbw_bytes_per_s(4, gib, 1.0) == 1.5 * gib
    assert arith.busbw_bytes_per_s(2, gib, 0.5) == 2.0 * gib
    assert arith.busbw_bytes_per_s(1, gib, 1.0) == 0.0   # no wire at all
