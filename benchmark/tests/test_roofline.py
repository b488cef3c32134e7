"""Pins the bytes and operations of both cells' shapes, and the peaks."""

import pytest

from benchmark import roofline

V5E = "TPU v5 lite"

# (rows, features, num_leaves) -> passes, bytes per tree, ops per tree
SHAPES = {
    "criteo-share.train": ((13_281_250, 67, 255), 8,
                           8 * 13_281_250 * (67 + 16),
                           2 * 8 * 13_281_250 * 67 * 3),
    "cdn-window.retrain": ((20_000_000, 53, 31), 5,
                           5 * 20_000_000 * (53 + 16),
                           2 * 5 * 20_000_000 * 53 * 3),
}


@pytest.mark.parametrize("cell", sorted(SHAPES))
def test_bytes_and_ops_of_the_cells(cell):
    shape, passes, nbytes, ops = SHAPES[cell]
    assert roofline.passes_per_tree(shape[2]) == passes
    assert roofline.tree_bytes(*shape) == nbytes
    assert roofline.tree_ops(*shape) == ops


def test_criteo_tree_is_bytes_bound_on_the_v5e():
    peaks = roofline.peaks_for(V5E)
    least = roofline.least_seconds(13_281_250, 67, 255, 10, peaks)
    assert least["bound"] == "bytes"
    assert least["seconds"] == pytest.approx(10 * 8_818_750_000 / 819e9)
    assert least["ops_s"] == pytest.approx(10 * 42_712_500_000 / 197e12)


def test_peaks_table():
    peaks = roofline.peaks_for(V5E)
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["int8_ops_per_s"] == 393e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    assert peaks["hbm_bytes"] == 16 * 2**30
    assert peaks["source"]


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks_for("TPU v9 imaginary")
