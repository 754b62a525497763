"""The shape functions against hand-counted values."""
import numpy as np
import pytest

from benchmark import shapes

# three leaves: the root (100 rows) splits 70 | 30; the 70 splits 45 | 25
TREE = {"num_leaves": 3,
        "left_child": np.array([1, -1]), "right_child": np.array([-2, -3]),
        "leaf_count": np.array([45, 30, 25]),
        "internal_count": np.array([100, 70])}


def test_histogram_rows_of_a_three_leaf_tree():
    # the root's 100, the smaller child of each split: 30 and 25
    assert shapes.histogram_rows(TREE) == 100 + 30 + 25


def test_histogram_and_iteration_work():
    nbytes, ops = shapes.histogram_work([TREE, TREE], columns=10)
    assert nbytes == 2 * 155 * (10 + 8)
    assert ops == 2 * 2 * 155 * 10
    b2, o2 = shapes.iteration_work([TREE, TREE], columns=10, rows=100)
    assert b2 == nbytes + 2 * 16 * 100 and o2 == ops


def test_a_stump_has_no_histogram_work():
    assert shapes.histogram_rows({"num_leaves": 1}) == 0


def test_least_seconds_says_which_bound_binds():
    peaks = shapes.peaks_for("TPU v5 lite")
    assert peaks["flops_per_s"] == 197e12 and peaks["bytes_per_s"] == 819e9
    assert shapes.least_seconds(819e9, 1.0, peaks) == (1.0, "bytes")
    assert shapes.least_seconds(1.0, 197e12, peaks) == (1.0, "ops")


@pytest.mark.parametrize("kind", ["TPU v9", "cpu", "source"])
def test_an_unknown_device_kind_is_an_error(kind):
    with pytest.raises(KeyError):
        shapes.peaks_for(kind)
