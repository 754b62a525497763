"""What the algorithm needs, counted from shapes and from the grown trees.

A tree's histogram work, with the subtraction trick the reference
documents, is one pass over the root's rows and, for each split, one over
the rows of its smaller child: each such row reads `columns` one-byte
bins and its gradient and hessian (8 B), and adds both into one cell a
column (2 adds).  The count is the algorithm's own, so it reads the same
under any wave width, kernel, precision or one-hot formulation, and as a
lower bound on what any of them moves it keeps a share of a roofline
under 100%.
"""
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(device_kind):
    """The chip's peaks from the benchmark's one table; an unknown kind is
    an error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError("no peaks for device kind %r in peaks.json"
                       % (device_kind,))
    return table[device_kind]


def histogram_rows(tree):
    """Rows a tree's histograms have to visit: the root's, and each
    split's smaller child's.  `tree` holds ``num_leaves``, ``left_child``,
    ``right_child``, ``leaf_count`` and ``internal_count``."""
    ni = int(tree["num_leaves"]) - 1
    if ni < 1:
        return 0

    def count(child):
        return int(tree["leaf_count"][~child] if child < 0
                   else tree["internal_count"][child])

    rows = int(tree["internal_count"][0])
    for node in range(ni):
        rows += min(count(int(tree["left_child"][node])),
                    count(int(tree["right_child"][node])))
    return rows


def histogram_work(trees, columns):
    """(bytes, operations) of the histogram work of `trees`."""
    rows = sum(histogram_rows(t) for t in trees)
    return rows * (columns + 8), 2 * rows * columns


def iteration_work(trees, columns, rows):
    """(bytes, operations) of whole iterations: the histogram work plus
    16 B a row for gradients, hessians and the score update."""
    b, ops = histogram_work(trees, columns)
    return b + 16 * rows * len(trees), ops


def least_seconds(nbytes, ops, peaks):
    """(seconds, which bound binds) on a chip with `peaks`."""
    by_bytes = nbytes / peaks["bytes_per_s"]
    by_ops = ops / peaks["flops_per_s"]
    return (by_bytes, "bytes") if by_bytes >= by_ops else (by_ops, "ops")
