"""What `waves_per_tree` reads (trace events that name the histogram
kernel over the window's trees) in the multiclass cell, where the
window's trees are every class tree: under `pallas_ct` a wave more is a
launch more over every row."""
from benchmark.files import load_module


def read(run):
    return load_module("metrics", "waves_per_tree").read(run)
