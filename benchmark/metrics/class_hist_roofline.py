"""What `bundled_hist_roofline` reads (the histogram work of the traced
trees over the store's G group bytes a row, against the kernel's device
time) in the multiclass cell, where the traced trees are every class
tree of the window."""
from benchmark.files import load_module


def read(run):
    return load_module("metrics", "bundled_hist_roofline").read(run)
