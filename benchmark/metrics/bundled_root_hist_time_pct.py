"""What `root_hist_time_pct` reads (device time under `root_histogram`,
the XLA one-hot pass over every row once a tree, over busy time) on a
bundled store: there the pass multiplies against the groups' 256-bin
width and is the cell's second bottleneck."""
from benchmark.files import load_module


def read(run):
    return load_module("metrics", "root_hist_time_pct").read(run)
