"""What `root_hist_time_pct` reads (device time under `root_histogram`,
the XLA one-hot pass over every row, over busy time) in the multiclass
cell: the pass runs once a class tree, seven times an iteration."""
from benchmark.files import load_module


def read(run):
    return load_module("metrics", "root_hist_time_pct").read(run)
