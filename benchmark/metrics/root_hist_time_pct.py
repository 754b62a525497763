"""Device time under `root_histogram` (the XLA one-hot pass over every
row, once a tree, and the histogram cache it starts) over busy time."""
from benchmark import phases


def read(run):
    return phases.scope_pct(run, "root_histogram")
