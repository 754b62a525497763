"""What `split_search_time_pct` reads (device time under `split_search`,
the bundle view cut out of it, over busy time) in the multiclass cell:
every wave of every class tree."""
from benchmark.files import load_module


def read(run):
    return load_module("metrics", "split_search_time_pct").read(run)
