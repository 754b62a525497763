"""What `split_search_time_pct` reads (device time under `split_search`
over busy time) on a bundled store.  Since the program declares
`bundle_view` an inner scope the search's share no longer holds the view
(`bundle_view_time_pct`); from a program that does not, it still does."""
from benchmark.files import load_module


def read(run):
    return load_module("metrics", "split_search_time_pct").read(run)
