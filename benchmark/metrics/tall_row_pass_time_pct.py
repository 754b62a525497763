"""What `row_pass_time_pct` reads (device time under `gradients` and
`score_update`, the per-row passes outside the grow loop, over busy time)
in the tall, narrow cell: there a row's 16 B of per-row state weigh half
as much as its 28 B of bins, and the score update is the Pallas
compare-select kernel."""
from benchmark.files import load_module


def read(run):
    return load_module("metrics", "row_pass_time_pct").read(run)
