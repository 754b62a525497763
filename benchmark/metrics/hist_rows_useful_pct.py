"""Rows whose histogram was built and kept (the smaller child of each
committed split) over the rows the passes visited (every row, once for the
root and once a wave), from the grow loop's own counters."""
from benchmark import phases


def read(run):
    return phases.counter_pct(run, "hist_rows", "rows_visited")
