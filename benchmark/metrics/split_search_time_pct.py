"""Device time under the `split_search` scope (the frontier pick and the
packed best-split search over the 2W children of a wave) over busy time."""
from benchmark import phases


def read(run):
    return phases.scope_pct(run, "split_search")
