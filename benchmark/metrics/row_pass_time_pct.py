"""Device time under `gradients` and `score_update`, the per-row passes
outside the grow loop, over busy time."""
from benchmark import phases


def read(run):
    return phases.scope_pct(run, "gradients", "score_update")
