"""Device time of operations the step program's scope table does not name,
over busy time: what the scopes lost to a refactor shows here first."""
from benchmark import phases


def read(run):
    return phases.scope_pct(run, "unscoped")
