"""Device time under `wave_partition` (split tables, row routing) and
`tree_commit` (histogram-cache and tree scatters, exact-order rollback)
over busy time."""
from benchmark import phases


def read(run):
    return phases.scope_pct(run, "wave_partition", "tree_commit")
