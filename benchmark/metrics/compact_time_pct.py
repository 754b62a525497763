"""Device time under `wave_compact` (the row slab of a wave's smaller
children: the mask, the sort that carries the per-row operands, the row
gather and its transpose) over busy time.  A program without the scope
reads as nothing."""
from benchmark import phases


def read(run):
    module = phases.timers()
    if module is None or "wave_compact" not in module.SCOPES:
        return None
    return phases.scope_pct(run, "wave_compact")
