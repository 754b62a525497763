"""Waves whose histogram launch read the row slab in place of all N rows,
over the waves of the window's trees, from the grow loop's own counters.
A program that does not count `compacted` reads as nothing."""
from benchmark import phases


def read(run):
    total = phases.window_counters(run)
    if not total or "compacted" not in total or not total.get("waves"):
        return None
    return 100.0 * total["compacted"] / total["waves"]
