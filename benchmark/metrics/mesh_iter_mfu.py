"""The whole iteration's share of the mesh's peak: what `iter_mfu` reads
(the least time one chip could take for the window's histogram work and
16 B a row, over the window) over the `shards` the trees' records name,
since the work is the whole table's and the mesh has that many chips.
A program whose records carry no `shards`, and a run whose trace holds
no device operation (no chip, so no peak to take a share of), read as
nothing."""
from benchmark import phases
from benchmark.files import load_module


def read(run):
    n = len(run.get("trees") or [])
    total = phases.window_counters(run)
    if not total or not total.get("shards") \
            or not (run.get("trace") or {}).get("busy_s"):
        return None
    one_chip = load_module("metrics", "iter_mfu").read(run)
    return None if one_chip is None else one_chip / (total["shards"] / n)
