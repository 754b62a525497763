"""Device nanoseconds of the slab's move a row it moved: the seconds under
`wave_compact` (the mask, the sort, the row gather by chunks, the
chunks' transposes and their write into the slab) over the window
trees' summed ``slab_rows_moved`` a shard.  A program without the scope
or the counter, or a step with no slab (``pallas_ct``), reads as
nothing."""
from benchmark import phases


def read(run):
    module = phases.timers()
    if module is None or "wave_compact" not in module.SCOPES:
        return None
    seconds = (phases.device_seconds(run) or {}).get("wave_compact")
    total = phases.window_counters(run)
    if not seconds or not total or not total.get("slab_rows_moved"):
        return None
    shards = total.get("shards", 0) / len(run["trees"]) or 1
    return 1e9 * seconds / (total["slab_rows_moved"] / shards)
