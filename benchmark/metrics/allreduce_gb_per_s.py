"""Bytes one shard handed to the all-reduce in the window's trees (the
grow loop's own count, from the operands' shapes) over the device seconds
under `hist_allreduce`.  A rate and no share: peaks.json holds no ICI
peak.  A program that does not count `allreduce_bytes`, or one device,
reads as nothing."""
from benchmark import phases
from benchmark.files import load_module


def read(run):
    total = phases.window_counters(run)
    seconds = load_module("metrics", "collective_ms").window_seconds(run)
    if not total or not total.get("allreduce_bytes") or not seconds:
        return None
    return total["allreduce_bytes"] / seconds / 1e9
