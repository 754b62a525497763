"""Median of the per-iteration host times in the traced window, one sync an
iteration.  It stands beside ``iters_per_s`` and never replaces it."""
import statistics


def read(run):
    times = run.get("iter_seconds") or []
    return statistics.median(times) * 1e3 if times else None
