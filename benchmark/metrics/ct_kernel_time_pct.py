"""Device time of the fused partition-and-histogram kernel
(`wave_partition_hist...`, by the operation's own name) over device busy
time.  Nothing where the trace holds no such operation."""
from benchmark.files import load_module


def read(run):
    busy = (run.get("trace") or {}).get("busy_s")
    seconds = load_module("metrics", "ct_hist_roofline").kernel_seconds(run)
    if not busy or seconds is None:
        return None
    return 100.0 * seconds / busy
