"""Device time of the wave histogram kernel that ran on the bundled store
(`wave_partition_hist...` or `wave_histogram_pallas...`, by the
operation's own name) over device busy time.  Nothing where the trace
holds neither."""
from benchmark.files import load_module


def read(run):
    busy = (run.get("trace") or {}).get("busy_s")
    seconds = load_module("metrics",
                          "bundled_hist_roofline").kernel_seconds(run)
    if not busy or seconds is None:
        return None
    return 100.0 * seconds / busy
