"""Device time under `hist_allreduce` over busy time.  The chip runs one
stream of operations, so nothing hides behind the all-reduce: this share
is the exposed one."""
from benchmark.files import load_module


def read(run):
    seconds = load_module("metrics", "collective_ms").window_seconds(run)
    busy = (run.get("trace") or {}).get("busy_s")
    if seconds is None or not busy:
        return None
    return 100.0 * seconds / busy
