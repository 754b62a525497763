"""The whole iteration's share of the chip's peak on a bundled store:
what `iter_mfu` reads (the least time for the histogram work of the
traced iterations plus 16 B a row for gradients, hessians and the score
update, over the traced window's own time), where `run["columns"]` is
the store's EFB GROUP count: one byte a group a row is the reference
algorithm's own store, so that is the algorithm's own work.  A run whose
trace holds no device operation reads as nothing."""
from benchmark.files import load_module


def read(run):
    if not (run.get("trace") or {}).get("busy_s"):
        return None
    return load_module("metrics", "iter_mfu").read(run)
