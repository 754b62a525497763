"""The whole iteration's share of the chip's peak in the tall, narrow
cell: what `iter_mfu` reads (the least time for the histogram work of the
traced iterations plus 16 B a row for gradients, hessians and the score
update, over the traced window's own time).  At 28 columns the 16 B a row
are a third of a full pass's bytes, so this share moves with the per-row
passes as much as with the kernel.  A run whose trace holds no device
operation (no chip, so no peak to take a share of) reads as nothing."""
from benchmark.files import load_module


def read(run):
    if not (run.get("trace") or {}).get("busy_s"):
        return None
    return load_module("metrics", "iter_mfu").read(run)
