"""The wave histogram kernel's share of its roofline on a bundled store:
the least time the chip could take for the histogram work of the traced
iterations (shapes.histogram_work over the store's GROUP columns, counted
from the grown trees) over the device time of the histogram kernel that
ran, found by the operation's own name: the fused `wave_partition_hist...`
or the slab's `wave_histogram_pallas...`, whichever `auto` took.  Nothing
where the trace holds neither."""
from benchmark import shapes

KERNELS = ("wave_partition_hist", "wave_histogram_pallas")


def kernel_seconds(run):
    """Summed self time of the kernel's launches, or None."""
    ops = (run.get("trace") or {}).get("device_ops") or []
    times = [s for name, s in ops if name.startswith(KERNELS)]
    return sum(times) if times else None


def read(run):
    seconds = kernel_seconds(run)
    if not seconds or not run.get("trees"):
        return None
    nbytes, ops = shapes.histogram_work(run["trees"], run["columns"])
    least, _ = shapes.least_seconds(nbytes, ops,
                                    shapes.peaks_for(run["device_kind"]))
    return 100.0 * least / seconds
