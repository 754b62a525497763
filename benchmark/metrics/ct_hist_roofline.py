"""The fused partition-and-histogram kernel's share of its roofline: the
least time the chip could take for the histogram work of the traced
iterations (shapes.histogram_work, counted from the grown trees: the
algorithm's own count, which does not change with the kernel) over the
device time of the operations whose own name is the kernel's
(`wave_partition_hist...`).  Nothing where the trace holds no such
operation: a program that takes another kernel."""
from benchmark import shapes

KERNEL = "wave_partition_hist"


def kernel_seconds(run):
    """Summed self time of the kernel's launches, or None."""
    ops = (run.get("trace") or {}).get("device_ops") or []
    times = [s for name, s in ops if name.startswith(KERNEL)]
    return sum(times) if times else None


def read(run):
    seconds = kernel_seconds(run)
    if not seconds or not run.get("trees"):
        return None
    nbytes, ops = shapes.histogram_work(run["trees"], run["columns"])
    least, _ = shapes.least_seconds(nbytes, ops,
                                    shapes.peaks_for(run["device_kind"]))
    return 100.0 * least / seconds
