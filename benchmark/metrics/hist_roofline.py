"""The histogram kernel's share of its roofline: the least time the chip
could take for the histogram work of the traced iterations (shapes.py,
counted from the grown trees) over the kernel's summed device time.
Nothing where the trace does not tell the histogram kernel apart."""
from benchmark import shapes


def read(run):
    trace = run.get("trace") or {}
    if not trace.get("hist_kernel_s") or not run.get("trees"):
        return None
    nbytes, ops = shapes.histogram_work(run["trees"], run["columns"])
    least, _ = shapes.least_seconds(nbytes, ops,
                                    shapes.peaks_for(run["device_kind"]))
    return 100.0 * least / trace["hist_kernel_s"]
