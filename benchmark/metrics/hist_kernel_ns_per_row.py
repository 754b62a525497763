"""Device nanoseconds of the wave histogram kernel a row it visited: the
seconds of the operations whose OWN name starts ``wave_histogram`` or
``wave_partition_hist`` (not the slice that reads the kernel's output,
whose text names it too) over the window trees' summed ``kernel_rows`` a
shard (the device seconds are a mean over the chips; under a mesh the
counter sums the shards').  A time a row: it does not move when a tree
takes an extra wave, nor when a neighbouring scope shrinks.  A trace
without the kernel, or a program without the counter, reads as nothing."""
from benchmark import phases

KERNELS = ("wave_histogram", "wave_partition_hist")


def read(run):
    ops = (run.get("trace") or {}).get("device_ops") or []
    seconds = sum(s for name, s in ops if name.startswith(KERNELS))
    total = phases.window_counters(run)
    if not seconds or not total or not total.get("kernel_rows"):
        return None
    shards = total.get("shards", 0) / len(run["trees"]) or 1
    return 1e9 * seconds / (total["kernel_rows"] / shards)
