"""What `bundled_kernel_time_pct` reads (the histogram kernel that ran,
`wave_partition_hist...` or `wave_histogram_pallas...` by the operation's
own name, over device busy time) in the multiclass cell: the kernel's
launches of every class tree."""
from benchmark.files import load_module


def read(run):
    return load_module("metrics", "bundled_kernel_time_pct").read(run)
