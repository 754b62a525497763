"""What `hist_kernel_ns_per_row` reads (the histogram kernel's device
seconds a row it visited, over the window trees' `kernel_rows`) in the
multiclass cell: the kernel's cost a row, which neither a wave more nor a
class skipped moves."""
from benchmark.files import load_module


def read(run):
    return load_module("metrics", "hist_kernel_ns_per_row").read(run)
