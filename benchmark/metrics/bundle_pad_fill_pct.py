"""The share of the kernels' one-hot that is not pad: the bins the EFB
groups hold (`group_bins_used`, the sum of their bin counts) over the
padded width the kernels multiply against (`group_bins_padded`, groups x
the bin pad), from the program's last `bundle` counter record (one a
learner built on a bundled dataset).  Nothing without a traced window or
from a program that records none."""
from benchmark import phases


def read(run):
    if not (run.get("trace") or {}).get("busy_s"):
        return None
    found = [r["fields"] for r in phases.records()
             if r["kind"] == "count" and r["name"] == "bundle"]
    if not found or not found[-1].get("group_bins_padded"):
        return None
    return 100.0 * found[-1]["group_bins_used"] \
        / found[-1]["group_bins_padded"]
