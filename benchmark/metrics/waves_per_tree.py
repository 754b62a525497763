"""Launches of the wave histogram kernel in the traced window over the
trees grown there.  Nothing where the trace does not name the kernel."""


def read(run):
    trace = run.get("trace") or {}
    trees = len(run.get("trees") or [])
    if not trees or not trace.get("hist_kernel_launches"):
        return None
    return trace["hist_kernel_launches"] / trees
