"""Device time under `hist_allreduce` (the psum of the root's and of each
wave's histograms over the mesh) in one traced iteration, in ms: the
trace's self times are a mean over the chips already.  A program whose
grow table names no such instruction (one device, or no table for the
staged chain) reads as nothing."""
from benchmark import phases


def window_seconds(run):
    """Seconds under `hist_allreduce` in the whole traced window."""
    seconds = phases.device_seconds(run)
    if not seconds or "hist_allreduce" not in seconds:
        return None
    return seconds["hist_allreduce"]


def read(run):
    seconds = window_seconds(run)
    trees = len(run.get("trees") or [])
    if seconds is None or not trees:
        return None
    return 1e3 * seconds / trees
