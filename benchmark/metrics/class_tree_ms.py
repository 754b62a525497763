"""The traced window's device busy milliseconds over the class trees it
grew (`run["trees"]`: one a class an iteration).  Nothing where the
trace holds no device operation or the window grew no tree."""


def read(run):
    busy = (run.get("trace") or {}).get("busy_s")
    trees = len(run.get("trees") or [])
    if not busy or not trees:
        return None
    return 1000.0 * busy / trees
