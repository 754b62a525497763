"""The whole iteration's share of the chip's peak: the least time for the
histogram work plus 16 B a row for gradients, hessians and the score
update, over the traced window's own time.  It still bounds a claim once
a later PR has taken a kernel off the path."""
from benchmark import shapes


def read(run):
    if not run.get("window_s") or not run.get("trees"):
        return None
    nbytes, ops = shapes.iteration_work(run["trees"], run["columns"],
                                        run["rows"])
    least, _ = shapes.least_seconds(nbytes, ops,
                                    shapes.peaks_for(run["device_kind"]))
    return 100.0 * least / run["window_s"]
