"""The softmax gradients' share of their roofline: the least time the
chip could take for what they have to move, over the device seconds under
the scope `gradients`.  A row reads its k scores (4k B) and its label
(4 B) and writes k gradients and k hessians (8k B): N x (12k + 4) B an
iteration, at the chip's bandwidth (a few operations a byte: the bytes
bind).  Nothing where the trace names no `gradients` time."""
from benchmark import phases, shapes


def gradient_bytes(rows, classes):
    """Bytes the softmax gradients of one iteration have to move."""
    return rows * (12 * classes + 4)


def read(run):
    seconds = (phases.device_seconds(run) or {}).get("gradients")
    iterations = len(run.get("iter_seconds") or [])
    if not seconds or not iterations or not run.get("classes"):
        return None
    nbytes = iterations * gradient_bytes(run["rows"], run["classes"])
    least = nbytes / shapes.peaks_for(run["device_kind"])["bytes_per_s"]
    return 100.0 * least / seconds
