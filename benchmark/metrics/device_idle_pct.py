"""100 x (1 - union of device-operation intervals over the traced window)."""


def read(run):
    trace = run.get("trace") or {}
    if not trace.get("window_s") or not trace.get("busy_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
