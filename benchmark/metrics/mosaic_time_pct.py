"""Device time of the Mosaic (Pallas) custom calls over device busy time."""


def read(run):
    trace = run.get("trace") or {}
    if not trace.get("busy_s") or trace.get("mosaic_s") is None:
        return None
    return 100.0 * trace["mosaic_s"] / trace["busy_s"]
