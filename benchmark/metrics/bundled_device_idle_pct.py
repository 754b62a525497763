"""What `device_idle_pct` reads (100 x (1 - union of device-operation
intervals over the traced window)) in the bundled cell: one dispatch an
iteration of a few seconds."""
from benchmark.files import load_module


def read(run):
    return load_module("metrics", "device_idle_pct").read(run)
