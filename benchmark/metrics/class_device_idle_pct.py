"""What `device_idle_pct` reads (100 x (1 - union of device-operation
intervals over the traced window)) in the multiclass cell: one dispatch
an iteration for the k class trees."""
from benchmark.files import load_module


def read(run):
    return load_module("metrics", "device_idle_pct").read(run)
