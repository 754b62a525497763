"""Host time inside ``Booster(...)`` that the upload itself takes: self
time of `host_copy`, `h2d`, `concat` and `transpose_xt` (calls, not the
device's work; the rest of `booster_init_s` is waiting for the device)."""
from benchmark import phases


def read(run):
    return phases.upload_host_s()
