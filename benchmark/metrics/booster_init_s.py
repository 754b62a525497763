"""Host clock around ``Booster(...)`` until the bin matrix and its transpose
are on the device: the paged upload, its concatenate and the transpose."""


def read(run):
    return run["spans"].get("booster_init_s")
