"""Host clock around opening the pre-binned directory and ``Dataset.construct``."""


def read(run):
    return run["spans"].get("construct_s")
