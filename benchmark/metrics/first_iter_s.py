"""Host clock around the first ``update`` and the read of its score: the
compile, or the load from the compile cache, and one iteration."""


def read(run):
    return run["spans"].get("first_iter_s")
