"""What `wave_slot_fill_pct` reads (committed splits over waves x W leaf
slots, from the grow loop's counters over the window's trees) on a
bundled store, where every wave visits every row: 254 of 384 is a tree of
12 waves, 254 of 416 one of 13, and a wave more is a launch more."""
from benchmark.files import load_module


def read(run):
    return load_module("metrics", "wave_slot_fill_pct").read(run)
