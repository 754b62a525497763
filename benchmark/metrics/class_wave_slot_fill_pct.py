"""What `wave_slot_fill_pct` reads (committed splits over waves x W leaf
slots, from the grow loop's counters: one `tree` record a class tree) in
the multiclass cell: 254 of 384 is a tree of 12 waves, 254 of 416 one
of 13."""
from benchmark.files import load_module


def read(run):
    return load_module("metrics", "wave_slot_fill_pct").read(run)
