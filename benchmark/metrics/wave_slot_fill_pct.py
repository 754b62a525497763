"""Committed splits over the leaf slots the histogram kernel was launched
for (waves x W), from the grow loop's own counters, over the window's
trees."""
from benchmark import phases


def read(run):
    return phases.counter_pct(run, "committed", "slots")
