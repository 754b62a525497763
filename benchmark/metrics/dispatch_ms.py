"""Median of the program's `dispatch` spans in the window: host time
from the step's call until the asynchronous call returns."""
from benchmark import phases


def read(run):
    return phases.window_dispatch_ms(run)
