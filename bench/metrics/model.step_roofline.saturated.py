"""Least time of the traced decode steps over their device time (%)."""
from harness import readers


def read(run):
    return readers.step_roofline(run)
