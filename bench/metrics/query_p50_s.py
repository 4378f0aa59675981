"""Median latency of the window's queries, scheduled send to done (s)."""
from harness import readers


def read(run):
    return readers.percentile(readers.latencies(run), 50)
