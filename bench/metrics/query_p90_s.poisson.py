"""90th percentile latency of the window's queries, scheduled send to
done (s). Kept beside the median: with 51 queries in the window five lie
beyond it, and its spread between runs is too wide for a bound."""
from harness import readers


def read(run):
    return readers.percentile(readers.latencies(run), 90)
