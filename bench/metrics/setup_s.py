"""Process start to window open: weights, engines, compilation or cache
loads, warm-up and the traffic's ramp (s)."""


def read(run):
    return run.setup_s
