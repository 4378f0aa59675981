"""The benchmark harness: one general runner for every cell, found by the
names in ``BENCHMARK.json``."""
