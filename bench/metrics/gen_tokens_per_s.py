"""Tokens the core LLM generated in the window, per second."""
from harness import readers


def read(run):
    return readers.gen_tokens_per_s(run)
