"""Mean sequences per decode step of the core LLM in the window."""
from harness import readers


def read(run):
    return readers.decode_batch(run)
