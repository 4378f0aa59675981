"""Model operations of the tokens processed in the window over the chip's bf16 peak (%)."""
from harness import readers


def read(run):
    return readers.mfu(run)
