"""Share of the queries' latency covered by their core-LLM primitives (%)."""
from harness import readers


def read(run):
    return readers.llm_share(run)
