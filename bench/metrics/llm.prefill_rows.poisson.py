"""Prefill chunks per chunk step of the core LLM in the window (seqs):
the engine's ``prefill_rows`` over its ``prefill_passes`` counter, close
minus open. None where the engine keeps no such counters, or no chunk
step landed in the window."""


def read(run):
    w = run.window
    keys = ("prefill_rows", "prefill_passes")
    if not all(k in w.close_stats for k in keys):
        return None
    rows, passes = (w.close_stats[k] - w.open_stats[k] for k in keys)
    return float(rows / passes) if passes > 0 else None
