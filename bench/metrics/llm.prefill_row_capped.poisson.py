"""Share of the core LLM's chunk steps in the window whose plan the cap
on chunks per pass ended while budget room was left and a prompt was
still waiting (%): the engine's ``prefill_row_capped`` over its
``prefill_passes`` counter, close minus open. None where the engine
keeps no such counters, or no chunk step landed in the window."""


def read(run):
    w = run.window
    keys = ("prefill_row_capped", "prefill_passes")
    if not all(k in w.close_stats for k in keys):
        return None
    capped, passes = (w.close_stats[k] - w.open_stats[k] for k in keys)
    return 100.0 * capped / passes if passes > 0 else None
