"""Step calls of the core LLM in the window on a (jitted step, argument
shapes) pair it had not run before: the engine's ``new_shapes`` counter,
close minus open. None where the engine keeps no such counter."""


def read(run):
    w = run.window
    if "new_shapes" not in w.close_stats:
        return None
    return float(w.close_stats["new_shapes"] - w.open_stats["new_shapes"])
