"""Host seconds of the core LLM's continuous-loop phases that are not the
wait for the device (plan, prepare, launch, readback and emit, the
engine's ``<phase>_s`` counters), over the window's seconds (%). None
where the engine keeps no such counters."""

PHASES = ("plan_s", "prepare_s", "launch_s", "readback_s", "emit_s")


def read(run):
    w = run.window
    secs = w.t_close - w.t_open
    if secs <= 0 or not all(k in w.close_stats for k in PHASES):
        return None
    host = sum(w.close_stats[k] - w.open_stats[k] for k in PHASES)
    return 100.0 * host / secs
