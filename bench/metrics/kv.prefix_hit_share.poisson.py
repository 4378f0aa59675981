"""Prompt tokens the core LLM forked from its radix prefix cache over the
prompt tokens its prefill tasks brought, in the window (%): the engine's
``radix_hit_tokens`` and ``prompt_tokens`` counters, close minus open.
None where the engine keeps no ``prompt_tokens`` counter."""


def read(run):
    w = run.window
    keys = ("radix_hit_tokens", "prompt_tokens")
    if not all(k in w.close_stats for k in keys):
        return None
    hits, prompt = (w.close_stats[k] - w.open_stats[k] for k in keys)
    return 100.0 * hits / prompt if prompt > 0 else None
