"""Device time of the prefill programs' executions in the window, over the
prompt tokens the engine really prefilled (padding and shared pages are
not tokens prefilled).  The programs are the engine's jitted prefill
steps, ``jit_prefill`` (slot cache) and ``jit_prefill_paged``."""

MODULES = ("jit_prefill", "jit_prefill_paged")


def read(run):
    from bench import trace

    ex = trace.executions(run.events)
    ns = sum(e - s for m in MODULES for s, e in ex.get(m, []))
    tokens = run.counters["prefill_tokens"]
    if ns == 0 or tokens == 0:
        return None
    return ns / 1e6 / tokens
