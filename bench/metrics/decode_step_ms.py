"""Mean device time of one execution of the engine's decode step
(``jit_decode``) in the window."""


def read(run):
    from bench import trace

    ex = trace.executions(run.events).get("jit_decode", [])
    if not ex:
        return None
    return sum(e - s for s, e in ex) / len(ex) / 1e6
