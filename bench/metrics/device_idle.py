"""Share of the measured window in which no op ran on the device."""


def read(run):
    from bench import trace

    t0, t1 = run.events.window
    return 100.0 * (1.0 - trace.busy_ns(run.events) / (t1 - t0))
