"""Every output token that ``step()`` handed back in the window, over the
window's seconds.  Tokens of requests that did not finish count.  A
closed-loop window ends with the first step that returns after
``--seconds`` (``loop.closed``)."""


def read(run):
    return run.counters["output_tokens"] / run.window_s
