"""90th percentile, over every request due in the window, of the time from
its due time to its first token.  A request with no first token by the
drain limit counts as infinitely late."""

import numpy as np


def read(run):
    from bench import loop

    late = loop.first_token_lateness(run.rec, run.t0, run.t1, run.limit)
    if not len(late):
        return None
    return float(np.percentile(late, 90, method="higher")) * 1e3
