"""95th percentile of every gap between consecutive tokens of one request,
where the later token came back in the window.  A token's time is when
``step()`` returned it."""

import numpy as np


def read(run):
    from bench import loop

    g = loop.gaps_in(run.rec, run.t0, run.t1)
    return float(np.percentile(g, 95)) * 1e3 if len(g) else None
