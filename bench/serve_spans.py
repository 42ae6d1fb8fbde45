"""The program's own host spans in a profiler trace, read beside the
reduction of ``bench/trace.py``.

``repro.serve.BatchingEngine`` marks its host work with ``serve.*`` spans
(``src/repro/serve/README.md``, Tracing): ``serve.step`` around each step
and, nested inside it on the calling thread, ``serve.admit`` (one per
admission round, with its ``rows``, ``tokens`` and ``uids``),
``serve.plan``, ``serve.prefill``, ``serve.pages``, ``serve.decode`` and
``serve.readback``.  They lie on the host plane, on the device's clock.
From them and a :class:`bench.trace.Events` of the same trace come the
readings below, each over the window of the harness's ``bench.window``
span.  A trace of a program without the spans gives None, or the
``bench.*`` split alone.

``bench/run.py`` does not read these yet: it removes the trace before its
readers run, and its ``Events`` keeps the ``bench.*`` spans only.
"""
from __future__ import annotations

import bisect
import collections
import gzip

from bench import trace


def load(path: str) -> list[tuple]:
    """The ``serve.*`` spans of ``path`` (an ``.xplane.pb`` or a gzipped
    one), each ``(name, start_ns, end_ns, args)``."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.end_ns, dict(e.stats))
            for plane in pd.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events
            if e.name.startswith("serve.")]


class Nest:
    """Which of a set of spans holds a point in time: the innermost that
    covers it.  The spans come from one thread, so any two either nest or
    follow one another."""

    def __init__(self, spans):
        self.spans = sorted(spans, key=lambda s: (s[1], -s[2]))
        self.starts = [s[1] for s in self.spans]
        self.parent, stack = [], []
        for i, s in enumerate(self.spans):
            while stack and self.spans[stack[-1]][2] <= s[1]:
                stack.pop()
            self.parent.append(stack[-1] if stack else -1)
            stack.append(i)

    def at(self, t):
        """The name of the innermost span covering ``t``, or None."""
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0 and self.spans[i][2] <= t:
            i = self.parent[i]
        return self.spans[i][0] if i >= 0 else None


def idle_by_inner_span(ev: trace.Events, serve: list, n: int = 10,
                       device: int = 0) -> list[list]:
    """Idle seconds in the window, by the innermost span that held the host
    at each gap's midpoint: a ``serve.*`` span where one does, else the
    ``bench.*`` span as :func:`bench.trace.idle_by_span` finds it, else
    ``none``."""
    nests = (Nest(serve), Nest(s for s in ev.spans if s[0] != trace.WINDOW_SPAN))
    tot: dict[str, float] = collections.defaultdict(float)
    for s, e in trace.idle_gaps(ev, device):
        mid = (s + e) / 2
        name = next((x for x in (nest.at(mid) for nest in nests) if x), "none")
        tot[name] += e - s
    return [[k, v / 1e9] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def named(ev: trace.Events, serve: list, name: str) -> list[tuple]:
    """The spans called ``name`` that start in the window."""
    t0, t1 = ev.window
    return [s for s in serve if s[0] == name and t0 <= s[1] < t1]


def overlap_ns(a, b) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    out, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def cover_ns(ev: trace.Events, spans) -> tuple[float, float]:
    """Of the window's time inside the union of ``spans``: how much it
    holds, and how much of that some op ran in, averaged over devices."""
    t0, t1 = ev.window
    cover = trace.union((max(s[1], t0), min(s[2], t1)) for s in spans)
    busy = [overlap_ns(trace.union((s, e) for _, s, e in trace.clip(o, t0, t1)), cover)
            for o in ev.ops]
    return sum(e - s for s, e in cover), sum(busy) / len(busy)


def admit_share(ev: trace.Events, serve: list):
    """Device-busy time inside ``serve.admit`` spans over device-busy time
    in the window, in %."""
    admits = named(ev, serve, "serve.admit")
    if not admits:
        return None
    return 100.0 * cover_ns(ev, admits)[1] / trace.busy_ns(ev)


def step_idle_ms(ev: trace.Events, serve: list):
    """Device-idle time inside ``serve.step`` spans over the number of
    steps, in ms: how long each step leaves the device waiting on the
    host's planning, dispatch, readback and bookkeeping."""
    steps = named(ev, serve, "serve.step")
    if not steps:
        return None
    inside, busy = cover_ns(ev, steps)
    return (inside - busy) / len(steps) / 1e6


def prefill_pad_share(ev: trace.Events, serve: list):
    """Share of the token rows the admission prefills computed that were
    padding, in %: ``1 - tokens / rows`` over the ``serve.admit`` spans'
    arguments."""
    admits = [a for a in named(ev, serve, "serve.admit") if "rows" in a[3]]
    rows = sum(a[3]["rows"] for a in admits)
    if not rows:
        return None
    return 100.0 * (1.0 - sum(a[3]["tokens"] for a in admits) / rows)
