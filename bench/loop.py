"""Drives ``repro.serve.BatchingEngine`` through a traffic schedule and
records, for every request, when it was due and when ``step()`` handed
each of its tokens back.

Host spans (``jax.profiler.TraceAnnotation``) mark what the harness does:
``bench.wait`` (the generator sleeping until the next arrival),
``bench.submit``, ``bench.step`` (one ``engine.step()``) and
``bench.bookkeep`` (recording the tokens a step returned).  A traced run
attributes the device's idle gaps to them.  ``bench.window`` spans the
measured window itself.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np
from jax.profiler import TraceAnnotation

WINDOW_SPAN = "bench.window"  # from the window's opening to its close


@dataclasses.dataclass
class Sent:
    item: object  # traffic.Item
    req: object  # repro.serve.Request
    due: float  # host clock
    times: list = dataclasses.field(default_factory=list)  # per token


class Recorder:
    def __init__(self, prefilled: int = 0):
        self.sent: list[Sent] = []
        self._live: list[Sent] = []
        # (host time, engine.prefill_tokens) after each step; the first
        # entry is the counter as the window opens
        self.steps: list[tuple[float, int]] = [(float("-inf"), prefilled)]

    def add(self, s: Sent):
        self.sent.append(s)
        self._live.append(s)

    def note(self, now: float, prefilled: int):
        self.steps.append((now, prefilled))
        keep = []
        for s in self._live:
            n = len(s.req.generated)
            if n > len(s.times):
                s.times.extend([now] * (n - len(s.times)))
            if not s.req.done:
                keep.append(s)
        self._live = keep


def busy(engine) -> bool:
    return bool(engine.queue) or any(r is not None for r in engine.slots)


def _submit(engine, rec, item, due):
    from repro.serve import Request

    req = Request(uid=item.uid, prompt=item.prompt, max_new=item.max_new)
    engine.submit(req)
    rec.add(Sent(item, req, due))


def closed(engine, items, backlog: int, seconds: float, clock=time.perf_counter,
           on_close=None):
    """Keep ``backlog`` requests queued; returns the recorder and the window
    ``(t0, t1)``.  The window closes as the first step that ends ``seconds``
    or more after it opened returns, so it holds whole steps only: a rate
    over it does not swing with whether one long admission finished just
    before a fixed close or just after.  ``on_close`` runs as it closes."""
    rec = Recorder(engine.prefill_tokens)
    todo = iter(items)
    span = TraceAnnotation(WINDOW_SPAN)
    span.__enter__()
    t0 = clock()
    t1 = t0 + seconds
    now = t0
    while now < t1:
        with TraceAnnotation("bench.submit"):
            while len(engine.queue) < backlog:
                item = next(todo, None)
                if item is None:
                    break
                _submit(engine, rec, item, clock())
        with TraceAnnotation("bench.step"):
            stepped = engine.step()
        now = clock()
        with TraceAnnotation("bench.bookkeep"):
            rec.note(now, engine.prefill_tokens)
        if not stepped and not engine.queue:
            raise RuntimeError("the closed loop ran out of requests in the window")
    span.__exit__(None, None, None)
    if on_close is not None:
        on_close()
    return rec, (t0, now)


def open_(engine, items, seconds: float, drain_s: float, clock=time.perf_counter,
          on_close=None):
    """Submit each item at its due time; after the window, serve on until
    every request due in it is done or ``drain_s`` has passed.  Returns the
    recorder, the window ``(t0, t1)`` and the drain limit."""
    rec = Recorder(engine.prefill_tokens)
    span = TraceAnnotation(WINDOW_SPAN)
    span.__enter__()
    t0 = clock()
    t1 = t0 + seconds
    limit = t1 + drain_s
    due = [(t0 + it.offset_s, it) for it in items if it.offset_s < seconds]
    i = 0
    closed_ = False
    while True:
        now = clock()
        if now >= t1 and not closed_:
            closed_ = True
            span.__exit__(None, None, None)
            if on_close is not None:
                on_close()
        if now >= limit or (i == len(due) and not busy(engine)):
            break
        with TraceAnnotation("bench.submit"):
            while i < len(due) and due[i][0] <= now:
                _submit(engine, rec, due[i][1], due[i][0])
                i += 1
        if busy(engine):
            with TraceAnnotation("bench.step"):
                engine.step()
            now = clock()
            with TraceAnnotation("bench.bookkeep"):
                rec.note(now, engine.prefill_tokens)
        elif i < len(due):
            with TraceAnnotation("bench.wait"):
                time.sleep(max(0.0, due[i][0] - clock()))
    if not closed_:
        span.__exit__(None, None, None)
        if on_close is not None:
            on_close()
    return rec, (t0, t1), limit


def tokens_in(rec: Recorder, t0: float, t1: float) -> int:
    """Tokens handed back in the window ``[t0, t1]``."""
    return sum(int(np.sum((np.asarray(s.times) >= t0) & (np.asarray(s.times) <= t1)))
               for s in rec.sent)


def gaps_in(rec: Recorder, t0: float, t1: float) -> np.ndarray:
    """Gaps between consecutive tokens of one request, where the later
    token came in the window ``[t0, t1]``."""
    out = []
    for s in rec.sent:
        t = np.asarray(s.times)
        if len(t) > 1:
            g, end = np.diff(t), t[1:]
            out.append(g[(end >= t0) & (end <= t1)])
    return np.concatenate(out) if out else np.zeros(0)


def first_token_lateness(rec: Recorder, t0: float, t1: float, limit: float):
    """Seconds from due to first token of each request due in the window;
    ``inf`` where none came by ``limit``."""
    out = []
    for s in rec.sent:
        if t0 <= s.due < t1:
            ok = s.times and s.times[0] <= limit
            out.append(s.times[0] - s.due if ok else float("inf"))
    return np.asarray(out)


def prefilled_in(rec: Recorder, t1: float) -> int:
    """Prompt tokens the engine prefilled in steps that ended by ``t1``."""
    last = [p for t, p in rec.steps if t <= t1][-1]
    return last - rec.steps[0][1]


def admitted_in(rec: Recorder, t0: float, t1: float) -> int:
    """Prompt tokens of the requests whose first token came in the window:
    admission hands back the first token in the same step."""
    return sum(len(s.item.prompt) for s in rec.sent
               if s.times and t0 <= s.times[0] <= t1)
