"""The load generator's clock semantics, on a stand-in engine and a fake
clock: whole steps in a closed window, due times in an open one."""
import numpy as np
import pytest

from bench import loop, traffic


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


class FakeEngine:
    """Admits queued requests into free slots, then hands back one token
    per active request; each step advances the clock by ``step_s``, an
    admission by ``admit_s`` more."""

    def __init__(self, clock, slots=2, step_s=0.1, admit_s=0.0):
        self.clock, self.step_s, self.admit_s = clock, step_s, admit_s
        self.queue, self.slots = [], [None] * slots
        self.prefill_tokens = 0

    def submit(self, req):
        self.queue.append(req)

    def step(self):
        for i, r in enumerate(self.slots):
            if r is None and self.queue:
                r = self.slots[i] = self.queue.pop(0)
                self.prefill_tokens += len(r.prompt)
                r.generated.append(1)
                self.clock.t += self.admit_s
        if all(r is None for r in self.slots):
            return False
        self.clock.t += self.step_s
        for i, r in enumerate(self.slots):
            if r is not None:
                if len(r.generated) < r.max_new:
                    r.generated.append(1)
                if len(r.generated) >= r.max_new:
                    r.done = True
                    self.slots[i] = None
        return True


def items(n, max_new=5, gap=0.0):
    return [traffic.Item(i, np.zeros(3, np.int32), max_new, i * gap) for i in range(n)]


def test_closed_window_holds_whole_steps():
    clock = Clock()
    eng = FakeEngine(clock, step_s=0.3)
    rec, (t0, t1) = loop.closed(eng, items(50), backlog=2, seconds=1.0, clock=clock)
    assert t1 - t0 == pytest.approx(1.2)  # 4 steps of 0.3 s: the first past 1.0
    # 2 first tokens at admission, then 2 per step
    assert loop.tokens_in(rec, t0, t1) == 2 + 2 * 4
    assert loop.prefilled_in(rec, t1) == 6
    # a request's first token (admission) and second (decode) come back
    # from the same step, so its first gap is 0
    assert sorted(loop.gaps_in(rec, t0, t1)) == pytest.approx([0, 0] + [0.3] * 6)


def test_open_loop_times_from_due_and_fails_what_never_starts():
    clock = Clock()
    eng = FakeEngine(clock, slots=1, step_s=0.5)
    rec, (t0, t1), limit = loop.open_(eng, items(4, max_new=3, gap=0.25), seconds=1.0,
                                      drain_s=0.6, clock=clock)
    late = loop.first_token_lateness(rec, t0, t1, limit)
    assert len(late) == 4  # all due in the window
    assert late[0] == pytest.approx(0.5)  # first token comes back with the step
    assert np.isinf(late).sum() >= 1  # one slot cannot reach all by the limit
    assert clock.t >= limit


def test_open_loop_sleeps_until_the_next_arrival():
    import time

    eng = FakeEngine(Clock(), slots=2, step_s=0.0)
    t = time.perf_counter()
    rec, (t0, t1), _ = loop.open_(eng, items(2, max_new=1, gap=0.2), seconds=0.3,
                                  drain_s=1.0)
    assert time.perf_counter() - t >= 0.2
    assert [s.due - t0 for s in rec.sent] == pytest.approx([0.0, 0.2])
