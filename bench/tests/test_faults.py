"""A run whose timed path is broken underneath comes out not correct.
The look for a chip is skipped; everything else is a run at toy size."""
import time

import jax
import numpy as np
import pytest

from bench import run
from bench.tests import small


@pytest.fixture(autouse=True)
def fresh_steps():
    """Compiled engine steps are shared within a process; a fault planted in
    the traced code needs steps traced anew, and leaves none behind."""
    from repro.serve._engine import _engine_steps

    _engine_steps.cache_clear()
    yield
    _engine_steps.cache_clear()


def _run(name):
    res = run.run_cell(small.cell(name), 2**31 + 9, 1.5, False, small.PEAKS,
                       jax.devices()[:1], time.perf_counter())
    return res["correct"], res["check"]


@pytest.mark.parametrize("name", ["wtab-batch", "tl1-chat"])
def test_a_token_altered_where_it_is_produced(name, monkeypatch):
    from repro.serve._engine import BatchingEngine

    orig = BatchingEngine._check
    vocab = small.WIDTHS["vocab_size"]

    def altered(self, packed):
        arr = np.array(orig(self, packed))
        arr[0, 0] = (arr[0, 0] + 1) % vocab  # slot 0's token, every step
        return arr

    monkeypatch.setattr(BatchingEngine, "_check", altered)
    ok, got = _run(name)
    assert ok is False
    assert got["logit_gap"]["value"] > got["logit_gap"]["limit"]


@pytest.mark.parametrize("name", ["tl1-batch", "tl1-chat"])
def test_a_cache_write_left_out(name, monkeypatch):
    """The step returns the cache's keys and values unchanged."""
    from repro.serve import _cache

    for fn in ("_onehot_write", "_paged_write"):
        monkeypatch.setattr(_cache, fn, lambda buf, *a, **k: buf)
    ok, got = _run(name)
    assert ok is False
