"""The engine's host spans and its padded-row counter.

A ``jax.profiler`` trace of a few ``BatchingEngine`` steps holds the
``serve.*`` spans, nested as the engine documents them: ``serve.step``
around each step; inside it ``serve.admit`` per admission round (with
``serve.plan``, ``serve.prefill`` and the round's ``serve.readback``),
``serve.pages`` in paged mode, ``serve.decode`` and the step's
``serve.readback``.  ``prefill_rows`` counts ``num_slots x bucket`` per
prefill, and an active profiler changes nothing the engine serves.
"""
import glob

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro.configs.base import get_config
from repro.models.layers import Ctx, ExecCfg
from repro.models.model import model_specs
from repro.models.params import init_params
from repro.serve import BatchingEngine, Request

# 3 requests on 2 slots: the first round admits two, a later one the third
PROMPTS = ((1, 2, 3, 4, 5), (6, 7, 8), tuple(range(10, 19)))


@pytest.fixture(scope="module")
def model():
    cfg = get_config("granite_8b", reduced=True)
    ctx = Ctx(cfg, ex=ExecCfg(remat="none"))
    return ctx, init_params(model_specs(cfg), jax.random.PRNGKey(0))


def serve(model, page_size=None):
    """Serve ``PROMPTS`` to the end; returns the engine, its requests and
    the bucket width of each prefill call."""
    ctx, params = model
    eng = BatchingEngine(params, ctx, num_slots=2, max_len=32, page_size=page_size)
    widths = []
    prefill = eng._prefill

    def counted(params, cache, tokens, *rest):
        widths.append(tokens.shape[1])
        return prefill(params, cache, tokens, *rest)

    eng._prefill = counted
    reqs = [Request(uid=i, prompt=jnp.asarray(p, jnp.int32), max_new=4 + i)
            for i, p in enumerate(PROMPTS)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    return eng, reqs, widths


def traced(model, tmp_path, page_size=None):
    """``serve`` under a profiler trace; also returns the trace's
    ``serve.*`` spans as (name, start, end, args), in start order."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = serve(model, page_size)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = [
        (e.name, e.start_ns, e.end_ns, dict(e.stats))
        for plane in ProfileData.from_file(path).planes
        if plane.name == "/host:CPU"
        for line in plane.lines
        for e in line.events
        if e.name.startswith("serve.")
    ]
    return (*out, sorted(spans, key=lambda s: (s[1], -s[2])))


def named(spans, name):
    return [s for s in spans if s[0] == name]


def inside(child, parents):
    """The one span of ``parents`` that holds ``child``."""
    (p,) = [p for p in parents if p[1] <= child[1] and child[2] <= p[2]]
    return p


def test_spans_nest_as_documented(model, tmp_path):
    eng, reqs, widths, spans = traced(model, tmp_path)
    steps, admits = named(spans, "serve.step"), named(spans, "serve.admit")
    decodes, reads = named(spans, "serve.decode"), named(spans, "serve.readback")
    assert {s[0] for s in spans} == {"serve.step", "serve.admit", "serve.plan",
                                     "serve.prefill", "serve.decode",
                                     "serve.readback"}
    # one admission span per prefill call, one readback span per readback
    assert len(admits) == len(widths) == 2
    assert len(reads) == eng.readbacks == len(decodes) + len(admits)
    assert len(named(spans, "serve.plan")) == len(named(spans, "serve.prefill")) == 2
    # every step but the last (which finds nothing to do) decodes once
    assert len(steps) == len(decodes) + 1
    for s in admits + decodes:
        inside(s, steps)
    for name in ("serve.plan", "serve.prefill"):
        for s in named(spans, name):
            inside(s, admits)
    # the admission's readback is inside it; a decode's follows the decode
    for a in admits:
        assert sum(a[1] <= r[1] and r[2] <= a[2] for r in reads) == 1
    for d in decodes:
        step = inside(d, steps)
        assert [r for r in reads if d[2] <= r[1] and r[2] <= step[2]
                and not any(a[1] <= r[1] <= a[2] for a in admits)]


def test_admission_spans_name_their_requests(model, tmp_path):
    eng, reqs, widths, spans = traced(model, tmp_path)
    args = [s[3] for s in named(spans, "serve.admit")]
    assert [str(a["uids"]).split() for a in args] == [["0", "1"], ["2"]]
    assert [a["rows"] for a in args] == [2 * w for w in widths] == [2 * 8, 2 * 16]
    assert [a["tokens"] for a in args] == [5 + 3, 9]


@pytest.mark.parametrize("page_size", [None, 4])
def test_prefill_rows_count_every_slot_at_the_bucket(model, tmp_path, page_size):
    eng, reqs, widths, spans = traced(model, tmp_path, page_size)
    assert eng.prefill_rows == sum(eng.num_slots * w for w in widths)
    assert eng.prefill_tokens == sum(len(p) for p in PROMPTS)
    pages = named(spans, "serve.pages")
    if page_size is None:
        assert not pages
    else:  # one page mapping before each decode, inside its step
        assert len(pages) == len(named(spans, "serve.decode"))
        for p in pages:
            inside(p, named(spans, "serve.step"))


def test_a_profiler_changes_nothing_served(model, tmp_path):
    eng, reqs, widths, spans = traced(model, tmp_path)
    assert spans
    plain, plain_reqs, plain_widths = serve(model)
    assert [r.generated for r in reqs] == [r.generated for r in plain_reqs]
    assert (eng.readbacks, eng.prefill_tokens, eng.prefill_rows) == (
        plain.readbacks, plain.prefill_tokens, plain.prefill_rows)
    assert widths == plain_widths
