"""The program's ``serve.*`` spans read beside the trace reduction: on
events made by hand, on a trace recorded on a TPU v5e, and on a traced
CPU rehearsal of a cell."""
import os
import time

import jax
import pytest

from bench import run, serve_spans, trace
from bench.tests import small

MS = 1e6  # ns


def events_with_engine_spans():
    """Two engine steps inside the harness's spans, in ms: the first admits
    (a prefill that runs 4-19 while the host waits in the admission's
    readback) and decodes (21.5-38.5); the second only decodes (44-59)."""
    bench = [("bench.submit", 0, 1), ("bench.step", 1, 40), ("bench.bookkeep", 40, 42),
             ("bench.step", 42, 60), ("bench.bookkeep", 60, 62),
             (trace.WINDOW_SPAN, 0, 62)]
    serve = [("serve.step", 1.2, 39.8), ("serve.admit", 1.5, 20),
             ("serve.plan", 1.5, 3), ("serve.prefill", 3, 4),
             ("serve.readback", 4, 19.5), ("serve.decode", 20.5, 21),
             ("serve.readback", 21, 39), ("serve.step", 42.2, 59.8),
             ("serve.decode", 42.5, 43), ("serve.readback", 43, 59.5)]
    mods = [("jit_prefill", 4, 19), ("jit_decode", 21.5, 38.5), ("jit_decode", 44, 59)]
    ms = lambda evs: [(n, s * MS, e * MS) for n, s, e in evs]  # noqa: E731
    args = {"serve.admit": {"rows": 2 * 16, "tokens": 5 + 3, "uids": "0 1"}}
    return (trace.Events([ms(mods)], [ms(mods)], ms(bench)),
            [(*s, args.get(s[0], {})) for s in ms(serve)])


def test_idle_goes_to_the_innermost_span():
    ev, serve = events_with_engine_spans()
    # gaps: 0-4 in serve.plan, 19-21.5 in serve.step between admission and
    # decode, 38.5-44 and 59-62 outside the engine's steps
    assert serve_spans.idle_by_inner_span(ev, serve) == [
        ["bench.bookkeep", pytest.approx(0.0085)],
        ["serve.plan", pytest.approx(0.004)],
        ["serve.step", pytest.approx(0.0025)],
    ]
    assert trace.idle_by_span(ev) == [["bench.bookkeep", pytest.approx(0.0085)],
                                      ["bench.step", pytest.approx(0.0065)]]
    # without the program's spans the split is the harness's
    assert serve_spans.idle_by_inner_span(ev, []) == trace.idle_by_span(ev)


def test_nest_finds_the_innermost_span_or_none():
    nest = serve_spans.Nest([("a", 0, 10), ("b", 2, 5), ("c", 3, 4), ("d", 6, 8)])
    assert [nest.at(t) for t in (1, 2.5, 3.5, 4.5, 5.5, 7, 10, -1)] == [
        "a", "b", "c", "b", "a", "d", None, None]


def test_engine_span_readings():
    ev, serve = events_with_engine_spans()
    busy = 15 + 17 + 15
    admit = serve_spans.admit_share(ev, serve)
    assert admit == pytest.approx(100 * 15 / busy)
    # the cross-check a chip run makes: the share of prefill executions
    prefill = sum(e - s for s, e in trace.executions(ev)["jit_prefill"])
    assert admit == pytest.approx(100 * prefill / trace.busy_ns(ev))
    # idle inside the steps: 1.2-4, 19-21.5, 38.5-39.8, 42.2-44, 59-59.8
    step_idle = serve_spans.step_idle_ms(ev, serve)
    assert step_idle == pytest.approx((2.8 + 2.5 + 1.3 + 1.8 + 0.8) / 2)
    # idle inside the steps and outside them make up the window's idle time
    t0, t1 = ev.window
    idle = t1 - t0 - trace.busy_ns(ev)
    between = [(0, 1.2 * MS), (39.8 * MS, 42.2 * MS), (59.8 * MS, t1)]
    outside = serve_spans.overlap_ns(trace.idle_gaps(ev), between)
    assert outside == pytest.approx((1.2 + 2.4 + 2.2) * MS)
    assert step_idle * 2 * MS + outside == pytest.approx(idle)
    assert serve_spans.prefill_pad_share(ev, serve) == pytest.approx(75.0)


def test_readings_are_silent_without_the_programs_spans():
    ev, _ = events_with_engine_spans()
    assert serve_spans.admit_share(ev, []) is None
    assert serve_spans.step_idle_ms(ev, []) is None
    assert serve_spans.prefill_pad_share(ev, []) is None


SPANS = os.path.join(os.path.dirname(__file__), "data", "tl1_batch_spans.xplane.pb.gz")


@pytest.fixture(scope="module")
def recorded():
    """``tl1-batch`` on one TPU v5e with the engine's spans: a window of
    1.4 s holding the first admission (a prefill of 8 x 64 rows) and one
    decode step."""
    return trace.load(SPANS), serve_spans.load(SPANS)


def test_recorded_engine_spans_nest(recorded):
    ev, serve = recorded
    assert [s[0] for s in sorted(serve, key=lambda s: s[1])] == [
        "serve.step", "serve.admit", "serve.plan", "serve.prefill",
        "serve.readback", "serve.decode", "serve.readback"]
    nest = serve_spans.Nest(serve)
    (ps, pe), = trace.executions(ev)["jit_prefill"]
    (ds, de), = trace.executions(ev)["jit_decode"]
    # the host waits in the admission's readback while the prefill runs, and
    # in the step's readback while the decode runs
    assert nest.at(ps + 1e6) == nest.at(pe - 1e6) == "serve.readback"
    assert nest.at(ds + 1e6) == "serve.readback"
    (admit,) = serve_spans.named(ev, serve, "serve.admit")
    assert admit[1] < ps < pe < admit[2] < ds
    assert admit[3] == {"rows": 8 * 64, "tokens": 208, "uids": "0 1 2 3 4 5 6 7"}


def test_recorded_engine_span_readings(recorded):
    ev, serve = recorded
    admit = serve_spans.admit_share(ev, serve)
    pre = trace.executions(ev)["jit_prefill"]
    exec_share = 100 * sum(e - s for s, e in pre) / trace.busy_ns(ev)
    assert admit == pytest.approx(93.918, abs=1e-3)
    assert abs(admit - exec_share) < 0.01
    assert serve_spans.step_idle_ms(ev, serve) == pytest.approx(5.503221)
    assert serve_spans.prefill_pad_share(ev, serve) == pytest.approx(
        100 * (1 - 208 / 512))
    # the idle that bench.step held, put down to the engine's spans: the
    # readback's return after each program, and the prefill's dispatch
    assert trace.idle_by_span(ev) == [["bench.step", pytest.approx(0.005919551)]]
    inner = serve_spans.idle_by_inner_span(ev, serve)
    assert [k for k, _ in inner] == ["serve.readback", "serve.prefill", "serve.decode"]
    assert inner[0][1] == pytest.approx(0.004090271)
    assert inner[1][1] == pytest.approx(0.001829266)
    assert sum(v for _, v in inner) == pytest.approx(0.005919551)


def test_traced_rehearsal_reads_the_engines_spans(monkeypatch):
    """A traced run of a cell on the CPU, as the reduction would read a
    chip's: its host spans, and one device busy while the host waits in
    each of the engine's readbacks (a CPU trace has no device plane)."""
    from jax.profiler import ProfileData

    kept = {}

    def host_spans_only(path):
        serve = serve_spans.load(path)
        bench = [(e.name, e.start_ns, e.end_ns)
                 for plane in ProfileData.from_file(path).planes
                 if plane.name == "/host:CPU" for line in plane.lines
                 for e in line.events if e.name.startswith("bench.")]
        ops = [("fusion.1", s, e) for n, s, e, _ in serve if n == "serve.readback"]
        ev = trace.Events([[]], [ops], bench)
        kept.update(ev=ev, serve=serve)
        return ev

    monkeypatch.setattr(trace, "load", host_spans_only)
    res, sent = run.measure(small.cell("tl1-batch"), 2**33 + 3, 1.0, True, small.PEAKS,
                            jax.devices()[:1], time.perf_counter())
    ev, serve = kept["ev"], kept["serve"]
    assert 0 < serve_spans.admit_share(ev, serve) < 100
    assert serve_spans.step_idle_ms(ev, serve) > 0
    # two slots at a bucket of 8 or 16 for 4-12-token prompts, often one
    # request a round
    assert 0 < serve_spans.prefill_pad_share(ev, serve) < 100
    # a lone uid comes back from the trace as a number
    admitted = {int(u) for a in serve_spans.named(ev, serve, "serve.admit")
                for u in str(a[3]["uids"]).split()}
    assert admitted <= {s.req.uid for s in sent}
    inner = dict(serve_spans.idle_by_inner_span(ev, serve))
    assert any(k.startswith("serve.") for k in inner)
    assert sum(inner.values()) == pytest.approx(
        sum(v for _, v in res["breakdown"]["idle_gaps"]))
