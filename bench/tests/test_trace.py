"""The reduction from trace events to per-layer metrics, on events made by
hand and on a small trace recorded on a TPU v5e."""
import os

import pytest

from bench import spec, trace

MS = 1e6  # ns


def events_for_decode(steps: int, kernel_ms: float, other_ms: float,
                      gap_ms: float = 1.0,
                      kernel_op: str = "_lut_tl1_grouped_padded.3"):
    """``steps`` decode executions, each a table-kernel op then another op,
    separated by idle gaps spent in ``bench.bookkeep``."""
    mods, ops, spans = [], [], []
    t = 0.0
    for _ in range(steps):
        spans.append(("bench.step", t, t + (kernel_ms + other_ms) * MS))
        mods.append(("jit_decode", t, t + (kernel_ms + other_ms) * MS))
        ops.append((kernel_op, t, t + kernel_ms * MS))
        ops.append(("fusion.7", t + kernel_ms * MS, t + (kernel_ms + other_ms) * MS))
        t += (kernel_ms + other_ms) * MS
        spans.append(("bench.bookkeep", t, t + gap_ms * MS))
        t += gap_ms * MS
    spans.append((trace.WINDOW_SPAN, 0.0, t))
    return trace.Events([mods], [ops], spans)


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_busy_and_idle_of_decode_steps():
    ev = events_for_decode(steps=4, kernel_ms=8.0, other_ms=2.0, gap_ms=1.0)
    assert trace.busy_ns(ev) == pytest.approx(40 * MS)
    idle = spec.reader("metrics", "device_idle")

    class R:
        events = ev

    assert idle(R) == pytest.approx(100 * 4 / 44)
    assert trace.idle_by_span(ev) == [["bench.bookkeep", pytest.approx(0.004)]]
    assert trace.top_ops(ev)[0] == ["_lut_tl1_grouped_padded.3", pytest.approx(0.032)]


def test_busy_is_averaged_over_devices_and_clipped_to_the_window():
    ev = trace.Events(
        modules=[[], []],
        ops=[[("a", -5 * MS, 5 * MS)], [("b", 0.0, 2 * MS), ("c", 1 * MS, 3 * MS)]],
        spans=[(trace.WINDOW_SPAN, 0.0, 10 * MS)],
    )
    assert trace.busy_ns(ev) == pytest.approx((5 + 3) / 2 * MS)


def test_decode_metrics_split_kernel_time_from_the_rest():
    ev = events_for_decode(steps=3, kernel_ms=8.0, other_ms=2.0)

    class R:
        events = ev

    assert spec.reader("metrics", "decode_step_ms")(R) == pytest.approx(10.0)
    assert spec.reader("metrics", "decode_rest_ms")(R) == pytest.approx(2.0)


def test_a_trace_without_kernels_gives_no_roofline():
    ev = events_for_decode(steps=2, kernel_ms=8.0, other_ms=2.0, kernel_op="fusion.3")

    class R:
        events = ev
        slots = 8

    assert spec.reader("metrics", "lut_roofline.decode")(R) is None
    assert spec.reader("metrics", "decode_rest_ms")(R) is None


def test_executions_start_in_the_window():
    ev = trace.Events(
        modules=[[("jit_decode(3)", -2.0, 1.0), ("jit_prefill", 1.0, 4.0)]],
        ops=[[]],
        spans=[(trace.WINDOW_SPAN, 0.0, 3.0)],
    )
    assert trace.executions(ev) == {"jit_prefill": [(1.0, 4.0)]}
    assert trace.module_name("jit_decode(12)") == "jit_decode"


RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "tl1_batch_small.xplane.pb.gz")


@pytest.fixture(scope="module")
def recorded():
    """A quarter-second window of ``tl1-batch`` recorded on one TPU v5e: the
    engine's first admission (a prefill of 8 x 64 rows) and one decode
    step, at granite_8b's widths."""
    return trace.load(RECORDED)


def test_recorded_trace_has_one_device_and_both_steps(recorded):
    assert len(recorded.ops) == 1
    assert {k: len(v) for k, v in trace.executions(recorded).items()} == {
        "jit_prefill": 1, "jit_decode": 1}
    spans = {s[0] for s in recorded.spans}
    assert {"bench.window", "bench.step", "bench.submit", "bench.bookkeep"} <= spans


def test_recorded_trace_busy_and_gaps(recorded):
    t0, t1 = recorded.window
    busy = trace.busy_ns(recorded)
    assert 0.9 * (t1 - t0) < busy < t1 - t0
    idle = sum(v for _, v in trace.idle_by_span(recorded))
    assert idle * 1e9 == pytest.approx(t1 - t0 - busy)


def test_recorded_kernels_are_found_inside_the_decode_step(recorded):
    from bench.metrics_common import KERNEL_PATTERNS

    class R:
        events = recorded
        slots = 8
        peaks = {"bf16_flops": 197e12, "hbm_bw": 819e9}
        info = {"stored_bytes": 218_103_920, "row_bytes": 1_179_728,
                "linear_work": {"linears": 872_415_232}}

    (s, e), = trace.executions(recorded)["jit_decode"]
    kern = [o for o in trace.ops_within(recorded, [(s, e)])
            if trace.matches(o[0], KERNEL_PATTERNS)]
    assert len(kern) == 4 * 5  # 4 layers x 5 projections (2 of them grouped)
    step = spec.reader("metrics", "decode_step_ms")(R)
    rest = spec.reader("metrics", "decode_rest_ms")(R)
    assert step == pytest.approx((e - s) / 1e6)
    assert 0 < rest < 0.2 * step
    share = spec.reader("metrics", "lut_roofline.decode")(R)
    assert 0 < share < 100


def test_top_ops_count_time_of_their_own(recorded):
    top = trace.top_ops(recorded)
    assert top[0][0].startswith("_lut_tl1_grouped_padded")
    assert all(" = " not in name for name, _ in top)
    t0, t1 = recorded.window
    own = sum(t for _, t in trace.self_times(trace.clip(recorded.ops[0], t0, t1)))
    assert own == pytest.approx(trace.busy_ns(recorded), rel=1e-6)


def test_self_time_of_a_loop_excludes_its_body():
    ops = [("%while.3 = loop", 0.0, 10.0), ("a", 1.0, 4.0), ("b", 5.0, 6.0)]
    assert dict(trace.self_times(ops)) == {"%while.3 = loop": 6.0, "a": 3.0, "b": 1.0}
