"""Reduction of a profiler trace to what the per-layer metrics read.

Input is the ``.xplane.pb`` that ``jax.profiler`` writes.  Device planes
(``/device:TPU:<n>``) carry two lines read here: ``XLA Modules``, one
event per execution of a compiled program, and ``XLA Ops``, one event per
operation.  The host plane carries the harness's own spans (``bench.*``),
among them ``bench.window`` around the measured window; both planes are on
one clock.

An op event's name is its HLO instruction as text (``%fusion.3 = f32[8]
fusion(...)``); :func:`short_name` keeps the instruction's own name.  A
Pallas kernel's name is that of its function (``_lut_tl1_padded.22``).
Everything below works on plain tuples, so it can be checked on events
made by hand as well as on a recorded trace.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import gzip
import os
import re

from bench.loop import WINDOW_SPAN  # noqa: E402
_SUFFIX = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Events:
    """One trace: per device, its module executions and its ops, each a
    ``(name, start_ns, end_ns)``; and the host's ``bench.*`` spans."""

    modules: list  # per device: list of (name, start, end)
    ops: list  # per device: list of (name, start, end)
    spans: list  # (name, start, end)

    @property
    def window(self) -> tuple[float, float]:
        w = [s for s in self.spans if s[0] == WINDOW_SPAN]
        if len(w) != 1:
            raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(w)}")
        return w[0][1], w[0][2]


def module_name(name: str) -> str:
    """``jit_decode(12)`` -> ``jit_decode``."""
    return _SUFFIX.sub("", name.strip())


def find_xplane(directory: str) -> str:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def load(path: str) -> Events:
    """Events of ``path``, an ``.xplane.pb`` or a gzipped one."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    modules, ops, spans = [], [], []
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            mods, these = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods = [(module_name(e.name), e.start_ns, e.end_ns)
                            for e in line.events]
                elif line.name == "XLA Ops":
                    these = [(e.name, e.start_ns, e.end_ns) for e in line.events]
            modules.append(mods)
            ops.append(these)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append((e.name, e.start_ns, e.end_ns))
    if not ops:
        raise ValueError(f"{path}: no TPU device plane with XLA Ops")
    return Events(modules, ops, spans)


def clip(evs, t0, t1):
    return [(n, max(s, t0), min(e, t1)) for n, s, e in evs if e > t0 and s < t1]


def union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(ev: Events) -> float:
    """Time in the window during which some op ran, averaged over devices."""
    t0, t1 = ev.window
    per = [sum(e - s for s, e in union((s, e) for _, s, e in clip(o, t0, t1)))
           for o in ev.ops]
    return sum(per) / len(per)


def executions(ev: Events, device: int = 0) -> dict[str, list[tuple]]:
    """Module executions that start in the window, by module name."""
    t0, t1 = ev.window
    out = collections.defaultdict(list)
    for name, s, e in ev.modules[device]:
        if t0 <= s < t1:
            out[name].append((s, e))
    return dict(out)


def ops_within(ev: Events, spans, device: int = 0):
    """Ops of ``device`` that lie inside one of ``spans`` ((start, end),
    sorted), each as (name, start, end)."""
    spans = sorted(spans)
    out = []
    j = 0
    for name, s, e in sorted(ev.ops[device], key=lambda o: o[1]):
        while j < len(spans) and spans[j][1] <= s:
            j += 1
        if j < len(spans) and spans[j][0] <= s and e <= spans[j][1]:
            out.append((name, s, e))
    return out


def short_name(name: str) -> str:
    """``%fusion.3 = f32[8]{0} fusion(...)`` -> ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%")


def matches(name: str, patterns) -> bool:
    """Whether the op's own name (not its operands') holds a pattern."""
    own = short_name(name)
    return any(p in own for p in patterns)


def self_times(ops) -> list[tuple[str, float]]:
    """Each op's time less the ops it encloses: a loop op (the layer scan's
    ``while``) encloses the ops of its body on the same line."""
    out, stack = [], []  # stack of [name, end, self]
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            out.append(tuple(stack.pop()[::2]))
        if stack:
            stack[-1][2] -= e - s
        stack.append([name, e, e - s])
    out.extend(tuple(x[::2]) for x in reversed(stack))
    return out


def top_ops(ev: Events, n: int = 10, device: int = 0) -> list[list]:
    """The ops that took most device time of their own in the window, in
    seconds, by short name."""
    t0, t1 = ev.window
    tot: dict[str, float] = collections.defaultdict(float)
    for name, t in self_times(clip(ev.ops[device], t0, t1)):
        tot[short_name(name)] += t
    return [[k, v / 1e9] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(ev: Events, device: int = 0) -> list[tuple[float, float]]:
    t0, t1 = ev.window
    out, cur = [], t0
    for s, e in union((s, e) for _, s, e in clip(ev.ops[device], t0, t1)):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < t1:
        out.append((cur, t1))
    return out


def idle_by_span(ev: Events, n: int = 10, device: int = 0) -> list[list]:
    """Idle seconds in the window, by the ``bench.*`` span (other than the
    window's own) that held the host at each gap's midpoint.  The harness's
    spans follow one another and never nest."""
    spans = sorted((s for s in ev.spans if s[0] != WINDOW_SPAN), key=lambda s: s[1])
    starts = [s[1] for s in spans]
    tot: dict[str, float] = collections.defaultdict(float)
    for s, e in idle_gaps(ev, device):
        mid = (s + e) / 2
        i = bisect.bisect_right(starts, mid) - 1
        name = spans[i][0] if i >= 0 and spans[i][2] > mid else "none"
        tot[name] += e - s
    return [[k, v / 1e9] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]
