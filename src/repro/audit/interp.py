"""Interval abstract interpretation over jaxprs (the range/overflow pass).

The structural rules in :mod:`repro.audit.rules` prove what a program *is*
(no dense matmuls, no per-step table copies); this module proves what its
values can *be*.  It walks a closed jaxpr with one conservative interval
``[lo, hi]`` per array (a sound join over the array's elements), applies a
transfer function per primitive (add/sub/mul/gather/select/shift/scan/...),
and flags every *signed-integer* arithmetic equation whose ideal-arithmetic
result interval escapes its machine dtype — i.e. a potential accumulator or
index-packing overflow, found statically, before anything executes.

Soundness conventions:

* Unknown primitives and opaque ``pallas_call`` equations fall back to the
  full dtype range of their outputs (callers can supply a closed-form
  ``pallas_model`` — :func:`repro.audit.ranges.pallas_interval_model` does,
  using the per-family accumulator certificates).
* Unsigned arithmetic is never flagged: wrapping is defined behaviour in
  XLA (and the threefry PRNG depends on it).  Overflowing unsigned results
  widen to the dtype range instead.
* ``convert_element_type`` is an intentional narrowing; the result interval
  is clamped to the target dtype, never flagged.
* ``scan`` / ``while`` carries run to a fixpoint with widening: after
  :data:`MAX_FIXPOINT_ITERS` non-converged iterations the carry widens to
  dtype ranges, then one final muted-free pass collects facts.

Integer *inputs* default to ±:data:`INT_INPUT_BOUND` (``2**24``) rather
than the full dtype range: graph inputs such as token ids, cache positions,
and packed LUT codes are small by construction, and seeding them at
``int32`` range would make ``pos + 1`` a false overflow.  The bound is a
documented precondition of the certificate ("integer graph inputs fit in
24 bits"), overridable per input via explicit ``arg_intervals``.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np
from jax._src.core import DropVar  # jax.extend.core does not export it
from jax.extend.core import ClosedJaxpr, Jaxpr, Literal

from repro.audit.walker import OPAQUE_PRIMITIVES

# Precondition on integer graph inputs (see module docstring).
INT_INPUT_BOUND = 2**24

MAX_FIXPOINT_ITERS = 8

_INF = math.inf


@dataclasses.dataclass(frozen=True)
class Interval:
    """Conservative ``[lo, hi]`` bound on every element of an array."""

    lo: float
    hi: float

    def __post_init__(self):
        if not (self.lo <= self.hi):
            raise ValueError(f"empty interval [{self.lo}, {self.hi}]")

    @staticmethod
    def point(v: float) -> "Interval":
        return Interval(float(v), float(v))

    def join(self, other: "Interval") -> "Interval":
        return Interval(min(self.lo, other.lo), max(self.hi, other.hi))

    def within(self, other: "Interval") -> bool:
        return other.lo <= self.lo and self.hi <= other.hi

    @property
    def mag(self) -> float:
        """max |value| the interval admits."""
        return max(abs(self.lo), abs(self.hi))


TOP = Interval(-_INF, _INF)


@dataclasses.dataclass(frozen=True)
class OverflowFact:
    """One signed-integer equation whose ideal result escapes its dtype."""

    primitive: str
    dtype: str
    ideal: tuple[float, float]
    detail: str

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def dtype_interval(dtype) -> Interval:
    """The machine range of a dtype (bool ``[0,1]``, floats ±max finite)."""
    d = np.dtype(dtype)
    if d.kind == "b":
        return Interval(0.0, 1.0)
    if d.kind in "iu":
        ii = np.iinfo(d)
        return Interval(float(ii.min), float(ii.max))
    if d.kind == "f":
        try:
            fi = np.finfo(d)
            return Interval(-float(fi.max), float(fi.max))
        except (ValueError, TypeError):  # exotic float types
            return TOP
    return TOP


def default_arg_intervals(jaxpr, int_bound: int = INT_INPUT_BOUND) -> list[Interval]:
    """The documented input policy: signed ints ±``int_bound`` (clipped to
    the dtype range, so int8 stays int8), unsigned/bool/narrow floats their
    dtype range, wide floats TOP.  ``jaxpr`` is a ``ClosedJaxpr`` (or has
    ``in_avals``)."""
    out = []
    for aval in jaxpr.in_avals:
        d = np.dtype(aval.dtype)
        rng = dtype_interval(d)
        if d.kind == "i":
            out.append(
                Interval(max(rng.lo, -float(int_bound)), min(rng.hi, float(int_bound)))
            )
        elif d.kind == "f" and d.itemsize >= 4:
            out.append(TOP)
        else:
            out.append(rng)
    return out


# ---------------------------------------------------------------------------
# interval arithmetic helpers
# ---------------------------------------------------------------------------


def _mul_bound(a: float, b: float) -> float:
    # inf * 0 is nan under IEEE; in interval arithmetic it is exactly 0
    if a == 0.0 or b == 0.0:
        return 0.0
    return a * b


def _i_add(a: Interval, b: Interval) -> Interval:
    return Interval(a.lo + b.lo, a.hi + b.hi)


def _i_sub(a: Interval, b: Interval) -> Interval:
    return Interval(a.lo - b.hi, a.hi - b.lo)


def _i_mul(a: Interval, b: Interval) -> Interval:
    cands = [_mul_bound(x, y) for x in (a.lo, a.hi) for y in (b.lo, b.hi)]
    return Interval(min(cands), max(cands))


def _i_neg(a: Interval) -> Interval:
    return Interval(-a.hi, -a.lo)


def _i_abs(a: Interval) -> Interval:
    if a.lo >= 0:
        return a
    if a.hi <= 0:
        return _i_neg(a)
    return Interval(0.0, a.mag)


def _i_scale(a: Interval, c: float) -> Interval:
    return _i_mul(a, Interval.point(c))


def _join_all(ivals) -> Interval:
    out = None
    for iv in ivals:
        out = iv if out is None else out.join(iv)
    return TOP if out is None else out


def _shift_candidates(a: Interval, s: Interval, op) -> Interval:
    if not (0 <= s.lo and s.hi <= 64) or a.mag == _INF:
        return TOP
    cands = [
        op(int(x), int(sh))
        for x in (a.lo, a.hi)
        for sh in (s.lo, s.hi)
        if abs(x) <= 2**63
    ]
    if not cands:
        return TOP
    return Interval(float(min(cands)), float(max(cands)))


# ---------------------------------------------------------------------------
# transfer functions: (eqn, in_intervals) -> list of IDEAL out intervals
# ---------------------------------------------------------------------------


def _reduced_count(eqn) -> int:
    """Elements contracted per output element of a reduction equation."""
    n_in = math.prod(eqn.invars[0].aval.shape) or 1
    n_out = math.prod(eqn.outvars[0].aval.shape) or 1
    return max(1, n_in // n_out)


def _t_reduce_sum(eqn, ins):
    n = _reduced_count(eqn)
    return [_i_scale(ins[0], float(n)) if ins[0].lo < 0 else Interval(
        ins[0].lo, _mul_bound(float(n), ins[0].hi))]


def _t_cumsum(eqn, ins):
    n = eqn.invars[0].aval.shape[eqn.params.get("axis", 0)] or 1
    lo = min(ins[0].lo, _mul_bound(float(n), ins[0].lo))
    hi = max(ins[0].hi, _mul_bound(float(n), ins[0].hi))
    return [Interval(lo, hi)]


def _t_dot_general(eqn, ins):
    ((lhs_c, _), _) = eqn.params["dimension_numbers"]
    c = math.prod(eqn.invars[0].aval.shape[d] for d in lhs_c) or 1
    return [_i_scale(_i_mul(ins[0], ins[1]), float(c))]


def _t_clamp(eqn, ins):
    lo_in, x, hi_in = ins
    lo = min(max(x.lo, lo_in.lo), hi_in.lo)
    hi = min(max(x.hi, lo_in.hi), hi_in.hi)
    return [Interval(min(lo, hi), max(lo, hi))]


def _t_bitwise(eqn, ins):
    a, b = ins
    if a.lo < 0 or b.lo < 0 or a.hi == _INF or b.hi == _INF:
        return [dtype_interval(eqn.outvars[0].aval.dtype)]
    name = eqn.primitive.name
    if name == "and":
        return [Interval(0.0, min(a.hi, b.hi))]
    return [Interval(0.0, a.hi + b.hi)]  # or/xor: <= sum of maxima


def _t_div(eqn, ins):
    a, b = ins
    if b.lo <= 0.0 <= b.hi:
        return [TOP]
    cands = [x / y for x in (a.lo, a.hi) for y in (b.lo, b.hi) if y != 0]
    return [Interval(min(cands), max(cands))]


def _t_rem(eqn, ins):
    m = ins[1].mag
    if m == _INF:
        return [TOP]
    return [Interval(-m, m)]


def _t_exp2(eqn, ins):
    lo = 2.0 ** ins[0].lo if ins[0].lo > -_INF else 0.0
    hi = 2.0 ** ins[0].hi if ins[0].hi < 1024 else _INF
    return [Interval(lo, hi)]


def _t_exp(eqn, ins):
    lo = math.exp(ins[0].lo) if ins[0].lo > -_INF else 0.0
    hi = math.exp(ins[0].hi) if ins[0].hi < 709 else _INF
    return [Interval(lo, hi)]


def _t_iota(eqn, ins):
    n = eqn.params["shape"][eqn.params["dimension"]]
    return [Interval(0.0, float(max(n - 1, 0)))]


def _t_argminmax(eqn, ins):
    n = math.prod(eqn.invars[0].aval.shape) or 1
    return [Interval(0.0, float(n - 1))]


def _t_square(eqn, ins):
    a = _i_abs(ins[0])
    return [Interval(_mul_bound(a.lo, a.lo), _mul_bound(a.hi, a.hi))]


def _t_integer_pow(eqn, ins):
    y = int(eqn.params["y"])
    if y < 0 or y > 64:
        return [TOP]
    out = Interval.point(1.0)
    for _ in range(y):
        out = _i_mul(out, ins[0])
    return [out]


def _t_floor_ceil(eqn, ins):
    a = ins[0]
    lo = math.floor(a.lo) if a.lo > -_INF else a.lo
    hi = math.ceil(a.hi) if a.hi < _INF else a.hi
    return [Interval(float(lo), float(hi))]


def _t_top_k(eqn, ins):
    # outputs: (top values, their indices along the searched axis)
    n = eqn.invars[0].aval.shape[-1]
    return [ins[0], Interval(0.0, float(max(n - 1, 0)))]


def _t_sort(eqn, ins):
    # sort permutes each operand independently (sort_key_val / argsort carry
    # the iota as a second operand — it must keep ITS interval, not the keys')
    return list(ins)


def _scatter_rows(eqn) -> int:
    """Update rows a scatter applies — the most that can hit ONE element."""
    upd_shape = eqn.invars[2].aval.shape
    window = set(eqn.params["dimension_numbers"].update_window_dims)
    return math.prod(
        d for i, d in enumerate(upd_shape) if i not in window
    ) or 1


def _t_scatter_add(eqn, ins):
    # worst case every update row lands on the same element
    n = float(_scatter_rows(eqn))
    u = ins[2]
    return [
        Interval(
            ins[0].lo + min(0.0, _mul_bound(n, u.lo)),
            ins[0].hi + max(0.0, _mul_bound(n, u.hi)),
        )
    ]


_UNIT = lambda eqn, ins: [Interval(-1.0, 1.0)]  # noqa: E731
_ZERO_ONE = lambda eqn, ins: [Interval(0.0, 1.0)]  # noqa: E731
_PASS = lambda eqn, ins: [ins[0]] * len(eqn.outvars)  # noqa: E731
_JOIN = lambda eqn, ins: [_join_all(ins)] * len(eqn.outvars)  # noqa: E731

_TRANSFER = {
    "add": lambda eqn, ins: [_i_add(ins[0], ins[1])],
    "add_any": lambda eqn, ins: [_i_add(ins[0], ins[1])],
    "sub": lambda eqn, ins: [_i_sub(ins[0], ins[1])],
    "mul": lambda eqn, ins: [_i_mul(ins[0], ins[1])],
    "div": _t_div,
    "rem": _t_rem,
    "neg": lambda eqn, ins: [_i_neg(ins[0])],
    "abs": lambda eqn, ins: [_i_abs(ins[0])],
    "sign": lambda eqn, ins: [Interval(-1.0, 1.0)],
    "max": lambda eqn, ins: [
        Interval(max(ins[0].lo, ins[1].lo), max(ins[0].hi, ins[1].hi))
    ],
    "min": lambda eqn, ins: [
        Interval(min(ins[0].lo, ins[1].lo), min(ins[0].hi, ins[1].hi))
    ],
    "clamp": _t_clamp,
    "select_n": lambda eqn, ins: [_join_all(ins[1:])],
    "and": _t_bitwise,
    "or": _t_bitwise,
    "xor": _t_bitwise,
    "not": lambda eqn, ins: [dtype_interval(eqn.outvars[0].aval.dtype)],
    "shift_left": lambda eqn, ins: [
        _shift_candidates(ins[0], ins[1], lambda x, s: x << s)
    ],
    "shift_right_logical": lambda eqn, ins: [
        _shift_candidates(ins[0], ins[1], lambda x, s: x >> s)
        if ins[0].lo >= 0
        else dtype_interval(eqn.outvars[0].aval.dtype)
    ],
    "shift_right_arithmetic": lambda eqn, ins: [
        _shift_candidates(ins[0], ins[1], lambda x, s: x >> s)
    ],
    "reduce_sum": _t_reduce_sum,
    "reduce_max": _PASS,
    "reduce_min": _PASS,
    "reduce_and": _PASS,
    "reduce_or": _PASS,
    "cumsum": _t_cumsum,
    "cummax": _PASS,
    "dot_general": _t_dot_general,
    "iota": _t_iota,
    "argmax": _t_argminmax,
    "argmin": _t_argminmax,
    "reduce_precision": _PASS,
    "stop_gradient": _PASS,
    "copy": _PASS,
    "reshape": _PASS,
    "broadcast_in_dim": _PASS,
    "transpose": _PASS,
    "squeeze": _PASS,
    "expand_dims": _PASS,
    "rev": _PASS,
    "slice": _PASS,
    "dynamic_slice": lambda eqn, ins: [ins[0]],
    "gather": lambda eqn, ins: [ins[0]],
    "split": _PASS,
    "concatenate": _JOIN,
    "pad": lambda eqn, ins: [_join_all(ins[:2])],
    "dynamic_update_slice": lambda eqn, ins: [_join_all(ins[:2])],
    "scatter": lambda eqn, ins: [_join_all(ins[: 3 : 2])],
    "scatter-add": _t_scatter_add,
    "scatter-min": lambda eqn, ins: [_join_all(ins[: 3 : 2])],
    "scatter-max": lambda eqn, ins: [_join_all(ins[: 3 : 2])],
    "sort": _t_sort,
    "top_k": _t_top_k,
    "device_put": _PASS,
    "tanh": _UNIT,
    "sin": _UNIT,
    "cos": _UNIT,
    "erf": _UNIT,
    "logistic": _ZERO_ONE,
    "exp": _t_exp,
    "exp2": _t_exp2,
    "square": _t_square,
    "integer_pow": _t_integer_pow,
    "floor": _t_floor_ceil,
    "ceil": _t_floor_ceil,
    "round": _t_floor_ceil,
    "nextafter": _PASS,
    "real": _PASS,
    "eq": _ZERO_ONE,
    "ne": _ZERO_ONE,
    "lt": _ZERO_ONE,
    "le": _ZERO_ONE,
    "gt": _ZERO_ONE,
    "ge": _ZERO_ONE,
    "is_finite": _ZERO_ONE,
    "sqrt": lambda eqn, ins: [
        Interval(math.sqrt(max(ins[0].lo, 0.0)), math.sqrt(ins[0].hi))
        if ins[0].hi < _INF
        else Interval(0.0, _INF)
    ],
}

# Signed-integer arithmetic worth flagging when its ideal interval escapes
# the machine dtype.  Deliberately excludes conversions/bitcasts (narrowing
# is intentional) and unsigned ops (wrapping is defined).
_FLAGGED = frozenset(
    {
        "add",
        "add_any",
        "sub",
        "mul",
        "dot_general",
        "reduce_sum",
        "cumsum",
        "scatter-add",
        "shift_left",
        "integer_pow",
        "square",
    }
)

_CALL_PRIMS = frozenset(
    {
        "jit",
        "closed_call",
        "core_call",
        "remat",
        "checkpoint",
        "custom_jvp_call",
        "custom_vjp_call",
        "custom_vjp_call_jaxpr",
        "remat2",
    }
)


def _sub_jaxpr(v):
    if isinstance(v, ClosedJaxpr):
        return v
    if isinstance(v, Jaxpr):
        return ClosedJaxpr(v, ())
    return None


def _const_interval(c) -> Interval:
    try:
        arr = np.asarray(c)
        if arr.size == 0:
            return Interval.point(0.0)
        if arr.dtype.kind in "biuf":
            lo = float(np.min(arr))
            hi = float(np.max(arr))
            if math.isnan(lo) or math.isnan(hi):
                return TOP
            return Interval(lo, hi)
    except (TypeError, ValueError, RuntimeError):
        pass
    return TOP


class _Interp:
    """One interpretation run: env management, fixpoints, fact collection."""

    def __init__(self, pallas_model=None):
        self.pallas_model = pallas_model
        self.facts: list[OverflowFact] = []
        self._mute = 0  # >0 while iterating a not-yet-converged fixpoint

    # -- env ----------------------------------------------------------------
    def _read(self, env, v) -> Interval:
        if isinstance(v, Literal):
            return _const_interval(v.val)
        got = env.get(v)
        return got if got is not None else dtype_interval(v.aval.dtype)

    def _write(self, env, v, ideal: Interval, name: str):
        if isinstance(v, DropVar):
            return
        d = np.dtype(v.aval.dtype)
        machine = dtype_interval(d)
        if d.kind == "i" and not ideal.within(machine):
            if name in _FLAGGED and not self._mute:
                self.facts.append(
                    OverflowFact(
                        primitive=name,
                        dtype=str(d),
                        ideal=(ideal.lo, ideal.hi),
                        detail=(
                            f"{name} -> {d} {v.aval.shape}: ideal range "
                            f"[{ideal.lo:.6g}, {ideal.hi:.6g}] escapes "
                            f"[{machine.lo:.0f}, {machine.hi:.0f}]"
                        ),
                    )
                )
            env[v] = machine  # wrapped value can be anywhere in the dtype
        elif d.kind in "ub" and not ideal.within(machine):
            env[v] = machine
        else:
            env[v] = ideal

    # -- control flow -------------------------------------------------------
    def _run_cond(self, eqn, ins) -> list[Interval]:
        branch_outs = [
            self.run(br, ins[1:]) for br in eqn.params["branches"]
        ]
        return [_join_all(outs) for outs in zip(*branch_outs)]

    def _fixpoint(self, body, n_carry: int, init: list[Interval], eqn):
        """Join-until-stable carry loop with widening; returns final carry
        plus the last body outputs (for scan's stacked ys)."""
        carry = list(init)
        outs = None
        self._mute += 1
        try:
            for _ in range(MAX_FIXPOINT_ITERS):
                outs = body(carry)
                new = [c.join(o) for c, o in zip(carry, outs[:n_carry])]
                if new == carry:
                    break
                carry = new
            else:
                carry = [
                    dtype_interval(v.aval.dtype)
                    for v in eqn.outvars[:n_carry]
                ]
        finally:
            self._mute -= 1
        outs = body(carry)  # one unmuted pass over the stabilised carry
        return carry, outs

    def _run_scan(self, eqn, ins) -> list[Interval]:
        p = eqn.params
        nc, ncarry = p["num_consts"], p["num_carry"]
        consts, init, xs = ins[:nc], ins[nc : nc + ncarry], ins[nc + ncarry :]
        body_jaxpr = p["jaxpr"]

        def body(carry):
            return self.run(body_jaxpr, consts + carry + xs)

        carry, outs = self._fixpoint(body, ncarry, init, eqn)
        return carry + outs[ncarry:]

    def _run_while(self, eqn, ins) -> list[Interval]:
        p = eqn.params
        cn, bn = p["cond_nconsts"], p["body_nconsts"]
        body_consts = ins[cn : cn + bn]
        init = ins[cn + bn :]
        body_jaxpr = p["body_jaxpr"]

        def body(carry):
            return self.run(body_jaxpr, body_consts + carry)

        carry, _ = self._fixpoint(body, len(init), init, eqn)
        return carry

    # -- main loop ----------------------------------------------------------
    def run(self, jaxpr, arg_intervals) -> list[Interval]:
        closed = _sub_jaxpr(jaxpr)
        if closed is None:
            raise TypeError(f"expected a jaxpr, got {type(jaxpr)!r}")
        inner = closed.jaxpr
        if len(arg_intervals) != len(inner.invars):
            raise ValueError(
                f"got {len(arg_intervals)} arg intervals for "
                f"{len(inner.invars)} jaxpr inputs"
            )
        env: dict = {}
        for v, c in zip(inner.constvars, closed.consts):
            env[v] = _const_interval(c)
        for v, iv in zip(inner.invars, arg_intervals):
            env[v] = iv

        for eqn in inner.eqns:
            name = eqn.primitive.name
            ins = [self._read(env, v) for v in eqn.invars]
            if name in OPAQUE_PRIMITIVES:
                outs = None
                if self.pallas_model is not None:
                    outs = self.pallas_model(eqn, ins)
                if outs is None:
                    outs = [dtype_interval(v.aval.dtype) for v in eqn.outvars]
            elif name == "cond":
                outs = self._run_cond(eqn, ins)
            elif name == "scan":
                outs = self._run_scan(eqn, ins)
            elif name == "while":
                outs = self._run_while(eqn, ins)
            elif name in _CALL_PRIMS:
                sub = None
                for v in eqn.params.values():
                    sub = _sub_jaxpr(v)
                    if sub is not None:
                        break
                if sub is not None and len(sub.jaxpr.invars) == len(ins):
                    outs = self.run(sub, ins)
                else:
                    outs = [dtype_interval(v.aval.dtype) for v in eqn.outvars]
            elif name == "convert_element_type":
                d = dtype_interval(eqn.outvars[0].aval.dtype)
                outs = [
                    Interval(
                        min(max(ins[0].lo, d.lo), d.hi),
                        max(min(ins[0].hi, d.hi), d.lo),
                    )
                ]
            else:
                fn = _TRANSFER.get(name)
                if fn is None:
                    outs = [dtype_interval(v.aval.dtype) for v in eqn.outvars]
                else:
                    try:
                        outs = fn(eqn, ins)
                    except (KeyError, IndexError, ValueError, OverflowError):
                        outs = [
                            dtype_interval(v.aval.dtype) for v in eqn.outvars
                        ]
            if len(outs) != len(eqn.outvars):  # malformed transfer: widen
                outs = [dtype_interval(v.aval.dtype) for v in eqn.outvars]
            for v, iv in zip(eqn.outvars, outs):
                self._write(env, v, iv, name)

        return [self._read(env, v) for v in inner.outvars]


def interval_eval(
    jaxpr,
    arg_intervals: list[Interval] | None = None,
    *,
    pallas_model=None,
) -> tuple[list[Interval], list[OverflowFact]]:
    """Propagate intervals through ``jaxpr``; return output intervals plus
    every signed-integer overflow fact found on the way.

    ``arg_intervals`` defaults to :func:`default_arg_intervals`'s policy.
    ``pallas_model(eqn, in_intervals) -> list[Interval] | None`` supplies
    closed-form bounds for opaque ``pallas_call`` outputs.
    """
    if arg_intervals is None:
        arg_intervals = default_arg_intervals(jaxpr)
    interp = _Interp(pallas_model=pallas_model)
    outs = interp.run(jaxpr, arg_intervals)
    return outs, interp.facts
