"""The `repro.audit.ranges` / `repro.audit.interp` range-certificate pass.

Covers the interval interpreter (transfer functions, control-flow
fixpoints, the signed-only flagging policy), the closed-form per-plan
certificates against brute-force empirical accumulators (property tests
over family x format x radix), the planner's certificate gate (a
crafted wide int16 TL1 plan must be rejected loudly; the symmetric
narrow plan must pass and come back stamped), the trace-time kernel
contract assert, and the seeded-overflow regression through
``overflow_violations``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.audit.interp import (
    INT_INPUT_BOUND,
    Interval,
    default_arg_intervals,
    interval_eval,
)
from repro.audit.ranges import layer_range_cert, overflow_violations
from repro.core.lut import (
    LUTPlan,
    apply_luts,
    build_luts,
    pack_codes,
    quantize_tables,
)
from repro.core.lut_tl1 import (
    TL1Plan,
    _accumulate,
    build_act_lut,
    pack_ternary,
    quantize_acts,
    unpack_indices,
)
from repro.core.planner import ModelPlan, plan_model
from repro.core.quantize import Float16Format
from repro.kernels.common import acc_capacity, check_acc_contract

# ---------------------------------------------------------------------------
# interval interpreter
# ---------------------------------------------------------------------------


def test_default_arg_intervals_policy():
    jaxpr = jax.make_jaxpr(lambda a, b, c: (a, b, c))(
        jnp.zeros((2,), jnp.int32),
        jnp.zeros((2,), jnp.int8),
        jnp.zeros((2,), jnp.float32),
    )
    i32, i8, f32 = default_arg_intervals(jaxpr)
    assert i32 == Interval(-float(INT_INPUT_BOUND), float(INT_INPUT_BOUND))
    assert i8 == Interval(-128.0, 127.0)  # dtype range tighter than the bound
    assert f32.lo == -np.inf and f32.hi == np.inf


def test_in_range_int_arithmetic_is_clean():
    jaxpr = jax.make_jaxpr(lambda x: x + 1)(jnp.zeros((4,), jnp.int32))
    outs, facts = interval_eval(jaxpr)
    assert facts == []
    assert outs[0].within(Interval(-(2.0**24) + 1, 2.0**24 + 1))


def test_seeded_int16_add_overflow_fires():
    # int16 inputs span the full dtype range; x + x escapes it ideally
    jaxpr = jax.make_jaxpr(lambda x: x + x)(jnp.zeros((4,), jnp.int16))
    _, facts = interval_eval(jaxpr)
    assert facts and facts[0].primitive == "add"
    assert "escapes" in facts[0].detail
    assert facts[0].dtype == "int16"


def test_unsigned_wrap_is_never_flagged():
    # threefry-style uint arithmetic wraps by design
    jaxpr = jax.make_jaxpr(lambda x: x + x)(jnp.zeros((4,), jnp.uint32))
    outs, facts = interval_eval(jaxpr)
    assert facts == []
    assert outs[0].within(Interval(0.0, float(2**32 - 1)))


def test_convert_element_type_narrows_without_flagging():
    jaxpr = jax.make_jaxpr(lambda x: x.astype(jnp.int16))(
        jnp.zeros((4,), jnp.int32)
    )
    outs, facts = interval_eval(jaxpr)
    assert facts == []
    assert outs[0].within(Interval(-32768.0, 32767.0))


def test_scan_fixpoint_converges_on_bounded_carry():
    def f(x):
        def body(c, _):
            return jnp.minimum(c + 1, 3), None

        y, _ = jax.lax.scan(body, x, None, length=100)
        return y

    jaxpr = jax.make_jaxpr(f)(jnp.zeros((), jnp.int32))
    outs, facts = interval_eval(
        jaxpr, [Interval.point(0.0)]
    )
    assert facts == []
    assert outs[0].within(Interval(0.0, 4.0))


def test_scan_accumulator_overflow_fires_after_widening():
    # an unbounded int32 running sum cannot converge: the carry widens to
    # the dtype range and the final unmuted pass flags the add
    def f(x, xs):
        def body(c, v):
            return c + v, None

        y, _ = jax.lax.scan(body, x, xs)
        return y

    jaxpr = jax.make_jaxpr(f)(
        jnp.zeros((), jnp.int32), jnp.zeros((8,), jnp.int32)
    )
    _, facts = interval_eval(jaxpr)
    assert any(f.primitive == "add" for f in facts)


def test_dot_general_contraction_scales_by_width():
    jaxpr = jax.make_jaxpr(lambda a, b: a @ b)(
        jnp.zeros((2, 16), jnp.float32), jnp.zeros((16, 3), jnp.float32)
    )
    outs, _ = interval_eval(
        jaxpr, [Interval(-1.0, 1.0), Interval(-1.0, 1.0)]
    )
    assert outs[0].within(Interval(-16.0, 16.0))
    assert outs[0].mag >= 16.0  # the bound is tight for +/-1 operands


# ---------------------------------------------------------------------------
# closed-form certificates
# ---------------------------------------------------------------------------


def test_weight_cert_fp16_full_uses_format_max():
    plan = LUTPlan(8, 4, 1, Float16Format(), mode="full")
    cert = layer_range_cert(plan)
    assert cert.family == "weight" and not cert.integer
    assert cert.max_abs_acc == pytest.approx(8 * 65504.0)
    assert cert.table_quant_err == 0.0
    assert cert.min_acc_dtype == "float32"


def test_weight_cert_bitplane_shift_radix1_matches_format_max():
    # 32 * (2**(1*11) - 1) == 65504: the radix-1 bound is exactly tight
    plan = LUTPlan(8, 4, 1, Float16Format(), mode="bitplane_shift")
    cert = layer_range_cert(plan)
    assert cert.max_abs_acc == pytest.approx(8 * 65504.0)


def test_weight_cert_narrow_format_adds_quant_terms():
    base = LUTPlan(8, 4, 1, Float16Format(), mode="bitplane_shift")
    narrow = dataclasses.replace(base, table_format="i8")
    cb, cn = layer_range_cert(base), layer_range_cert(narrow)
    assert cn.max_abs_acc == pytest.approx(cb.max_abs_acc * (1 + 1 / 127))
    assert cn.table_quant_err == pytest.approx(cb.max_abs_acc / 127)
    assert cn.total_err > cb.total_err


def test_tl1_cert_int_path_counts_code_units():
    plan = TL1Plan(4096, 64, act_bits=8)
    cert = layer_range_cert(plan)
    assert cert.family == "tl1" and cert.integer
    assert cert.entry_max == 254.0  # 2 * (2**7 - 1)
    assert cert.max_abs_acc == 254.0 * plan.num_chunks
    assert cert.min_acc_dtype == "int32"  # 520192 > int16
    assert cert.table_quant_err == 0.0


def test_tl1_cert_exact_path_is_float_and_errorless():
    plan = TL1Plan(4096, 64, act_bits=None)
    cert = layer_range_cert(plan)
    assert not cert.integer
    assert plan.acc_dtype == "float32"  # __post_init__ normalises
    assert cert.total_err == 0.0


# ---------------------------------------------------------------------------
# property tests: empirical |acc| never exceeds the static bound
# ---------------------------------------------------------------------------


_TL1_QS, _TL1_BITS = (5, 24, 64), (2, 4, 8)
_RADICES, _FORMATS = (1, 2, 4), ("i8", "i16")


def _tl1_case(q, act_bits, seed):
    p = 8
    plan = TL1Plan(q, p, act_bits=act_bits)
    cert = layer_range_cert(plan)
    rng = np.random.default_rng(seed)
    # adversarial-leaning inputs: full-scale activations, dense ternary
    x = jnp.asarray(rng.uniform(-1.0, 1.0, size=(4, q)), jnp.float32)
    t = jnp.asarray(rng.choice([-1, 0, 1], size=(q, p), p=[0.45, 0.1, 0.45]))
    codes, _ = quantize_acts(x, plan)
    acc = _accumulate(build_act_lut(codes), unpack_indices(pack_ternary(t)))
    assert float(jnp.max(jnp.abs(acc))) <= cert.max_abs_acc
    # ...and the per-entry LUT bound holds too
    lut = build_act_lut(codes)
    assert float(jnp.max(jnp.abs(lut))) <= cert.entry_max


def _weight_case(radix, table_format, seed):
    q, p = 24, 8
    plan = LUTPlan(
        q,
        p,
        1,
        Float16Format(mantissa_radix=radix),
        mode="bitplane_shift",
        table_format=table_format,
    )
    cert = layer_range_cert(plan)
    rng = np.random.default_rng(seed)
    W = jnp.asarray(rng.uniform(-1.0, 1.0, size=(q, p)), jnp.float32)
    x = jnp.asarray(rng.uniform(0.0, 1.0, size=(4, q)), jnp.float32)
    narrow, scale = quantize_tables(build_luts(W, plan), table_format)
    dequant = narrow.astype(jnp.float32) * scale
    acc = apply_luts(dequant, pack_codes(x, plan), plan)
    assert float(jnp.max(jnp.abs(acc))) <= cert.max_abs_acc


@pytest.fixture(scope="module")
def compiled_cases():
    """Run every shape the property tests draw once, so JAX compiles its
    eager ops here and not inside an example hypothesis times against its
    deadline (the first call of a new shape takes seconds, later ones ms)."""
    for q in _TL1_QS:
        for act_bits in _TL1_BITS:
            _tl1_case(q, act_bits, 0)
    for radix in _RADICES:
        for table_format in _FORMATS:
            _weight_case(radix, table_format, 0)


@pytest.mark.usefixtures("compiled_cases")
@settings(max_examples=15)
@given(
    q=st.sampled_from(_TL1_QS),
    act_bits=st.sampled_from(_TL1_BITS),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_tl1_empirical_acc_within_static_bound(q, act_bits, seed):
    _tl1_case(q, act_bits, seed)


@pytest.mark.usefixtures("compiled_cases")
@settings(max_examples=10)
@given(
    radix=st.sampled_from(_RADICES),
    table_format=st.sampled_from(_FORMATS),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_weight_empirical_acc_within_static_bound(radix, table_format, seed):
    _weight_case(radix, table_format, seed)


# ---------------------------------------------------------------------------
# planner gate + kernel contract + overflow rule class
# ---------------------------------------------------------------------------

_WIDE = dict(in_features=4096, out_features=64)  # 2048 chunks: |acc| > int16
_NARROW = dict(in_features=64, out_features=16)  # 32 chunks: fits int16


def _params(q, p):
    return {"ffn": {"w": jax.ShapeDtypeStruct((q, p), jnp.float32)}}


def test_planner_rejects_unprovable_tl1_acc_dtype():
    with pytest.raises(ValueError, match="no overflow-safe plan"):
        plan_model(
            _params(4096, 64),
            float("inf"),
            families=("tl1",),
            tl1_acc_dtype="int16",
        )


def test_planner_stamps_provably_safe_plans():
    mplan = plan_model(
        _params(64, 16),
        float("inf"),
        families=("tl1",),
        tl1_acc_dtype="int16",
    )
    ((key, plan),) = mplan.layers.items()
    assert plan.acc_dtype == "int16"
    cert = layer_range_cert(plan)
    assert plan.max_abs_acc == cert.max_abs_acc
    assert cert.max_abs_acc <= acc_capacity("int16")
    # the stamp survives a JSON round trip (checkpoint path)
    rt = ModelPlan.from_json(mplan.to_json())
    assert rt.layers[key].max_abs_acc == plan.max_abs_acc
    assert rt.layers[key].acc_dtype == "int16"


def test_stamp_is_excluded_from_plan_equality():
    plan = TL1Plan(**_NARROW, act_bits=8)
    stamped = dataclasses.replace(plan, max_abs_acc=8128.0)
    assert stamped == plan  # derived metadata, like a cache
    assert dataclasses.replace(plan, acc_dtype="int16") != plan


def test_check_acc_contract_raises_on_forged_bound():
    plan = TL1Plan(**_NARROW, act_bits=8, acc_dtype="int16")
    ok = dataclasses.replace(plan, max_abs_acc=8128.0)
    check_acc_contract("lut_tl1", ok, "int32")  # declared + kernel both fit
    forged = dataclasses.replace(plan, max_abs_acc=1e6)
    with pytest.raises(ValueError, match="capacity"):
        check_acc_contract("lut_tl1", forged, "int32")
    wide_ok = dataclasses.replace(
        TL1Plan(**_WIDE, act_bits=8), max_abs_acc=520192.0
    )
    with pytest.raises(ValueError, match="too narrow"):
        check_acc_contract("lut_tl1", wide_ok, "int16")
    # no stamp -> no-op (pre-contract plans keep tracing)
    check_acc_contract("lut_tl1", TL1Plan(**_NARROW), "int32")


def test_overflow_violations_fire_on_crafted_wide_int16_plan():
    wide = TL1Plan(**_WIDE, act_bits=8, acc_dtype="int16")
    hits = overflow_violations(ModelPlan(layers={"ffn/w": wide}))
    kinds = {v.primitive for v in hits}
    assert "accumulate" in kinds
    assert all(v.rule == "overflow" for v in hits)
    # the symmetric narrow plan is clean under the identical predicate
    narrow = TL1Plan(**_NARROW, act_bits=8, acc_dtype="int16")
    assert overflow_violations(ModelPlan(layers={"ffn/w": narrow})) == []


def test_overflow_violations_flag_stale_stamp():
    plan = dataclasses.replace(TL1Plan(**_NARROW, act_bits=8), max_abs_acc=1.0)
    hits = overflow_violations(ModelPlan(layers={"ffn/w": plan}))
    assert any(v.primitive == "stale_bound" for v in hits)


def test_overflow_violations_walk_named_graphs():
    mplan = ModelPlan(layers={"ffn/w": TL1Plan(**_NARROW, act_bits=8)})
    bad = jax.make_jaxpr(lambda x: x + x)(jnp.zeros((4,), jnp.int16))
    hits = overflow_violations(mplan, graphs=(("decode", bad),))
    assert any(
        v.primitive == "add" and v.detail.startswith("decode:") for v in hits
    )
    clean = jax.make_jaxpr(lambda x: x + 1)(jnp.zeros((4,), jnp.int32))
    assert overflow_violations(mplan, graphs=(("decode", clean),)) == []
