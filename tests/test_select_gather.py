"""The kernels' compare-and-select row gather equals ``jnp.take`` exactly,
and a table the kernels cannot serve raises instead of falling back."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.lut_tl1 import TL1Plan
from repro.core.planner import enumerate_plans, plan_model
from repro.core.quantize import FixedPointFormat, Float16Format
from repro.kernels.common import MAX_SELECT_ENTRIES, select_entries
from repro.kernels.lut_affine.ops import (
    lut_affine,
    lut_affine_experts,
    lut_affine_grouped,
)


def _kernel_entry_counts() -> set[int]:
    """Every entry count the planner can hand the kernels: each format and
    mode it enumerates, at every chunk size, under the kernels' cap."""
    counts = {TL1Plan(8, 8).num_entries}
    signs = (False, True)
    fmts = [FixedPointFormat(b, 0, signed=s) for b in range(1, 9) for s in signs]
    fmts += [
        Float16Format(signed=s, mantissa_radix=r) for r in (1, 2, 3, 4) for s in signs
    ]
    for fmt in fmts:
        modes = ("bitplane", "full", "bitplane_shift")
        for pt in enumerate_plans(8, 8, fmt, modes=modes):
            if pt.plan.num_entries <= MAX_SELECT_ENTRIES:
                counts.add(pt.plan.num_entries)
    return counts


def test_planner_emits_only_counts_tested_here():
    counts = _kernel_entry_counts()
    assert counts <= set(range(2, MAX_SELECT_ENTRIES + 1))
    assert {2, 4, 8, 9, 16} <= counts


@pytest.mark.parametrize("entries", range(2, MAX_SELECT_ENTRIES + 1))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8, jnp.int32])
def test_select_rows_equals_take(entries, dtype):
    """Weight tables: ``(E, p)`` rows picked by ``(rows, 1)`` codes."""
    kt, kc = jax.random.split(jax.random.PRNGKey(entries))
    table = (jax.random.normal(kt, (entries, 256)) * 50).astype(dtype)
    codes = jax.random.randint(kc, (24, 1), 0, entries)
    got = select_entries([table[e : e + 1] for e in range(entries)], codes)
    want = jnp.take(table, codes[:, 0], axis=0)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("entries", range(2, MAX_SELECT_ENTRIES + 1))
def test_select_columns_equals_take(entries):
    """TL1 orientation: ``(rows, 1)`` entry columns picked by ``(1, p)``
    weight indices (the 9-entry activation LUT)."""
    kt, kc = jax.random.split(jax.random.PRNGKey(100 + entries))
    lut = jax.random.randint(kt, (16, entries), -(2**15), 2**15)
    idx = jax.random.randint(kc, (1, 384), 0, entries)
    got = select_entries([lut[:, e : e + 1] for e in range(entries)], idx)
    want = jnp.take(lut, idx[0], axis=1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize(
    "op", ["lut_affine", "lut_affine_grouped", "lut_affine_experts"]
)
def test_kernels_refuse_tables_past_the_select_cap(op):
    entries = 2 * MAX_SELECT_ENTRIES
    codes = jnp.zeros((4, 2, 8), jnp.int32)
    scales = jnp.ones((2,), jnp.float32)
    with pytest.raises(ValueError, match="select gather"):
        if op == "lut_affine":
            lut_affine(codes, jnp.zeros((8, entries, 128)), scales, interpret=True)
        elif op == "lut_affine_grouped":
            tables = jnp.zeros((2, 8, entries, 128))
            lut_affine_grouped(codes, tables, scales, interpret=True)
        else:
            lut_affine_experts(
                codes, jnp.zeros((2, 1, 8, entries, 128)), scales,
                jnp.asarray([3, 1], jnp.int32), interpret=True,
            )


def test_planner_keeps_kernel_plans_under_the_cap():
    params = {"ffn": {"w": jax.ShapeDtypeStruct((64, 32), jnp.float32)}}
    # fp16 bitplane tables start at 64 entries: nothing is servable
    with pytest.raises(ValueError, match="no feasible LUT plan"):
        plan_model(params, math.inf, max_entries=MAX_SELECT_ENTRIES)
    mplan = plan_model(
        params,
        math.inf,
        fmt=FixedPointFormat(8, 4, signed=True),
        max_entries=MAX_SELECT_ENTRIES,
    )
    assert all(p.num_entries <= MAX_SELECT_ENTRIES for p in mplan.layers.values())
    assert max(p.num_entries for p in mplan.layers.values()) == MAX_SELECT_ENTRIES
