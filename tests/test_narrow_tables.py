"""Narrow (int8/int16) table storage and bitplane_shift exponent codes.

Three layers of evidence, matching the execution stack:

* ``quantize_tables`` semantics — power-of-2 scales, per-table-set
  ``trailing`` shapes (the leaf must stay layer-scan sliceable), and the
  dequant error bound.
* Pallas kernels (interpret mode) vs the jnp oracle across a shape grid,
  for i8/i16 tables and for ``shift_bits`` exponent-carrying codes, on the
  single / grouped / experts entry points.
* The ``bitplane_shift`` mode end to end: radix-r mantissa planes with the
  sigma barrel-shift applied at accumulate reproduce the fp16 matmul, and
  stay accurate after i8 table quantization (the whole point of the mode:
  sigma-free tables span only ``[-(2**r-1), 2**r-1]``).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.lut import (
    LUTPlan,
    apply_luts,
    build_luts,
    lut_affine_reference,
    pack_codes,
    plane_scales,
    quantize_tables,
    table_scale,
)
from repro.core.quantize import Float16Format
from repro.kernels.lut_affine.ops import (
    lut_affine,
    lut_affine_experts,
    lut_affine_grouped,
)
from repro.kernels.lut_affine.ref import (
    lut_affine_experts_ref,
    lut_affine_grouped_ref,
    lut_affine_ref,
)

pytestmark = pytest.mark.slow  # interpret-mode Pallas sweeps


# ---------------------------------------------------------------------------
# quantize_tables / table_scale
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fmt,qmax", [("i8", 127), ("i16", 32767)])
def test_quantize_tables_pow2_scale_and_error_bound(fmt, qmax):
    tables = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 8)) * 3.0
    q, scale = quantize_tables(tables, fmt)
    assert q.dtype == (jnp.int8 if fmt == "i8" else jnp.int16)
    s = float(scale)
    assert s == 2.0 ** round(np.log2(s))  # power of two: folding is a shift
    assert float(jnp.abs(q).max()) <= qmax
    # dequant error is at most half a quantization step
    err = np.abs(np.asarray(q, np.float32) * s - np.asarray(tables))
    assert err.max() <= s / 2 + 1e-7


def test_table_scale_trailing_shapes():
    # (L, G, k, E, p): trailing=4 covers one grouped set; the leading scan
    # dim L keeps per-entry scales so lax.scan can slice the leaf
    tables = jax.random.normal(jax.random.PRNGKey(1), (3, 2, 4, 8, 5))
    assert table_scale(tables, "i8", trailing=4).shape == (3,)
    assert table_scale(tables, "i8", trailing=3).shape == (3, 2)
    assert table_scale(tables, "i8").shape == ()  # None: whole-leaf scalar
    q, scale = quantize_tables(tables, "i8", trailing=4)
    assert scale.shape == (3,)
    for i in range(3):
        want = np.asarray(tables[i])
        got = np.asarray(q[i], np.float32) * float(scale[i])
        assert np.abs(got - want).max() <= float(scale[i]) / 2 + 1e-7


# ---------------------------------------------------------------------------
# Pallas kernels vs oracle: narrow tables
# ---------------------------------------------------------------------------

_GRID = [
    (1, 1, 1, 2, 1),  # degenerate minimum
    (4, 3, 7, 8, 10),  # ragged everything
    (16, 3, 32, 16, 96),  # bitplane_shift-style planes
    (3, 2, 130, 16, 130),  # k and p beyond one block
]


@pytest.mark.parametrize("dtype", [jnp.int8, jnp.int16])
@pytest.mark.parametrize("B,n,k,E,p", _GRID)
def test_lut_affine_narrow_matches_ref(B, n, k, E, p, dtype):
    kc, kt = jax.random.split(jax.random.PRNGKey(B * 13 + k))
    codes = jax.random.randint(kc, (B, n, k), 0, E)
    lim = int(jnp.iinfo(dtype).max)
    tables = jax.random.randint(kt, (k, E, p), -lim, lim, jnp.int32).astype(dtype)
    scales = 2.0 ** -jnp.arange(n, dtype=jnp.float32)  # dequant scale folded in
    got = lut_affine(codes, tables, scales, interpret=True)
    want = lut_affine_ref(codes, tables, scales)
    rel = 1e-5
    atol = rel * float(np.abs(np.asarray(want)).max() + 1.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=rel, atol=atol)


@pytest.mark.parametrize("dtype", [jnp.int8, jnp.int16])
def test_grouped_and_experts_narrow_match_ref(dtype):
    G, B, n, k, E, p = 3, 5, 2, 9, 16, 33
    kc, kt = jax.random.split(jax.random.PRNGKey(7))
    codes = jax.random.randint(kc, (B, n, k), 0, E)
    lim = int(jnp.iinfo(dtype).max)
    tables = jax.random.randint(kt, (G, k, E, p), -lim, lim, jnp.int32).astype(dtype)
    scales = jnp.asarray([1.0, 0.25])
    got = lut_affine_grouped(codes, tables, scales, interpret=True)
    want = lut_affine_grouped_ref(codes, tables, scales)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-3)

    NE, T = 2, 6
    etables = jnp.stack([tables, tables[::-1]])  # (NE, G, k, E, p)
    ecodes = jax.random.randint(jax.random.PRNGKey(8), (T, n, k), 0, E)
    group_sizes = jnp.asarray([4, 2], jnp.int32)
    got = lut_affine_experts(ecodes, etables, scales, group_sizes, interpret=True)
    want = lut_affine_experts_ref(ecodes, etables, scales, group_sizes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-3)


# ---------------------------------------------------------------------------
# Pallas kernels vs oracle: shift_bits (bitplane_shift contract)
# ---------------------------------------------------------------------------


def _shift_codes(key, shape, index_bits):
    """Packed codes: low index_bits = table index, high bits = fp16 exponent."""
    kf, ke = jax.random.split(key)
    field = jax.random.randint(kf, shape, 0, 2**index_bits)
    exp = jax.random.randint(ke, shape, 1, 13)  # sane sigma range
    return field | (exp << index_bits)


@pytest.mark.parametrize("B,n,k,E,p", [(4, 4, 7, 16, 10), (9, 4, 130, 16, 130)])
def test_lut_affine_shift_bits_matches_ref(B, n, k, E, p):
    index_bits = 4
    assert E == 2**index_bits
    kc, kt = jax.random.split(jax.random.PRNGKey(B + k))
    codes = _shift_codes(kc, (B, n, k), index_bits)
    tables = jax.random.randint(kt, (k, E, p), -7, 8, jnp.int32).astype(jnp.int8)
    scales = 2.0 ** (3.0 * jnp.arange(n, dtype=jnp.float32))  # radix-3 planes
    got = lut_affine(codes, tables, scales, shift_bits=index_bits, interpret=True)
    want = lut_affine_ref(codes, tables, scales, shift_bits=index_bits)
    rel = 1e-5
    atol = rel * float(np.abs(np.asarray(want)).max() + 1e-30)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=rel, atol=atol)


def test_grouped_shift_bits_matches_ref():
    index_bits, G, B, n, k, p = 4, 2, 4, 4, 16, 40
    E = 2**index_bits
    kc, kt = jax.random.split(jax.random.PRNGKey(3))
    codes = _shift_codes(kc, (B, n, k), index_bits)
    tables = jax.random.randint(kt, (G, k, E, p), -7, 8, jnp.int32).astype(jnp.int8)
    scales = 2.0 ** (3.0 * jnp.arange(n, dtype=jnp.float32))
    got = lut_affine_grouped(
        codes, tables, scales, shift_bits=index_bits, interpret=True
    )
    want = lut_affine_grouped_ref(codes, tables, scales, shift_bits=index_bits)
    rel = 1e-5
    atol = rel * float(np.abs(np.asarray(want)).max() + 1e-30)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=rel, atol=atol)


# ---------------------------------------------------------------------------
# bitplane_shift mode end to end
# ---------------------------------------------------------------------------


def test_bitplane_shift_matches_fp16_matmul():
    """Radix-4 mantissa planes + sigma-at-accumulate == the fp16 affine map."""
    fmt = Float16Format(signed=True, mantissa_radix=4)
    q, p = 64, 24
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    W = jax.random.normal(k1, (q, p)) / np.sqrt(q)
    x = jax.random.normal(k2, (8, q)) * 2.0
    plan = LUTPlan(q, p, 1, fmt, mode="bitplane_shift")
    assert len(plane_scales(plan)) == 3  # ceil(11 / 4) mantissa planes
    got = lut_affine_reference(x, W, None, plan)
    want = fmt.quantize(x).astype(jnp.float32) @ W
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6)


def test_bitplane_shift_tables_survive_i8_quantization():
    """Sigma-free table entries span only small integers times W-columns, so
    i8 storage keeps the result close — the property that makes the narrow
    frontier numerically safe (sigma-laden tables lose ~everything)."""
    fmt = Float16Format(signed=True, mantissa_radix=4)
    q, p = 64, 24
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    W = jax.random.normal(k1, (q, p)) / np.sqrt(q)
    x = jax.random.normal(k2, (8, q)) * 2.0
    plan = LUTPlan(q, p, 1, fmt, mode="bitplane_shift", table_format="i8")
    tables = build_luts(W, plan)
    qt, scale = quantize_tables(tables, "i8")
    codes = pack_codes(x, plan)
    scales = jnp.asarray(plane_scales(plan), jnp.float32) * scale
    got = apply_luts(qt, codes, plan, scales=scales)
    want = fmt.quantize(x).astype(jnp.float32) @ W
    # same bar as the planner's convert-equivalence check; sigma-laden
    # tables fail this by ~50x (rel err ~1.0), sigma-free pass easily
    denom = np.abs(np.asarray(want)).max() + 1e-6
    assert np.abs(np.asarray(got) - np.asarray(want)).max() / denom < 0.05
