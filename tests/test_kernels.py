"""Per-kernel allclose sweeps: Pallas (interpret=True on CPU) vs jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.lut import LUTPlan, build_luts, pack_codes, plane_scales
from repro.core.quantize import FixedPointFormat
from repro.kernels.binary_matmul.ops import binary_matmul
from repro.kernels.binary_matmul.ref import binary_matmul_ref
from repro.kernels.bitplane_pack.ops import bitplane_pack
from repro.kernels.bitplane_pack.ref import bitplane_pack_ref
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

pytestmark = pytest.mark.slow  # interpret-mode Pallas sweeps: ~45s on CPU


# ---------------------------------------------------------------------------
# lut_affine
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,n,k,E,p",
    [
        (1, 1, 1, 2, 1),  # degenerate minimum
        (4, 3, 7, 8, 10),  # ragged everything
        (16, 11, 32, 4, 96),  # fp16-style planes (bitplane_shift tables)
        (3, 4, 130, 16, 130),  # k and p beyond one block
        (130, 2, 5, 8, 257),  # batch beyond one block, odd p
    ],
)
def test_lut_affine_matches_ref(B, n, k, E, p, dtype):
    kc, kt, ks = jax.random.split(jax.random.PRNGKey(B * 7 + k), 3)
    codes = jax.random.randint(kc, (B, n, k), 0, E)
    tables = jax.random.normal(kt, (k, E, p), dtype=jnp.float32).astype(dtype)
    scales = 2.0 ** jnp.arange(n, dtype=jnp.float32)
    got = lut_affine(codes, tables, scales, interpret=True)
    want = lut_affine_ref(codes, tables, scales)
    # blocked accumulation reorders fp32 sums; scale atol to the output range
    rel = 1e-5 if dtype == jnp.float32 else 2e-2
    atol = rel * float(np.abs(np.asarray(want)).max() + 1.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=rel, atol=atol)


def test_lut_affine_leading_dims_and_bias():
    kc, kt = jax.random.split(jax.random.PRNGKey(0))
    codes = jax.random.randint(kc, (2, 3, 4, 8), 0, 16)  # (d0, d1, n, k)
    tables = jax.random.normal(kt, (8, 16, 12))
    scales = jnp.ones((4,))
    bias = jnp.arange(12.0)
    got = lut_affine(codes, tables, scales, bias=bias, interpret=True)
    ref = lut_affine_ref(codes.reshape(6, 4, 8), tables, scales)
    want = ref.reshape(2, 3, 12) + bias
    # blocked accumulation reorders fp32 sums (same slack as matches_ref)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_lut_affine_end_to_end_exact_vs_core():
    """Kernel path == core oracle == quantised matmul, bitwise (int weights)."""
    fmt = FixedPointFormat(4, 2, signed=True)
    q, p, m = 50, 33, 3
    plan = LUTPlan(q, p, m, fmt)
    kw, kx = jax.random.split(jax.random.PRNGKey(5))
    W = jax.random.randint(kw, (q, p), -8, 8).astype(jnp.float32)
    x = jax.random.uniform(kx, (9, q), minval=-3.0, maxval=3.0)
    tables = build_luts(W, plan)
    codes = pack_codes(x, plan)
    scales = jnp.asarray(plane_scales(plan), jnp.float32)
    got = lut_affine(codes, tables, scales, interpret=True)
    xq = fmt.dequantize(fmt.quantize(x))
    np.testing.assert_allclose(np.asarray(got), np.asarray(xq @ W), rtol=0, atol=0)


# ---------------------------------------------------------------------------
# lut_affine_grouped (fused batched decode path)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "G,B,n,k,E,p",
    [
        (1, 1, 1, 1, 2, 1),  # degenerate minimum
        (3, 4, 3, 7, 8, 10),  # QKV-style group, ragged everything
        (2, 16, 11, 32, 4, 96),  # gate/up-style group, fp16 planes
        (4, 3, 4, 130, 16, 130),  # k and p beyond one block
        (2, 130, 2, 5, 8, 257),  # batch beyond one block, odd p
    ],
)
def test_lut_affine_grouped_matches_ref(G, B, n, k, E, p, dtype):
    kc, kt = jax.random.split(jax.random.PRNGKey(G * 13 + B * 7 + k), 2)
    codes = jax.random.randint(kc, (B, n, k), 0, E)
    tables = jax.random.normal(kt, (G, k, E, p), dtype=jnp.float32).astype(dtype)
    scales = 2.0 ** jnp.arange(n, dtype=jnp.float32)
    got = lut_affine_grouped(codes, tables, scales, interpret=True)
    want = lut_affine_grouped_ref(codes, tables, scales)
    # same slack as the ungrouped kernel: blocked fp32 accumulation order
    rel = 1e-5 if dtype == jnp.float32 else 2e-2
    atol = rel * float(np.abs(np.asarray(want)).max() + 1.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=rel, atol=atol)
    # fused grid == G separate dispatches of the per-projection kernel
    per = jnp.stack(
        [lut_affine(codes, tables[g], scales, interpret=True) for g in range(G)]
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(per), rtol=rel, atol=atol)


def test_lut_affine_grouped_leading_dims_and_bias():
    kc, kt = jax.random.split(jax.random.PRNGKey(1))
    codes = jax.random.randint(kc, (2, 3, 4, 8), 0, 16)  # (d0, d1, n, k)
    tables = jax.random.normal(kt, (3, 8, 16, 12))
    scales = jnp.ones((4,))
    biases = jnp.arange(36.0).reshape(3, 12)
    got = lut_affine_grouped(codes, tables, scales, biases=biases, interpret=True)
    assert got.shape == (3, 2, 3, 12)
    want = lut_affine_grouped_ref(codes.reshape(6, 4, 8), tables, scales).reshape(
        3, 2, 3, 12
    ) + biases[:, None, None, :]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# lut_affine_experts (ragged MoE dispatch over pre-stacked expert tables)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "E,G,T,n,k,En,p,sizes",
    [
        (1, 1, 1, 1, 1, 2, 1, (1,)),  # degenerate minimum
        (4, 2, 11, 3, 7, 8, 10, (3, 0, 6, 2)),  # gate/up stack, empty group
        (8, 1, 16, 11, 32, 4, 96, (2,) * 8),  # w_down stack, fp16 planes
        (3, 2, 130, 2, 5, 16, 129, (50, 0, 80)),  # T and p beyond one block
        (2, 2, 6, 4, 130, 16, 130, (1, 5)),  # k beyond one block, skewed
    ],
)
def test_lut_affine_experts_matches_ref(E, G, T, n, k, En, p, sizes, dtype):
    kc, kt = jax.random.split(jax.random.PRNGKey(E * 13 + T * 7 + k), 2)
    codes = jax.random.randint(kc, (T, n, k), 0, En)
    tables = jax.random.normal(kt, (E, G, k, En, p), dtype=jnp.float32).astype(dtype)
    scales = 2.0 ** jnp.arange(n, dtype=jnp.float32)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    got = lut_affine_experts(codes, tables, scales, group_sizes, interpret=True)
    want = lut_affine_experts_ref(codes, tables, scales, group_sizes)
    rel = 1e-5 if dtype == jnp.float32 else 2e-2
    atol = rel * float(np.abs(np.asarray(want)).max() + 1.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=rel, atol=atol)


def test_lut_affine_experts_equals_segmented_per_expert_dispatch():
    """The ragged grid == slicing each expert's row segment and running the
    plain grouped kernel on it (the oracle-of-oracles cross-check)."""
    E, G, n, k, En, p = 3, 2, 4, 6, 16, 12
    sizes = (4, 0, 5)
    T = sum(sizes)
    kc, kt = jax.random.split(jax.random.PRNGKey(9), 2)
    codes = jax.random.randint(kc, (T, n, k), 0, En)
    tables = jax.random.normal(kt, (E, G, k, En, p))
    scales = 0.5 ** jnp.arange(n, dtype=jnp.float32)
    got = lut_affine_experts(
        codes, tables, scales, jnp.asarray(sizes, jnp.int32), interpret=True
    )
    start = 0
    segs = []
    for e, sz in enumerate(sizes):
        if sz:
            segs.append(
                lut_affine_grouped(
                    codes[start : start + sz], tables[e], scales, interpret=True
                )
            )
        start += sz
    want = jnp.concatenate(segs, axis=1)  # (G, T, p) in expert order
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


def test_pick_blocks_respects_vmem_budget_for_groups():
    """The default tiling obeys the (8, 128) tiling and the VMEM budget at
    every group fan-out G.  The grouped grid walks G, holding one member's
    double-buffered table tile at a time, so G must not change the pick."""
    from repro.kernels.lut_affine.autotune import (
        VMEM_BUDGET,
        TunePoint,
        block_k_options,
        pick_blocks,
        vmem_bytes,
    )

    shapes = [  # (k, E, p)
        (7, 2, 64),
        (1024, 16, 4096),  # granite_8b attention, 4-element chunks
        (3584, 16, 4096),  # granite_8b w_down
        (4096, 4, 14336),  # bitplane_shift, one element per chunk
        (352, 16, 300),  # ragged k and p: padded to the tiling
    ]
    for table_bytes in (1, 2, 4):
        for k, E, p in shapes:
            picks = set()
            for G in (1, 2, 3, 8):
                pt = TunePoint(B=8, k=k, entries=E, p=p, n=11, G=G,
                               table_bytes=table_bytes)
                bb, bp, bk = pick_blocks(pt)
                assert bb % 8 == 0 and bp % 128 == 0, (pt, bb, bp)
                assert bk in block_k_options(k), (pt, bk)
                assert vmem_bytes(pt, (bb, bp, bk)) <= VMEM_BUDGET, pt
                picks.add((bb, bp, bk))
            assert len(picks) == 1, (k, E, p, picks)


# ---------------------------------------------------------------------------
# bitplane_pack
# ---------------------------------------------------------------------------


@given(
    B=st.integers(1, 9),
    q=st.integers(1, 70),
    m=st.integers(1, 4),
    bits=st.integers(2, 8),
    frac=st.integers(0, 4),
    signed=st.booleans(),
)
@settings(max_examples=15, deadline=None)
def test_pack_fixed_matches_ref(B, q, m, bits, frac, signed):
    x = jax.random.uniform(
        jax.random.PRNGKey(B * q), (B, q), minval=-4.0, maxval=4.0
    )
    kw = dict(kind="fixed", bits=bits, frac=frac, signed=signed, m=m)
    got = bitplane_pack(x, interpret=True, **kw)
    want = bitplane_pack_ref(x, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("B,q,m", [(1, 1, 1), (5, 33, 2), (8, 130, 4), (130, 16, 1)])
def test_pack_float16_matches_ref(B, q, m):
    x = jax.random.uniform(jax.random.PRNGKey(q), (B, q), maxval=100.0)
    x = x * (jax.random.uniform(jax.random.PRNGKey(q + 1), (B, q)) > 0.1)
    kw = dict(kind="float16", bits=16, frac=0, signed=False, m=m)
    got = bitplane_pack(x, interpret=True, **kw)
    want = bitplane_pack_ref(x, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_pack_float16_subnormals():
    x = jnp.asarray([[5.96e-8, 1.2e-7, 6.0e-5, 0.0]])
    kw = dict(kind="float16", bits=16, frac=0, signed=False, m=2)
    got = bitplane_pack(x, interpret=True, **kw)
    want = bitplane_pack_ref(x, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# binary_matmul
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,n,q,p",
    [(1, 1, 1, 1), (4, 8, 100, 30), (65, 11, 300, 140), (2, 16, 513, 257)],
)
def test_binary_matmul_matches_ref(B, n, q, p, dtype):
    kp, kw = jax.random.split(jax.random.PRNGKey(n * q))
    planes = jax.random.bernoulli(kp, 0.5, (B, n, q)).astype(jnp.int8)
    W = (jax.random.normal(kw, (q, p)) / np.sqrt(q)).astype(dtype)
    scales = 0.5 ** jnp.arange(n, dtype=jnp.float32)
    got = binary_matmul(planes, W, scales, interpret=True)
    want = binary_matmul_ref(planes, W, scales)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


def test_binary_matmul_equals_lut_path():
    """The MXU path computes the same function as the m=1 LUT path (exact,
    integer weights): validates the beyond-paper optimisation's correctness
    claim from DESIGN.md §2."""
    fmt = FixedPointFormat(5, 3, signed=True)
    q, p = 40, 17
    plan = LUTPlan(q, p, 1, fmt)
    kw, kx = jax.random.split(jax.random.PRNGKey(11))
    W = jax.random.randint(kw, (q, p), -8, 8).astype(jnp.float32)
    x = jax.random.uniform(kx, (6, q), minval=-2.0, maxval=2.0)
    codes = pack_codes(x, plan)  # (6, n, k=q) with m=1: code == bit
    scales = jnp.asarray(plane_scales(plan), jnp.float32)
    via_bmm = binary_matmul(codes.astype(jnp.int8), W, scales, interpret=True)
    tables = build_luts(W, plan)
    via_lut = lut_affine(codes, tables, scales, interpret=True)
    np.testing.assert_allclose(np.asarray(via_bmm), np.asarray(via_lut), rtol=0, atol=0)
