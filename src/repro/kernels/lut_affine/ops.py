"""Jit'd public wrapper around the LUT affine Pallas kernel.

Handles padding to block multiples, block-size selection under a VMEM
budget, bias, and arbitrary leading batch dims.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import (
    ceil_to,
    check_acc_contract,
    check_entries,
    code_lanes,
    default_interpret,
    pad_axis,
)
from repro.kernels.lut_affine.autotune import TunePoint, check_blocks, pick_blocks
from repro.kernels.lut_affine.lut_affine import (
    lut_affine_experts_pallas,
    lut_affine_grouped_pallas,
    lut_affine_pallas,
)

def _tiling(blocks, B: int, k: int, E: int, p: int, n: int, G: int, tables):
    """``blocks`` checked against the kernels' tiling and VMEM rules, or the
    untuned default (``autotune.pick_blocks``) when ``blocks`` is None."""
    pt = TunePoint(
        B=B, k=k, entries=E, p=p, n=n, G=G, table_bytes=tables.dtype.itemsize
    )
    return check_blocks(pt, blocks) if blocks else pick_blocks(pt)


def _plane_major(codes2: jax.Array, rows: int, chunks: int, block_k: int):
    """(B, n, k) codes -> zero-padded (n, rows, code_lanes), the kernels'
    layout (padded chunks index entry 0 of a zero table -> contribute
    nothing; lanes past a narrow tile's chunks are never read)."""
    codes2 = jnp.swapaxes(codes2, 0, 1)
    return pad_axis(pad_axis(codes2, 1, rows), 2, code_lanes(chunks, block_k))


@functools.partial(
    jax.jit,
    static_argnames=("block_b", "block_p", "block_k", "interpret", "shift_bits"),
)
def _lut_affine_padded(
    codes, tables, scales, block_b, block_p, block_k, interpret, shift_bits
):
    return lut_affine_pallas(
        codes,
        tables,
        scales,
        block_b=block_b,
        block_p=block_p,
        block_k=block_k,
        interpret=interpret,
        shift_bits=shift_bits,
    )


def lut_affine(
    codes: jax.Array,  # (..., n, k) int32
    tables: jax.Array,  # (k, E, p)
    scales: jax.Array,  # (n,)
    bias: jax.Array | None = None,
    *,
    interpret: bool | None = None,
    blocks: tuple[int, int, int] | None = None,
    shift_bits: int = 0,
    plan=None,
) -> jax.Array:
    """out[..., :] = sum_j scales[j] * sum_c tables[c, codes[..., j, c], :] + bias

    ``blocks`` overrides the default ``autotune.pick_blocks`` tiling with autotuned
    ``(block_b, block_p, block_k)`` tile sizes (see ``autotune.py``);
    ``shift_bits`` selects the ``bitplane_shift`` code contract; ``plan``
    (a ``LUTPlan``) asserts the accumulator contract at trace time when it
    carries a proved ``max_abs_acc`` (this kernel accumulates fp32)."""
    if plan is not None:
        check_acc_contract("lut_affine", plan, "float32")
    if interpret is None:
        interpret = default_interpret()
    *lead, n, k = codes.shape
    k2, E, p = tables.shape
    assert k == k2, f"codes have {k} chunks, tables {k2}"  # before padding
    check_entries("lut_affine", E)
    B = 1
    for d in lead:
        B *= d

    block_b, block_p, block_k = _tiling(blocks, B, k, E, p, n, 1, tables)
    Bp, pp, kp = ceil_to(B, block_b), ceil_to(p, block_p), ceil_to(k, block_k)
    codes2 = _plane_major(codes.reshape(B, n, k), Bp, kp, block_k)
    tables_p = pad_axis(pad_axis(tables, 0, kp), 2, pp)

    out = _lut_affine_padded(
        codes2, tables_p, scales, block_b, block_p, block_k, interpret, shift_bits
    )[:B, :p]
    if bias is not None:
        out = out + bias.astype(out.dtype)
    return out.reshape(*lead, p)


@functools.partial(
    jax.jit,
    static_argnames=("block_b", "block_p", "block_k", "interpret", "shift_bits"),
)
def _lut_affine_grouped_padded(
    codes, tables, scales, block_b, block_p, block_k, interpret, shift_bits
):
    return lut_affine_grouped_pallas(
        codes,
        tables,
        scales,
        block_b=block_b,
        block_p=block_p,
        block_k=block_k,
        interpret=interpret,
        shift_bits=shift_bits,
    )


def lut_affine_grouped(
    codes: jax.Array,  # (..., n, k) int32 — one packed input for the group
    tables: jax.Array,  # (G, k, E, p) — same-shape projections, pre-stacked
    scales: jax.Array,  # (n,)
    biases: jax.Array | None = None,  # (G, p)
    *,
    interpret: bool | None = None,
    blocks: tuple[int, int, int] | None = None,
    shift_bits: int = 0,
    plan=None,
) -> jax.Array:
    """Fused batched decode path: ``out[g, ..., :] = lut_affine(codes,
    tables[g], scales) (+ biases[g])`` for all ``G`` projections in ONE
    Pallas grid — one dispatch per decode step for a whole QKV or gate/up
    group instead of one per projection.  ``tables`` is exactly the leaf a
    converted ``core.convert.LUTGroup`` stores (stacked once at conversion
    time), so serving never re-stacks per step."""
    if plan is not None:
        check_acc_contract("lut_affine_grouped", plan, "float32")
    if interpret is None:
        interpret = default_interpret()
    *lead, n, k = codes.shape
    G, k2, E, p = tables.shape
    assert k == k2, f"codes have {k} chunks, tables {k2}"  # before padding
    check_entries("lut_affine_grouped", E)
    B = 1
    for d in lead:
        B *= d

    block_b, block_p, block_k = _tiling(blocks, B, k, E, p, n, G, tables)
    Bp, pp, kp = ceil_to(B, block_b), ceil_to(p, block_p), ceil_to(k, block_k)
    codes2 = _plane_major(codes.reshape(B, n, k), Bp, kp, block_k)
    tables_p = pad_axis(pad_axis(tables, 1, kp), 3, pp)

    out = _lut_affine_grouped_padded(
        codes2, tables_p, scales, block_b, block_p, block_k, interpret, shift_bits
    )[:, :B, :p]
    if biases is not None:
        out = out + biases[:, None, :].astype(out.dtype)
    return out.reshape(G, *lead, p)


@functools.partial(
    jax.jit,
    static_argnames=("block_b", "block_p", "block_k", "interpret", "shift_bits"),
)
def _lut_affine_experts_padded(
    offsets, codes, tables, scales, block_b, block_p, block_k, interpret, shift_bits
):
    return lut_affine_experts_pallas(
        offsets,
        codes,
        tables,
        scales,
        block_b=block_b,
        block_p=block_p,
        block_k=block_k,
        interpret=interpret,
        shift_bits=shift_bits,
    )


def lut_affine_experts(
    codes: jax.Array,  # (T, n, k) int32 — tokens sorted by expert
    tables: jax.Array,  # (E, G, k, En, p) — pre-stacked expert tables
    scales: jax.Array,  # (n,)
    group_sizes: jax.Array,  # (E,) int32 tokens per expert, sum == T
    *,
    interpret: bool | None = None,
    blocks: tuple[int, int, int] | None = None,
    shift_bits: int = 0,
    plan=None,
) -> jax.Array:
    """Ragged MoE dispatch over pre-stacked expert tables: token row ``t``
    (sorted by expert, the ``lax.ragged_dot`` layout) is evaluated against
    its expert's ``tables[e]`` for all ``G`` fused projections in ONE grid —
    the LUT-affine replacement for a grouped GEMM.  ``tables`` is exactly
    the scan-sliced leaf a converted expert ``LUTGroup`` stores (a lone
    ``LUTLinear`` stack passes ``tables[:, None]``)."""
    if plan is not None:
        check_acc_contract("lut_affine_experts", plan, "float32")
    if interpret is None:
        interpret = default_interpret()
    T, n, k = codes.shape
    E, G, k2, En, p = tables.shape
    assert k == k2, f"codes have {k} chunks, tables {k2}"  # before padding
    assert group_sizes.shape == (E,), (group_sizes.shape, E)
    check_entries("lut_affine_experts", En)

    block_b, block_p, block_k = _tiling(blocks, T, k, En, p, n, 1, tables)
    Tp, pp, kp = ceil_to(T, block_b), ceil_to(p, block_p), ceil_to(k, block_k)
    # padded token rows sit past offsets[-1] -> outside every expert's row
    # range -> left at the kernel's zero init and sliced off below
    codes2 = _plane_major(codes, Tp, kp, block_k)
    tables_p = pad_axis(pad_axis(tables, 2, kp), 4, pp)
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(group_sizes.astype(jnp.int32))]
    )

    out = _lut_affine_experts_padded(
        offsets,
        codes2,
        tables_p,
        scales,
        block_b,
        block_p,
        block_k,
        interpret,
        shift_bits,
    )[:, :T, :p]
    return out
