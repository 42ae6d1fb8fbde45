"""Jit'd public wrappers around the TL1 Pallas kernels.

Handles padding to block multiples, block-size selection under the VMEM
budget, dequantization (per-token activation scale x ternary weight scale),
bias, and arbitrary leading batch dims.  Input is the flat padded code
vector ``repro.core.lut_tl1.quantize_acts`` produces.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import (
    ceil_to,
    check_acc_contract,
    code_lanes,
    default_interpret,
    pad_axis,
)
from repro.kernels.lut_affine.autotune import TunePoint, check_blocks, pick_blocks
from repro.kernels.lut_tl1.lut_tl1 import lut_tl1_grouped_pallas, lut_tl1_pallas

def _tiling(blocks, B: int, kb: int, p: int, G: int):
    """``blocks`` checked against the kernels' tiling and VMEM rules, or the
    untuned default (``autotune.pick_blocks``) when ``blocks`` is None.
    The packed-index tile is ``kb_block * p_block`` BYTES, so block_k
    usually reaches far along the packed axis."""
    pt = TunePoint(
        B=B, k=kb, entries=9, p=p, n=1, G=G, table_bytes=1, family="tl1"
    )
    return check_blocks(pt, blocks) if blocks else pick_blocks(pt)


def _acts3(acts: jax.Array, kb: int, rows: int, chunks: int, block_k: int):
    """(..., 4*kb) flat codes -> zero-padded (4, rows, code_lanes) kernel
    layout (slot ``j`` of packed byte ``c`` at ``[j, :, c]``) + lead dims.
    Padded chunk rows meet zero activation codes -> every LUT entry they
    can index is 0."""
    *lead, q4 = acts.shape
    assert q4 == 4 * kb, (q4, kb)
    B = 1
    for d in lead:
        B *= d
    acts3 = jnp.transpose(acts.reshape(B, kb, 4), (2, 0, 1))
    return pad_axis(pad_axis(acts3, 1, rows), 2, code_lanes(chunks, block_k)), lead


def _rows(acts: jax.Array) -> int:
    B = 1
    for d in acts.shape[:-1]:
        B *= d
    return B


def _dequant(out, act_scale, scale, bias):
    out = out.astype(jnp.float32)
    if act_scale is not None:
        out = out * act_scale
    if scale is not None:
        out = out * scale
    if bias is not None:
        out = out + bias.astype(out.dtype)
    return out


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_p", "block_k", "interpret")
)
def _lut_tl1_padded(acts, tables, block_b, block_p, block_k, interpret):
    return lut_tl1_pallas(
        acts,
        tables,
        block_b=block_b,
        block_p=block_p,
        block_k=block_k,
        interpret=interpret,
    )


def lut_tl1(
    acts: jax.Array,  # (..., 4*kb) int32 codes (or f32, exact variant)
    tables: jax.Array,  # (kb, p) uint8 packed base-3 indices
    act_scale: jax.Array | None = None,  # (..., 1) per-token dequant scale
    scale: jax.Array | None = None,  # ternary weight scale
    bias: jax.Array | None = None,  # (p,)
    *,
    interpret: bool | None = None,
    blocks: tuple[int, int, int] | None = None,
    plan=None,
) -> jax.Array:
    """out[..., :] = act_scale * scale * sum_c lut[c, widx[c, :]] + bias

    ``blocks`` overrides the default ``autotune.pick_blocks`` tiling with autotuned
    ``(block_b, block_p, block_k)`` tile sizes (block_k in packed bytes);
    ``plan`` (a ``TL1Plan``) asserts the accumulator contract at trace time
    when it carries a proved ``max_abs_acc``."""
    if plan is not None:
        check_acc_contract(
            "lut_tl1",
            plan,
            "int32" if jnp.issubdtype(acts.dtype, jnp.integer) else "float32",
        )
    if interpret is None:
        interpret = default_interpret()
    kb, p = tables.shape
    B = _rows(acts)

    block_b, block_p, block_k = _tiling(blocks, B, kb, p, 1)
    Bp, pp, kp = ceil_to(B, block_b), ceil_to(p, block_p), ceil_to(kb, block_k)
    acts3, lead = _acts3(acts, kb, Bp, kp, block_k)
    # padded p columns are sliced off below
    tables_p = pad_axis(pad_axis(tables, 0, kp), 1, pp)

    out = _lut_tl1_padded(acts3, tables_p, block_b, block_p, block_k, interpret)
    out = out[:B, :p].reshape(*lead, p)
    if act_scale is not None:
        act_scale = act_scale.reshape(*lead, 1)
    return _dequant(out, act_scale, scale, bias)


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_p", "block_k", "interpret")
)
def _lut_tl1_grouped_padded(acts, tables, block_b, block_p, block_k, interpret):
    return lut_tl1_grouped_pallas(
        acts,
        tables,
        block_b=block_b,
        block_p=block_p,
        block_k=block_k,
        interpret=interpret,
    )


def lut_tl1_grouped(
    acts: jax.Array,  # (..., 4*kb) — one quantized input for the group
    tables: jax.Array,  # (G, kb, p) uint8 — pre-stacked same-shape projections
    act_scale: jax.Array | None = None,  # (..., 1)
    scale: jax.Array | None = None,  # (G,) per-member ternary scales
    biases: jax.Array | None = None,  # (G, p)
    *,
    interpret: bool | None = None,
    blocks: tuple[int, int, int] | None = None,
    plan=None,
) -> jax.Array:
    """Fused batched decode path: ``out[g] = lut_tl1(acts, tables[g],
    act_scale, scale[g]) (+ biases[g])`` for all ``G`` projections in ONE
    Pallas grid.  ``tables`` is exactly the leaf a TL1-converted
    ``core.convert.LUTGroup`` stores."""
    if plan is not None:
        check_acc_contract(
            "lut_tl1_grouped",
            plan,
            "int32" if jnp.issubdtype(acts.dtype, jnp.integer) else "float32",
        )
    if interpret is None:
        interpret = default_interpret()
    G, kb, p = tables.shape
    B = _rows(acts)

    block_b, block_p, block_k = _tiling(blocks, B, kb, p, G)
    Bp, pp, kp = ceil_to(B, block_b), ceil_to(p, block_p), ceil_to(kb, block_k)
    acts3, lead = _acts3(acts, kb, Bp, kp, block_k)
    tables_p = pad_axis(pad_axis(tables, 1, kp), 2, pp)

    out = _lut_tl1_grouped_padded(
        acts3, tables_p, block_b, block_p, block_k, interpret
    )
    out = out[:, :B, :p].reshape(G, *lead, p)
    if act_scale is not None:
        act_scale = act_scale.reshape(*lead, 1)
    if scale is not None:
        scale = scale.reshape(G, *([1] * (out.ndim - 1)))
    if biases is not None:
        biases = biases.reshape(G, *([1] * (out.ndim - 2)), p)
    return _dequant(out, act_scale, scale, biases)
