"""Pallas TPU kernels for the TL1 activation-side LUT family.

Computes ``out[b, :] = sum_c lut_b[c, widx[c, :]]`` where ``widx`` are the
base-3 ternary weight-pair indices packed two-per-byte at conversion time
(``repro.core.lut_tl1.pack_ternary``) and ``lut_b`` is the per-token 9-entry
activation LUT built *inside the kernel* each step: entry ``i`` of pair
chunk ``c`` is ``(i//3 - 1) * a[2c] + (i%3 - 1) * a[2c+1]`` — nine sums /
differences of two activations, adds only.

TPU mapping
-----------
Same shape discipline as ``lut_affine``: grid ``(batch_tiles, out_tiles,
packed_chunk_tiles)`` with the output block revisited and accumulated across
chunk tiles.  Per step the table tile is ``(kb_block, p_block)`` **bytes**
(the packed indices, loaded as whole (32, 128) uint8 tiles and widened into
an int32 scratch), the activation tile is ``(4, bb, lanes)`` codes (slot-
major, 128-lane windows like ``lut_affine``'s codes), and each packed byte
unpacks to two nibble indices selecting from two freshly built 9-entry LUTs
of ``(bb, 1)`` columns.  The select is a compare-and-select tree
(``common.select_entries``) — the inverse of the weight family's row
select: here the table axis is tiny and the *index* operand is
weight-shaped.

LUT entries are int16, accumulation int32.  Both are *proved* per-plan
contracts, not folklore: ``repro.audit.ranges.layer_range_cert`` certifies
``|entry| <= 2*qa`` and ``|acc| <= 2*qa*num_chunks`` (``qa =
2**(act_bits-1) - 1``), the planner stamps the bound on each ``TL1Plan``
(``max_abs_acc`` / ``acc_dtype``) and rejects plans it cannot prove safe,
and the wrappers in ``ops.py`` re-assert the contract at trace time via
``repro.kernels.common.check_acc_contract``.  With fp32 activation codes
(``act_bits=None``) entries and accumulator stay fp32 and the kernel is
exact w.r.t. a dense matmul over the ternary weights.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (
    LANES,
    code_lanes,
    lane_column,
    lane_window,
    select_entries,
    window_start,
)


def _acc_dtypes(acts_dtype):
    if jnp.issubdtype(acts_dtype, jnp.integer):
        return jnp.int16, jnp.int32
    return jnp.float32, jnp.float32


def _pair_lut(a0, a1, entry_dtype, acc_dtype):
    """(bb, 1) x2 activation codes -> the 9 LUT entries, adds only.

    Entry ``i = (s0+1)*3 + (s1+1)`` holds ``s0*a0 + s1*a1``, rounded to the
    entry dtype and selected in the accumulator dtype (the chip's vector
    unit selects 32-bit lanes).
    """
    z = jnp.zeros_like(a0)
    lut = [-a0 - a1, -a0, a1 - a0, -a1, z, a1, a0 - a1, a0, a0 + a1]
    return [e.astype(entry_dtype).astype(acc_dtype) for e in lut]


_SLAB = 32  # packed-byte rows per aligned load: the (32, 128) uint8 tile


def _accumulate(acts_ref, table_slab, rows_ref, shape, *, block_k: int):
    """Shared accumulate over one packed-chunk tile.

    acts_ref  : (4, bb, lanes) VMEM — slot ``j`` of packed byte ``c``
                (element ``4c + j``) is lane ``c`` of plane ``j``, read from
                one 128-lane window at a time (``common.code_lanes``)
    table_slab: r -> (32, pb) uint8 packed rows ``r .. r+31`` (``r`` a
                multiple of 32: uint8 rows load in whole (32, 128) tiles)
    rows_ref  : (32, pb) int32 VMEM scratch — the slab widened, so each
                row loads on its own
    """
    entry_dtype, acc_dtype = _acc_dtypes(acts_ref.dtype)
    window = lane_window(block_k)

    def window_body(w, acc):
        c0 = window_start(w, block_k)
        acts = [acts_ref[j, :, pl.ds(c0, LANES)] for j in range(4)]

        def slab_body(s, acc):
            r0 = pl.multiple_of(s * _SLAB, _SLAB)
            rows_ref[...] = table_slab(pl.multiple_of(c0 + r0, _SLAB)).astype(
                jnp.int32
            )

            def row_body(r, acc):
                a = [lane_column(t, r0 + r) for t in acts]  # (bb, 1) each
                w_row = rows_ref[pl.ds(r, 1), :]  # (1, pb) packed byte
                lo, hi = w_row & 15, w_row >> 4
                for idx, lut in (
                    (lo, _pair_lut(a[0], a[1], entry_dtype, acc_dtype)),
                    (hi, _pair_lut(a[2], a[3], entry_dtype, acc_dtype)),
                ):
                    acc = acc + select_entries(lut, idx)
                return acc

            return jax.lax.fori_loop(0, _SLAB, row_body, acc)

        return jax.lax.fori_loop(0, window // _SLAB, slab_body, acc)

    return jax.lax.fori_loop(
        0, block_k // window, window_body, jnp.zeros(shape, acc_dtype)
    )


def _kernel(acts_ref, tables_ref, out_ref, rows_ref, *, block_k: int):
    """One (batch, out, packed-chunk) grid step.

    tables_ref: (kb, pb) uint8 VMEM — packed base-3 weight-pair indices
    out_ref   : (bb, pb) int32/f32 VMEM (revisited across chunk tiles)
    """

    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += _accumulate(
        acts_ref,
        lambda r: tables_ref[pl.ds(r, _SLAB), :],
        rows_ref,
        out_ref.shape,
        block_k=block_k,
    )


def _grouped_kernel(acts_ref, tables_ref, out_ref, rows_ref, *, block_k: int):
    """One (group, batch, out, packed-chunk) grid step.

    The activation tile is shared across the group dimension — the fused
    projections all quantize the same input once — only the per-group
    packed-index tile changes.

    tables_ref: (1, kb, pb) u8 VMEM (leading 1 = this group)
    out_ref   : (1, bb, pb)    VMEM (revisited across chunk tiles)
    """

    @pl.when(pl.program_id(3) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[0] += _accumulate(
        acts_ref,
        lambda r: tables_ref[0, pl.ds(r, _SLAB), :],
        rows_ref,
        out_ref.shape[1:],
        block_k=block_k,
    )


def lut_tl1_pallas(
    acts: jax.Array,  # (4, B, lanes) int32 (f32: exact variant), code_lanes
    tables: jax.Array,  # (kb, p) uint8 packed indices
    *,
    block_b: int,
    block_p: int,
    block_k: int,
    interpret: bool,
) -> jax.Array:
    four, B, lanes = acts.shape
    kb, p = tables.shape
    assert four == 4, acts.shape
    assert lanes == code_lanes(kb, block_k), (acts.shape, tables.shape)
    assert B % block_b == 0 and p % block_p == 0 and kb % block_k == 0
    grid = (B // block_b, p // block_p, kb // block_k)
    _, acc_dtype = _acc_dtypes(acts.dtype)

    return pl.pallas_call(
        functools.partial(_kernel, block_k=block_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (4, block_b, code_lanes(block_k, block_k)),
                lambda b, q, c: (0, b, c),
            ),
            pl.BlockSpec((block_k, block_p), lambda b, q, c: (c, q)),
        ],
        out_specs=pl.BlockSpec((block_b, block_p), lambda b, q, c: (b, q)),
        out_shape=jax.ShapeDtypeStruct((B, p), acc_dtype),
        scratch_shapes=[pltpu.VMEM((_SLAB, block_p), jnp.int32)],
        interpret=interpret,
    )(acts, tables)


def lut_tl1_grouped_pallas(
    acts: jax.Array,  # (4, B, lanes) — one quantized input for the group
    tables: jax.Array,  # (G, kb, p) uint8
    *,
    block_b: int,
    block_p: int,
    block_k: int,
    interpret: bool,
) -> jax.Array:
    """All ``G`` same-shape TL1 projections of one decode step in a single
    grid — one dispatch per step for a whole QKV or gate/up group."""
    four, B, lanes = acts.shape
    G, kb, p = tables.shape
    assert four == 4, acts.shape
    assert lanes == code_lanes(kb, block_k), (acts.shape, tables.shape)
    assert B % block_b == 0 and p % block_p == 0 and kb % block_k == 0
    grid = (G, B // block_b, p // block_p, kb // block_k)
    _, acc_dtype = _acc_dtypes(acts.dtype)

    return pl.pallas_call(
        functools.partial(_grouped_kernel, block_k=block_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (4, block_b, code_lanes(block_k, block_k)),
                lambda g, b, q, c: (0, b, c),
            ),
            pl.BlockSpec((1, block_k, block_p), lambda g, b, q, c: (g, c, q)),
        ],
        out_specs=pl.BlockSpec((1, block_b, block_p), lambda g, b, q, c: (g, b, q)),
        out_shape=jax.ShapeDtypeStruct((G, B, p), acc_dtype),
        scratch_shapes=[pltpu.VMEM((_SLAB, block_p), jnp.int32)],
        interpret=interpret,
    )(acts, tables)
