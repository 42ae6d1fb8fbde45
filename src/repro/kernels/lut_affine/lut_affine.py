"""Pallas TPU kernel for the paper-faithful LUT affine map.

Computes ``out[b, :] = sum_j scales[j] * sum_c tables[c, codes[b, j, c], :]``
— the TableNet bitplane shift-and-add — with the tables resident in VMEM.

TPU mapping
-----------
The FPGA "RAM read per chunk" becomes a *row select* from a VMEM-resident
``(entries, p_block)`` tile: a binary tree of compare-and-selects over the
entries (``common.select_entries``, at most ``MAX_SELECT_ENTRIES``), the
multiplier-free gather Mosaic lowers.  The grid is ``(batch_tiles,
out_tiles, chunk_tiles)``; chunk tiles revisit the output block and
accumulate, so arbitrarily large layers stream through a fixed VMEM budget
(``autotune.vmem_bytes``).

Codes arrive plane-major, ``(n, B, lanes)``: a plane is a leading-dim load
and a chunk is a lane column of a 128-lane window, brought to lane 0 by a
rotate.  Tile shapes obey the (8, 128) tiling: the batch tile is a multiple
of 8, the output tile a multiple of 128, and the chunk tile a multiple of
128 or the whole chunk axis (its codes then padded to one 128-lane window,
``common.code_lanes``).

A grid step walks its chunks and planes in one of two orders, chosen at
trace time from ``(block_b, block_p, planes)`` (``loop_order``):

* chunk-outer, while the per-plane partials stay small (every decode
  tile, and batch tiles up to 32 rows at 8 planes and 512 lanes).  Once
  per chunk the table tile is loaded, widened to f32 and each row
  broadcast to an (8, pb) block.  Then, per 8-row group and plane, only
  the code column's rotate, broadcast and bit tests, the select tree and
  one add into that plane's f32 partial (a VMEM scratch) remain.  The
  planes of a chunk are independent of each other, so an iteration of
  the chunk loop holds one chain per plane where the plane-outer order
  has a single short dependent chain.
* plane-outer, for wider batch tiles: per (plane, chunk) the whole
  ``(bb, pb)`` block is gathered from the tile (``common.select_entries``)
  into the plane's partial, a loop carry, so the table-side work is done
  once per plane.

Both keep one f32 partial per plane, add the chunks to it in chunk order
and combine the partials as ``acc + scales[j] * plane_j`` in plane order,
so they give the same bits.  The chunk loops are ``fori_loop``s.
All accumulation is fp32 regardless of the table dtype — narrow
(int8/int16) tables are widened to f32 in the kernel, their dequant scale folded
into ``scales`` by the caller — matching the paper's full-precision-output
claim.

``shift_bits > 0`` selects the ``bitplane_shift`` contract: the code's low
``shift_bits`` index the (tiny, exponent-free) table and its high bits carry
the element's fp16 exponent, applied to the gathered row as
``2**(max(e,1)-25)`` — the barrel shift of the mode's name.
"""
from __future__ import annotations

import collections
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

_SCALES = pl.BlockSpec(memory_space=pltpu.SMEM)  # (n,) plane scales, whole


def _table_rows(table):
    """(E, pb) table tile -> its E rows, each (1, pb) f32."""
    rows = table.astype(jnp.float32)
    return [rows[e : e + 1] for e in range(rows.shape[0])]


def _gather(rows, code, shift_bits: int):
    """A chunk's E f32 table rows + (bb, 1) codes -> (bb, pb) gathered rows,
    sigma-scaled when the codes carry an exponent in their high bits
    (bitplane_shift)."""
    E = len(rows)
    idx = code & (E - 1) if shift_bits else code
    out = select_entries(rows, idx)
    if shift_bits:
        sig = jnp.exp2(jnp.maximum(code >> shift_bits, 1).astype(jnp.float32) - 25.0)
        out = out * sig
    return out


# Rows of one f32 vreg: the chunk-outer order gathers 8 batch rows at a time.
SUBLANES = 8

# Most f32 vregs of per-plane partials (planes x block_b x block_p / 1024)
# for which a grid step walks chunks outside planes.  Measured on a v5e at
# granite_8b's gate/up shape (8 planes, 512-lane output tiles), ms a call,
# plane-outer against chunk-outer: 8 rows (32 vregs) 82.0 / 15.2; 32 rows
# (128 vregs) 92.2 / 67.0; 64 rows (256 vregs) 118.5 / 130.2.
CHUNK_OUTER_MAX_PARTIALS = 128

# Kernels traced per loop order (``"chunk_outer"``, ``"plane_outer"``):
# incremented when ``_accumulate`` is traced, so once per compiled kernel.
ORDERS_COMPILED: collections.Counter = collections.Counter()


def loop_order(block_b: int, block_p: int, planes: int) -> str:
    """The loop order a ``(block_b, block_p)`` grid step over ``planes``
    bitplanes compiles to (see ``CHUNK_OUTER_MAX_PARTIALS``)."""
    partials = planes * block_b * block_p // (SUBLANES * LANES)
    return "chunk_outer" if partials <= CHUNK_OUTER_MAX_PARTIALS else "plane_outer"


def _chunk_outer(
    codes_ref, table_at, scales_ref, shape, *, block_k, planes, shift_bits
):
    """Chunks outside, planes inside: each chunk's table tile is loaded,
    widened and its rows broadcast to (8, pb) once, then every 8-row group
    and every plane gathers from them into its own f32 partial, kept in a
    VMEM scratch of ``(planes, bb, pb)``."""
    bb, pb = shape
    window = lane_window(block_k)

    def body(parts_ref):
        parts_ref[...] = jnp.zeros(parts_ref.shape, jnp.float32)

        def window_body(w, carry):
            c0 = window_start(w, block_k)

            def chunk_body(c, carry):
                rows = [
                    jnp.broadcast_to(row, (SUBLANES, pb))
                    for row in _table_rows(table_at(c0 + c))
                ]

                def group_body(g, carry):
                    r = pl.ds(pl.multiple_of(g * SUBLANES, SUBLANES), SUBLANES)
                    for j in range(planes):
                        col = lane_column(codes_ref[j, r, pl.ds(c0, LANES)], c)
                        gathered = _gather(rows, col, shift_bits)
                        parts_ref[j, r, :] = parts_ref[j, r, :] + gathered
                    return carry

                return jax.lax.fori_loop(0, bb // SUBLANES, group_body, carry)

            return jax.lax.fori_loop(0, window, chunk_body, carry)

        jax.lax.fori_loop(0, block_k // window, window_body, 0)
        acc = jnp.zeros(shape, jnp.float32)
        for j in range(planes):
            acc = acc + scales_ref[j] * parts_ref[j]
        return acc

    return pl.run_scoped(body, pltpu.VMEM((planes, bb, pb), jnp.float32))


def _plane_outer(
    codes_ref, table_at, scales_ref, shape, *, block_k, planes, shift_bits
):
    """Planes outside, chunks inside: each (plane, chunk) widens the table
    tile and gathers its full ``(bb, pb)`` block from it."""
    window = lane_window(block_k)

    def plane_body(j, acc):
        def window_body(w, plane):
            c0 = window_start(w, block_k)
            codes = codes_ref[j, :, pl.ds(c0, LANES)]  # (bb, 128)

            def chunk_body(c, plane):
                col = lane_column(codes, c)
                rows = _table_rows(table_at(c0 + c))
                return plane + _gather(rows, col, shift_bits)

            return jax.lax.fori_loop(0, window, chunk_body, plane)

        plane = jax.lax.fori_loop(
            0, block_k // window, window_body, jnp.zeros(shape, jnp.float32)
        )
        return acc + scales_ref[j] * plane

    return jax.lax.fori_loop(0, planes, plane_body, jnp.zeros(shape, jnp.float32))


def _accumulate(
    codes_ref, table_at, scales_ref, shape, *, block_k, planes, shift_bits
):
    """``sum_j scales[j] * sum_c table_at(c)[codes[j, :, c]]`` over one
    chunk tile, in the loop order ``loop_order`` picks for the tile.

    codes_ref : (n, bb, lanes) int32 VMEM — plane-major: a plane is a
                leading-dim load, a chunk a lane column, read from one
                128-lane window at a time (``common.code_lanes``)
    table_at  : c -> (E, pb) table tile of chunk ``c``
    scales_ref: (n,) f32 SMEM

    Chunk-outer (``_chunk_outer``) loads, widens and broadcasts each
    chunk's table tile once for all planes and 8-row groups; per plane and
    group only the code column's rotate and bit tests, the select tree and
    the add remain.  Plane-outer (``_plane_outer``) prepares the tile again
    for every plane and gathers the whole ``(bb, pb)`` block at once.  Both
    sum each plane's chunks into its own f32 partial in chunk order and
    combine the partials as ``acc + scales[j] * plane_j`` in plane order,
    so every output bit is the same whichever runs.
    """
    order = loop_order(*shape, planes)
    ORDERS_COMPILED[order] += 1
    fn = _chunk_outer if order == "chunk_outer" else _plane_outer
    return fn(
        codes_ref,
        table_at,
        scales_ref,
        shape,
        block_k=block_k,
        planes=planes,
        shift_bits=shift_bits,
    )


def _kernel(codes_ref, tables_ref, scales_ref, out_ref, **kw):
    """One (batch, out, chunk) grid step.

    tables_ref: (kb, E, pb) f32/bf16/int8 VMEM
    out_ref   : (bb, pb) f32              VMEM (revisited across chunk tiles)
    """

    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += _accumulate(
        codes_ref, lambda c: tables_ref[c], scales_ref, out_ref.shape, **kw
    )


def _grouped_kernel(codes_ref, tables_ref, scales_ref, out_ref, **kw):
    """One (group, batch, out, chunk) grid step.

    The codes block is *shared* across the group dimension — the fused
    projections all read the same packed input — so revisiting it per group
    costs no extra packing, only the per-group table tile changes.

    tables_ref: (1, kb, E, pb) VMEM (leading 1 = this group)
    out_ref   : (1, bb, pb) f32 VMEM (revisited across chunk tiles)
    """

    @pl.when(pl.program_id(3) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[0] += _accumulate(
        codes_ref, lambda c: tables_ref[0, c], scales_ref, out_ref.shape[1:], **kw
    )


def _experts_kernel(
    offsets_ref,  # (E + 1,) int32 scalar-prefetch: group start offsets
    codes_ref,
    tables_ref,
    scales_ref,
    out_ref,
    *,
    block_b: int,
    **kw,
):
    """One (group, token, out, expert, chunk) grid step.

    Tokens arrive SORTED by expert (the ``ragged_dot`` layout), so expert
    ``e`` owns the contiguous row range ``[offsets[e], offsets[e+1])``.  The
    grid walks every (token block, expert) pair; blocks outside the expert's
    row range skip the gather entirely (``pl.when``), so compute scales with
    the actual group occupancy — only the table-tile DMA is dense.  Rows a
    block shares with a neighbouring expert are masked before accumulation.

    tables_ref : (1, 1, kb, En, pb)       VMEM (this expert+group's tiles)
    out_ref    : (1, bb, pb) f32          VMEM (revisited across (e, chunk))
    """
    bt, e, kt = pl.program_id(1), pl.program_id(3), pl.program_id(4)

    @pl.when((e == 0) & (kt == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    start, end = offsets_ref[e], offsets_ref[e + 1]
    row0 = bt * block_b

    @pl.when((start < row0 + block_b) & (end > row0))
    def _compute():
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (block_b, 1), 0)
        live = (rows >= start) & (rows < end)  # (bb, 1)
        acc = _accumulate(
            codes_ref,
            lambda c: tables_ref[0, 0, c],
            scales_ref,
            out_ref.shape[1:],
            **kw,
        )
        out_ref[0] += jnp.where(live, acc, 0.0)


def lut_affine_experts_pallas(
    offsets: jax.Array,  # (E + 1,) int32 cumulative group offsets
    codes: jax.Array,  # (n, T, lanes) int32 plane-major, tokens sorted by expert
    tables: jax.Array,  # (E, G, k, En, p) pre-stacked expert tables
    scales: jax.Array,  # (n,) f32
    *,
    block_b: int,
    block_p: int,
    block_k: int,
    interpret: bool,
    shift_bits: int = 0,
) -> jax.Array:
    """Ragged (MoE expert) LUT affine: every token row against its own
    expert's pre-stacked tables, all ``G`` fused projections of the stack in
    the same grid.  ``offsets`` is scalar-prefetched (SMEM) so the row-range
    test runs before any table tile is touched."""
    n, T, lanes = codes.shape
    E, G, k, En, p = tables.shape
    assert lanes == code_lanes(k, block_k), (lanes, k, block_k)
    assert offsets.shape == (E + 1,), offsets.shape
    assert T % block_b == 0 and p % block_p == 0 and k % block_k == 0
    grid = (G, T // block_b, p // block_p, E, k // block_k)
    lanes_k = code_lanes(block_k, block_k)

    kernel = functools.partial(
        _experts_kernel,
        block_b=block_b,
        block_k=block_k,
        planes=n,
        shift_bits=shift_bits,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, block_b, lanes_k), lambda g, b, q, e, c, offs: (0, b, c)),
            pl.BlockSpec(
                (1, 1, block_k, En, block_p),
                lambda g, b, q, e, c, offs: (e, g, c, 0, q),
            ),
            _SCALES,
        ],
        out_specs=pl.BlockSpec(
            (1, block_b, block_p), lambda g, b, q, e, c, offs: (g, b, q)
        ),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((G, T, p), jnp.float32),
        interpret=interpret,
    )(offsets.astype(jnp.int32), codes, tables, scales.astype(jnp.float32))


def lut_affine_grouped_pallas(
    codes: jax.Array,  # (n, B, lanes) int32 plane-major, shared by the group
    tables: jax.Array,  # (G, k, E, p)
    scales: jax.Array,  # (n,) f32
    *,
    block_b: int,
    block_p: int,
    block_k: int,
    interpret: bool,
    shift_bits: int = 0,
) -> jax.Array:
    """All ``G`` same-shape projections of one decode step in a single grid:
    one Pallas dispatch instead of ``G`` (QKV / gate-up fusion)."""
    n, B, lanes = codes.shape
    G, k, E, p = tables.shape
    assert lanes == code_lanes(k, block_k), (lanes, k, block_k)
    assert B % block_b == 0 and p % block_p == 0 and k % block_k == 0
    grid = (G, B // block_b, p // block_p, k // block_k)
    lanes_k = code_lanes(block_k, block_k)

    kernel = functools.partial(
        _grouped_kernel, block_k=block_k, planes=n, shift_bits=shift_bits
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, block_b, lanes_k), lambda g, b, q, c: (0, b, c)),
            pl.BlockSpec((1, block_k, E, block_p), lambda g, b, q, c: (g, c, 0, q)),
            _SCALES,
        ],
        out_specs=pl.BlockSpec((1, block_b, block_p), lambda g, b, q, c: (g, b, q)),
        out_shape=jax.ShapeDtypeStruct((G, B, p), jnp.float32),
        interpret=interpret,
    )(codes, tables, scales.astype(jnp.float32))


def lut_affine_pallas(
    codes: jax.Array,  # (n, B, lanes) int32 plane-major (common.code_lanes)
    tables: jax.Array,  # (k, E, p)
    scales: jax.Array,  # (n,) f32
    *,
    block_b: int,
    block_p: int,
    block_k: int,
    interpret: bool,
    shift_bits: int = 0,
) -> jax.Array:
    n, B, lanes = codes.shape
    k, E, p = tables.shape
    assert lanes == code_lanes(k, block_k), (lanes, k, block_k)
    assert B % block_b == 0 and p % block_p == 0 and k % block_k == 0
    grid = (B // block_b, p // block_p, k // block_k)
    lanes_k = code_lanes(block_k, block_k)

    kernel = functools.partial(
        _kernel, block_k=block_k, planes=n, shift_bits=shift_bits
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, block_b, lanes_k), lambda b, q, c: (0, b, c)),
            pl.BlockSpec((block_k, E, block_p), lambda b, q, c: (c, 0, q)),
            _SCALES,
        ],
        out_specs=pl.BlockSpec((block_b, block_p), lambda b, q, c: (b, q)),
        out_shape=jax.ShapeDtypeStruct((B, p), jnp.float32),
        interpret=interpret,
    )(codes, tables, scales.astype(jnp.float32))
