"""Shared helpers for the Pallas TPU kernels."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Largest |value| each accumulator dtype can hold exactly enough for the
# contract check: integer dtypes their max code, float32 its max finite.
# Keys are the strings a plan's ``acc_dtype`` field carries.
ACC_CAPACITY: dict[str, float] = {
    "int16": float(2**15 - 1),
    "int32": float(2**31 - 1),
    "int64": float(2**63 - 1),
    "float32": float(np.finfo(np.float32).max),
}


def acc_capacity(acc_dtype: str) -> float:
    """Capacity of an accumulator dtype name (raises on unknown names)."""
    try:
        return ACC_CAPACITY[acc_dtype]
    except KeyError:
        raise ValueError(
            f"unknown accumulator dtype {acc_dtype!r}; "
            f"expected one of {sorted(ACC_CAPACITY)}"
        ) from None


def check_acc_contract(op: str, plan, kernel_acc_dtype: str) -> None:
    """Trace-time accumulator-contract assert.

    ``plan`` is duck-typed (any object with ``acc_dtype`` and a proved
    ``max_abs_acc`` stamped by the planner via ``repro.audit.ranges``).
    No-op when the plan carries no proved bound; otherwise raises if the
    bound exceeds either the plan's *declared* accumulator capacity or the
    capacity of the dtype this kernel actually accumulates in.  Runs at
    trace time — a violating plan can never reach execution.
    """
    bound = getattr(plan, "max_abs_acc", None)
    if bound is None:
        return
    declared = plan.acc_dtype
    if bound > acc_capacity(declared):
        raise ValueError(
            f"{op}: plan declares acc_dtype={declared!r} but its proved "
            f"|acc| bound {bound:.6g} exceeds that dtype's capacity "
            f"{acc_capacity(declared):.6g}"
        )
    if bound > acc_capacity(kernel_acc_dtype):
        raise ValueError(
            f"{op}: kernel accumulates in {kernel_acc_dtype}, too narrow "
            f"for the plan's proved |acc| bound {bound:.6g}"
        )


def ceil_to(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def pad_axis(x: jax.Array, axis: int, to: int, value=0) -> jax.Array:
    """Zero-pad ``axis`` of ``x`` up to length ``to``."""
    cur = x.shape[axis]
    if cur == to:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, to - cur)
    return jnp.pad(x, pads, constant_values=value)


def default_interpret() -> bool:
    """Whether Pallas kernels run in interpret mode: True on the CPU backend
    (bit-identical semantics, executed by XLA:CPU), False on TPU.  Any other
    backend raises — the kernels target TPU, and a silent interpreter there
    would time and serve a path no device runs."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels target TPU (or the CPU interpreter); "
        f"backend {backend!r} has neither"
    )


# Largest table an in-kernel compare-and-select gather serves.  Each entry
# costs one select per gathered element, so larger tables (fp16 bitplane
# plans: 64+ entries) would also cost more bytes per weight than any chip
# holds at model widths; the planner keeps serving plans under it
# (``plan_model(max_entries=...)``) and the kernels raise above it.
MAX_SELECT_ENTRIES = 16


def select_entries(entries, idx: jax.Array) -> jax.Array:
    """``entries[idx]`` as a binary tree of compare-and-selects.

    ``entries`` is a sequence of arrays that broadcast against ``idx``;
    ``idx`` holds integers in ``[0, len(entries))``.  Level ``i`` of the tree
    picks between neighbouring nodes by bit ``i`` of ``idx``, so ``E``
    entries cost ``E - 1`` selects and no multiply — the row gather Mosaic
    lowers where a vector-indexed ``jnp.take`` does not.  The result equals
    the indexed entry exactly (no arithmetic touches the values).
    """
    level = list(entries)
    shape = jnp.broadcast_shapes(idx.shape, *(e.shape for e in level))
    # test bits on the full-shape index: Mosaic cannot broadcast a boolean
    # mask across sublanes, only the integers it is computed from
    idx = jnp.broadcast_to(idx, shape)
    bit = 0
    while len(level) > 1:
        take_hi = (idx & (1 << bit)) != 0
        nxt = [
            jnp.where(take_hi, level[i + 1], level[i])
            for i in range(0, len(level) - 1, 2)
        ]
        if len(level) % 2:  # odd tail: valid indices reaching it have bit 0
            nxt.append(level[-1])
        level = nxt
        bit += 1
    return jnp.broadcast_to(level[0], shape)


LANES = 128  # vreg lane width: codes tiles are read this many lanes at a time


def lane_window(block_k: int) -> int:
    """Chunks of a tile handled per codes read: one 128-lane window, or
    the whole tile when it is narrower (block_k is then its full axis)."""
    return min(block_k, LANES)


def code_lanes(k: int, block_k: int) -> int:
    """Lanes of the chunk axis the codes array carries for ``k`` chunks in
    ``block_k`` tiles: each tile padded to whole 128-lane windows, because a
    lane rotate of a narrower vector does not lower."""
    return k // block_k * ceil_to(block_k, LANES)


def window_start(w, block_k: int):
    """First lane of window ``w``: static 0 when one window spans the
    tile, else an aligned multiple of 128."""
    if block_k <= LANES:
        return 0
    return pl.multiple_of(w * LANES, LANES)


def lane_column(tile: jax.Array, c) -> jax.Array:
    """Column ``c`` (traced or static) of a 2-D ``(rows, 128)`` tile as
    ``(rows, 1)``: a lane rotate brings it to lane 0, since a dynamic lane
    slice does not lower on TPU."""
    lanes = tile.shape[1]
    return pltpu.roll(tile, (lanes - c) % lanes, 1)[:, :1]


def check_entries(op: str, entries: int) -> None:
    """Raise when a table is too wide for the select gather (no fallback)."""
    if entries > MAX_SELECT_ENTRIES:
        raise ValueError(
            f"{op}: {entries}-entry tables exceed the kernel's "
            f"{MAX_SELECT_ENTRIES}-entry select gather; plan with "
            f"plan_model(max_entries={MAX_SELECT_ENTRIES})"
        )
