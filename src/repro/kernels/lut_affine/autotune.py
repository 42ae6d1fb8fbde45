"""Autotuner for LUT-affine Pallas block shapes.

:func:`pick_blocks` is a one-shot heuristic: it maximises the chunk tile
under a VMEM budget and fixes the batch tile at ``min(B, 128)``.  That is a
fine default, but the best ``(block_b, block_p, block_k)`` tiling depends on
the *shape point* a dispatch actually presents — decode batch, chunk count,
entry count, output width, plane count, group fan-out — and the trade-offs
(grid-step overhead vs table-tile DMA vs padding waste) move against each
other as those vary.

This module searches the candidate tilings for a shape point and returns a
winner that callers persist on the layer's :class:`~repro.core.lut.LUTPlan`
(``plan.blocks``).  Plans JSON-round-trip through ``ModelPlan`` and ride
checkpoints, so a tuned serving process restores with its tilings intact and
``models.layers`` / ``models.moe`` dispatch the kernels with them directly.

Two search modes:

* ``analytic`` (default) — a deterministic cost model: grid steps times a
  per-step cost of fixed overhead + table/code tile DMA + gather-accumulate
  work.  Padding waste is captured because step counts use padded sizes.
  Fully reproducible across hosts, so CI can re-search the committed
  baseline points and fail on drift (``python -m
  repro.kernels.lut_affine.autotune check``).
* ``measured`` — wall-clock the compiled kernel on a TPU over the
  candidate set (it raises on any other backend: interpreter timings say
  nothing about the chip).  Slower and machine-dependent; for hand tuning,
  not CI.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from typing import Iterable, Mapping, Sequence

from repro.kernels.common import ceil_to

# Cost-model constants (arbitrary units; only ratios matter).  A grid step
# pays a fixed dispatch/pipeline overhead, one byte of tile DMA costs DMA,
# and one gathered-and-accumulated output element costs FMA.
_STEP_OVERHEAD = 4096.0
_DMA = 1.0
_FMA = 0.25

# Bytes of double-buffered blocks one grid step may hold (``vmem_bytes``);
# half of the 16 MiB scoped VMEM a v5e kernel gets by default, leaving room
# for the in-kernel fp32 accumulators.
VMEM_BUDGET = 8 * 2**20


@dataclasses.dataclass(frozen=True)
class TunePoint:
    """The shape a LUT dispatch presents to the kernel (either family).

    For ``family="tl1"`` the axes reinterpret: ``k`` counts *packed bytes*
    along the input (the ``lut_tl1`` chunk axis), ``entries`` is the 9-entry
    per-pair activation LUT, ``n`` is 1 and ``table_bytes`` 1 (uint8 packed
    indices).
    """

    B: int  # batch rows per dispatch (decode: batch size)
    k: int  # chunks (tl1: packed bytes)
    entries: int  # table entries per chunk (tl1: 9)
    p: int  # output features
    n: int  # planes (tl1: 1)
    G: int = 1  # grouped fan-out (1 = ungrouped)
    table_bytes: int = 4  # bytes per stored table element (4/2/1)
    family: str = "weight"  # table family: "weight" | "tl1"

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: Mapping) -> "TunePoint":
        # "family" is a string and absent from pre-TL1 baseline rows
        vals = {
            f.name: int(d[f.name])
            for f in dataclasses.fields(cls)
            if f.name != "family"
        }
        return cls(**vals, family=str(d.get("family", "weight")))

    @classmethod
    def from_plan(cls, plan, batch: int, G: int = 1) -> "TunePoint":
        if plan.table_family == "tl1":
            return cls(
                B=int(batch),
                k=plan.packed_chunks,
                entries=plan.num_entries,
                p=plan.out_features,
                n=1,
                G=int(G),
                table_bytes=1,
                family="tl1",
            )
        from repro.core.lut import plane_scales

        return cls(
            B=int(batch),
            k=plan.num_chunks,
            entries=plan.num_entries,
            p=plan.out_features,
            n=len(plane_scales(plan)),
            G=int(G),
            table_bytes=max(1, plan.storage_bits // 8),
        )


def block_k_options(k: int, family: str = "weight") -> list[int]:
    """Legal chunk tiles for a ``k``-chunk axis.  The chunk tile is the lane
    axis of the codes block, so Mosaic takes a multiple of 128 (the chunk
    axis is then zero-padded up to it) or the whole axis when ``k <= 128``.
    TL1 reads its uint8 packed rows in whole 32-row tiles, so there a short
    axis is padded to a multiple of 32 first."""
    if k <= 128:
        return [ceil_to(k, 32) if family == "tl1" else k]
    opts, bk = [], 128
    while bk <= ceil_to(k, 128):
        opts.append(bk)
        bk *= 2
    return opts


def vmem_bytes(pt: TunePoint, blocks: tuple[int, int, int]) -> int:
    """VMEM one grid step holds: every block double-buffered, each padded
    to its dtype's (sublane, 128) tile (8 rows for 4-byte, 16 for 2-byte,
    32 for 1-byte elements).  ``G`` does not multiply it: the grouped grid
    walks the group axis, one member's table tile at a time."""
    bb, bp, bk = blocks
    sublanes = 32 // pt.table_bytes
    codes = (4 if pt.family == "tl1" else pt.n) * bb * ceil_to(bk, 128) * 4
    if pt.family == "tl1":
        tables = ceil_to(bk, sublanes) * bp * pt.table_bytes  # packed bytes
    else:
        tables = bk * ceil_to(pt.entries, sublanes) * bp * pt.table_bytes
    out = bb * bp * 4
    return 2 * (codes + tables + out)


def blocks_fit_vmem(pt: TunePoint, blocks: tuple[int, int, int]) -> bool:
    """Whether a tiling obeys the kernels' (8, 128) tiling for ``pt`` and
    fits their VMEM budget.

    The reusable legality predicate: ``candidate_blocks`` enumerates with
    it, :func:`pick_blocks` picks under it, and ``repro.audit``'s
    plan-consistency rule re-checks any ``blocks`` riding a ``ModelPlan``.
    """
    bb, bp, bk = blocks = tuple(blocks)
    tiled = bb % 8 == 0 and bp % 128 == 0 and bk in block_k_options(pt.k, pt.family)
    return tiled and vmem_bytes(pt, blocks) <= VMEM_BUDGET


def candidate_blocks(pt: TunePoint) -> list[tuple[int, int, int]]:
    """All legal ``(block_b, block_p, block_k)`` tilings for ``pt``:
    batch tiles of 8..128 rows, output tiles of 128..512 lanes, the chunk
    tiles of :func:`block_k_options`, within :func:`blocks_fit_vmem`."""
    bbs = [bb for bb in (8, 16, 32, 64, 128) if bb <= ceil_to(pt.B, 8) * 2]
    bps = [bp for bp in (128, 256, 512) if bp <= ceil_to(pt.p, 128)]
    return [
        (bb, bp, bk)
        for bb in bbs
        for bp in bps
        for bk in block_k_options(pt.k, pt.family)
        if blocks_fit_vmem(pt, (bb, bp, bk))
    ]


def check_blocks(pt: TunePoint, blocks) -> tuple[int, int, int]:
    """``blocks`` as a tuple, or ValueError when it breaks the tiling or the
    VMEM budget (Mosaic would refuse it; the interpreter would not)."""
    blocks = tuple(int(v) for v in blocks)
    if not blocks_fit_vmem(pt, blocks):
        raise ValueError(
            f"blocks {blocks} are not a legal tiling of {pt}: need batch % 8, "
            f"out % 128, chunk tile in {block_k_options(pt.k, pt.family)}, "
            f"and at most {VMEM_BUDGET} bytes of VMEM "
            f"(this takes {vmem_bytes(pt, blocks)})"
        )
    return blocks


def pick_blocks(pt: TunePoint) -> tuple[int, int, int]:
    """The untuned default tiling: the batch tile covers the batch (up to
    128 rows), then the widest output tile and the largest chunk tile that
    fit the budget.  Raises when even the smallest tiles do not fit."""
    bb = min(ceil_to(pt.B, 8), 128)
    for bp in [bp for bp in (512, 256, 128) if bp <= ceil_to(pt.p, 128)]:
        for bk in reversed(block_k_options(pt.k, pt.family)):
            if blocks_fit_vmem(pt, (bb, bp, bk)):
                return bb, bp, bk
    raise ValueError(f"no tiling of {pt} fits the {VMEM_BUDGET}-byte VMEM budget")


def analytic_cost(pt: TunePoint, blocks: tuple[int, int, int]) -> float:
    """Deterministic cost of running ``pt`` with ``blocks`` (lower = better)."""
    bb, bp, bk = blocks
    steps = (
        (ceil_to(pt.B, bb) // bb)
        * (ceil_to(pt.p, bp) // bp)
        * (ceil_to(pt.k, bk) // bk)
        * pt.G
    )
    if pt.family == "tl1":
        # per step: packed-byte tile + activation-code tile DMA; work is the
        # in-kernel 9-entry LUT build (2 per byte) plus two p-wide gathers
        # per packed byte
        table_tile = bk * bp * pt.table_bytes
        codes_tile = bb * 4 * bk * 4
        work = bb * bk * (2 * pt.entries + 2 * bp)
        return steps * (
            _STEP_OVERHEAD + _DMA * (table_tile + codes_tile) + _FMA * work
        )
    table_tile = bk * pt.entries * bp * pt.table_bytes
    codes_tile = bb * pt.n * bk * 4
    gather = bb * pt.n * bk * bp  # rows gathered x width, accumulated
    return steps * (_STEP_OVERHEAD + _DMA * (table_tile + codes_tile) + _FMA * gather)


def _measure(pt: TunePoint, blocks: tuple[int, int, int], reps: int = 5) -> float:
    """Median wall-clock seconds of the compiled kernel on the TPU."""
    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        raise RuntimeError(
            f"measured autotuning times the TPU kernel; backend is "
            f"{jax.default_backend()!r}"
        )

    from repro.kernels.lut_affine.ops import lut_affine, lut_affine_grouped

    key = jax.random.PRNGKey(0)
    if pt.family == "tl1":
        from repro.kernels.lut_tl1.ops import lut_tl1, lut_tl1_grouped

        acts = jax.random.randint(key, (pt.B, 4 * pt.k), -127, 128, jnp.int32)
        tshape = (pt.k, pt.p) if pt.G == 1 else (pt.G, pt.k, pt.p)
        packed = jnp.zeros(tshape, jnp.uint8)

        def run_tl1():
            if pt.G > 1:
                return lut_tl1_grouped(acts, packed, blocks=blocks)
            return lut_tl1(acts, packed, blocks=blocks)

        run_tl1().block_until_ready()
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            run_tl1().block_until_ready()
            ts.append(time.perf_counter() - t0)
        return sorted(ts)[len(ts) // 2]

    codes = jax.random.randint(key, (pt.B, pt.n, pt.k), 0, pt.entries, jnp.int32)
    dt = {1: jnp.int8, 2: jnp.int16, 4: jnp.float32}[pt.table_bytes]
    tshape = (pt.k, pt.entries, pt.p)
    if pt.G > 1:
        tshape = (pt.G,) + tshape
    tables = jnp.zeros(tshape, dt)
    scales = jnp.ones((pt.n,), jnp.float32)

    def run():
        if pt.G > 1:
            return lut_affine_grouped(codes, tables, scales, blocks=blocks)
        return lut_affine(codes, tables, scales, blocks=blocks)

    run().block_until_ready()  # compile outside the timed region
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        run().block_until_ready()
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2]


def search_blocks(
    pt: TunePoint, mode: str = "analytic", reps: int = 5
) -> tuple[int, int, int]:
    """Best ``(block_b, block_p, block_k)`` for ``pt`` under ``mode``.

    Ties break lexicographically on the tiling itself, so the analytic
    winner is a pure function of the point — the property the CI drift
    check relies on.
    """
    cands = candidate_blocks(pt)
    if not cands:  # entries * 128 alone busts the budget: defer to heuristic
        return None
    if mode == "analytic":
        return min(cands, key=lambda blk: (analytic_cost(pt, blk), blk))
    if mode == "measured":
        return min(cands, key=lambda blk: (_measure(pt, blk, reps), blk))
    raise ValueError(f"unknown autotune mode {mode!r}")


# ---------------------------------------------------------------------------
# ModelPlan integration
# ---------------------------------------------------------------------------


def _group_sizes(mplan) -> dict[str, int]:
    """Layer key -> fused fan-out G (members of the same pre-stacked group)."""
    sizes: dict[str, int] = {}
    for group in mplan.groups:
        for key in group:
            sizes[key] = len(group)
    return sizes


def attach_tuned_blocks(mplan, batch: int, mode: str = "analytic"):
    """Return ``mplan`` with every layer plan's ``blocks`` set to the tuned
    tiling for a ``batch``-row dispatch (decode: the serving batch size).

    Group members share one plan object in spirit; the knapsack already
    assigns them identical plans, and the same ``(point -> blocks)`` search
    keeps them identical after tuning, so pre-stacked groups still fuse.
    """
    sizes = _group_sizes(mplan)
    layers = {}
    for key, plan in mplan.layers.items():
        pt = TunePoint.from_plan(plan, batch, G=sizes.get(key, 1))
        layers[key] = dataclasses.replace(plan, blocks=search_blocks(pt, mode))
    return dataclasses.replace(mplan, layers=layers)


# ---------------------------------------------------------------------------
# Baseline file + drift check (CI)
# ---------------------------------------------------------------------------


def points_from_model_plan(mplan, batch: int) -> list[TunePoint]:
    """Deduplicated shape points a ModelPlan dispatches at ``batch`` rows."""
    sizes = _group_sizes(mplan)
    seen: dict[TunePoint, None] = {}
    for key, plan in sorted(mplan.layers.items()):
        seen.setdefault(TunePoint.from_plan(plan, batch, G=sizes.get(key, 1)))
    return list(seen)


def write_baseline(path: str, points: Iterable[TunePoint], mode: str = "analytic"):
    rows = []
    for pt in points:
        blocks = search_blocks(pt, mode)
        rows.append({**pt.to_json(), "blocks": list(blocks) if blocks else None})
    payload = {"mode": mode, "points": rows}
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    return payload


def check_baseline(path: str) -> list[str]:
    """Re-search every recorded point; return human-readable mismatches."""
    with open(path) as f:
        payload = json.load(f)
    errs = []
    for row in payload["points"]:
        pt = TunePoint.from_json(row)
        got = search_blocks(pt, payload.get("mode", "analytic"))
        want = tuple(row["blocks"]) if row["blocks"] is not None else None
        if (tuple(got) if got else None) != want:
            errs.append(f"{pt}: committed {want}, re-search found {got}")
    return errs


def main(argv: Sequence[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    for name in ("write", "check"):
        sp = sub.add_parser(name)
        sp.add_argument("--baseline", required=True)
        if name == "write":
            sp.add_argument("--mode", default="analytic")
            sp.add_argument(
                "--plan", help="ModelPlan JSON to derive shape points from"
            )
            sp.add_argument("--batch", type=int, default=2)
    args = ap.parse_args(argv)
    if args.cmd == "check":
        errs = check_baseline(args.baseline)
        for e in errs:
            print(f"autotune drift: {e}", file=sys.stderr)
        if errs:
            return 1
        with open(args.baseline) as f:
            n = len(json.load(f)["points"])
        print(f"autotune baseline OK: {n} points re-searched, no drift")
        return 0
    if args.plan:
        from repro.core.planner import ModelPlan

        with open(args.plan) as f:
            mplan = ModelPlan.from_json(json.load(f))
        points = points_from_model_plan(mplan, args.batch)
    else:  # refresh winners for the points already recorded
        with open(args.baseline) as f:
            points = [TunePoint.from_json(r) for r in json.load(f)["points"]]
    write_baseline(args.baseline, points, args.mode)
    print(f"wrote {args.baseline}: {len(points)} points ({args.mode})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
