"""Partition planner: enumerate LUT configurations and their cost trade-off.

Reproduces the paper's size-vs-operations curves (Figs. 5, 7, 8) and picks a
plan under a memory budget.  All accounting is closed-form from
:class:`repro.core.lut.LUTPlan`; the formulas were validated against every
number the paper states for the linear classifier and the MLP (see
``tests/test_analysis.py``).

Beyond the per-layer helpers, :func:`plan_model` runs the whole-model pass:
it walks a parameter tree, enumerates the Pareto frontier of plans for every
eligible linear layer, and greedily spends a *global* LUT byte budget where
it buys the largest reduction in shift/add work — emitting a serializable
:class:`ModelPlan` that :func:`repro.core.convert.convert_params` applies
per layer.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Iterable, Iterator, Mapping, Sequence, Union

from repro.core.lut import LUTPlan
from repro.core.lut_tl1 import TL1Plan
from repro.core.quantize import FixedPointFormat, Float16Format

# The two table families the pipeline is polymorphic over.  Both plan types
# expose the same accounting surface (num_chunks / total_lut_bytes /
# lut_evaluations / shift_add_ops / blocks), so a PlanPoint — and therefore
# the knapsack — treats them uniformly.
AnyPlan = Union[LUTPlan, TL1Plan]
TABLE_FAMILIES = ("weight", "tl1")


@dataclasses.dataclass(frozen=True)
class PlanPoint:
    plan: AnyPlan
    num_tables: int
    lut_bytes: int
    lut_evaluations: int
    shift_add_ops: int

    @staticmethod
    def of(plan: AnyPlan) -> "PlanPoint":
        return PlanPoint(
            plan=plan,
            num_tables=plan.num_chunks,
            lut_bytes=plan.total_lut_bytes,
            lut_evaluations=plan.lut_evaluations,
            shift_add_ops=plan.shift_add_ops,
        )


def _narrow_format_safe(fmt, mode: str) -> bool:
    """Whether i8/i16 storage with ONE power-of-2 scale per table set keeps
    quantization error at the int8-weight level.  True when table entries
    don't bake in the fp16 exponent range: the sigma-factored
    ``bitplane_shift`` tables span only ``[-(2**r - 1), 2**r - 1]`` times the
    weight range, and fixed-point bitplane tables are plain subset sums of
    weight rows.  Sigma-laden float tables (``bitplane`` / ``full``) span
    ~2**30 in magnitude across entries, which one 8/16-bit scale cannot
    represent — a narrow format there silently zeroes most entries."""
    if isinstance(fmt, Float16Format):
        return mode == "bitplane_shift"
    return mode == "bitplane"


def enumerate_plans(
    in_features: int,
    out_features: int,
    fmt,
    modes: Sequence[str] = ("bitplane", "full"),
    max_index_bits: int = 24,
    max_chunk: int | None = None,
    table_formats: Sequence[str | None] = (None,),
) -> list[PlanPoint]:
    """All uniform-chunk plans whose index width stays implementable.

    ``table_formats`` extends the frontier with narrow-storage variants
    (``"i8"`` / ``"i16"``); they are emitted only where single-scale
    quantization is accuracy-safe (see :func:`_narrow_format_safe`).
    """
    points: list[PlanPoint] = []
    is_float = isinstance(fmt, Float16Format)
    for mode in modes:
        if is_float:
            if mode == "bitplane":
                fpe = fmt.fields_per_element
            elif mode == "bitplane_shift":
                fpe = fmt.mantissa_radix + (1 if fmt.signed else 0)
            else:
                fpe = 15
        else:
            if mode == "bitplane_shift":
                continue  # float16-only mode
            fpe = 1 if mode == "bitplane" else fmt.total_bits
        hi = max_index_bits // fpe
        if max_chunk is not None:
            hi = min(hi, max_chunk)
        for m in range(1, max(hi, 0) + 1):
            if mode in ("full", "bitplane_shift") and is_float and m != 1:
                continue
            for table_format in table_formats:
                if table_format is not None and not _narrow_format_safe(fmt, mode):
                    continue
                try:
                    plan = LUTPlan(
                        in_features,
                        out_features,
                        m,
                        fmt,
                        mode=mode,
                        table_format=table_format,
                    )
                except ValueError:
                    continue
                points.append(PlanPoint.of(plan))
    return points


def tradeoff_curve(points: Iterable[PlanPoint]) -> list[PlanPoint]:
    """Pareto frontier of (lut_bytes, shift_add_ops), sorted by size."""
    pts = sorted(points, key=lambda p: (p.lut_bytes, p.shift_add_ops))
    frontier: list[PlanPoint] = []
    best_ops = math.inf
    for p in pts:
        if p.shift_add_ops < best_ops:
            frontier.append(p)
            best_ops = p.shift_add_ops
    return frontier


def plan_under_budget(
    in_features: int,
    out_features: int,
    fmt,
    max_lut_bytes: int,
    modes: Sequence[str] = ("bitplane",),
) -> LUTPlan:
    """Fewest-ops plan whose tables fit the budget (raises if none fits)."""
    candidates = [
        p
        for p in enumerate_plans(in_features, out_features, fmt, modes=modes)
        if p.lut_bytes <= max_lut_bytes
    ]
    if not candidates:
        raise ValueError(
            f"no LUT plan for {in_features}x{out_features} fits "
            f"{max_lut_bytes} bytes"
        )
    return min(candidates, key=lambda p: (p.shift_add_ops, p.lut_bytes)).plan


def default_serving_plan(
    in_features: int, out_features: int, chunk_size: int = 4
) -> LUTPlan:
    """The plan LM serving uses unless a config overrides it: binary16 input
    (the paper's finding: fp16 inner activations preserve accuracy where
    fixed point does not), bitplane mode, moderate chunks."""
    return LUTPlan(in_features, out_features, chunk_size, Float16Format())


# ---------------------------------------------------------------------------
# Whole-model planning: per-layer plans under a global byte budget
# ---------------------------------------------------------------------------


def _fmt_to_json(fmt) -> dict:
    if isinstance(fmt, Float16Format):
        out = {"kind": "float16", "signed": fmt.signed}
        if fmt.mantissa_radix != 1:
            out["mantissa_radix"] = fmt.mantissa_radix
        return out
    return {
        "kind": "fixed",
        "total_bits": fmt.total_bits,
        "frac_bits": fmt.frac_bits,
        "signed": fmt.signed,
    }


def _fmt_from_json(d: Mapping) -> Any:
    if d["kind"] == "float16":
        return Float16Format(
            signed=d["signed"], mantissa_radix=d.get("mantissa_radix", 1)
        )
    return FixedPointFormat(d["total_bits"], d["frac_bits"], signed=d["signed"])


def plan_to_json(plan: AnyPlan) -> dict:
    if isinstance(plan, TL1Plan):
        out = {
            "family": "tl1",
            "in_features": plan.in_features,
            "out_features": plan.out_features,
            "act_bits": plan.act_bits,
        }
        if plan.blocks is not None:
            out["blocks"] = list(plan.blocks)
        # acc contract rides checkpoints like blocks; defaults stay implicit
        # so pre-contract plan JSON and its golden files keep round-tripping.
        if plan.act_bits is not None and plan.acc_dtype != "int32":
            out["acc_dtype"] = plan.acc_dtype
        if plan.max_abs_acc is not None:
            out["max_abs_acc"] = plan.max_abs_acc
        return out
    out = {
        "in_features": plan.in_features,
        "out_features": plan.out_features,
        "chunk_size": plan.chunk_size,
        "fmt": _fmt_to_json(plan.fmt),
        "mode": plan.mode,
        "out_bits": plan.out_bits,
    }
    if plan.table_format is not None:
        out["table_format"] = plan.table_format
    if plan.blocks is not None:
        out["blocks"] = list(plan.blocks)
    if plan.acc_dtype != "float32":
        out["acc_dtype"] = plan.acc_dtype
    if plan.max_abs_acc is not None:
        out["max_abs_acc"] = plan.max_abs_acc
    return out


def plan_from_json(d: Mapping) -> AnyPlan:
    # "family" is absent from plans serialized before the TL1 family existed;
    # those are all weight-family, so the default keeps old ModelPlan JSON
    # (and the checkpoints it rides on) loading unchanged.
    family = d.get("family", "weight")
    blocks = d.get("blocks")
    blocks = tuple(blocks) if blocks is not None else None
    if family == "tl1":
        return TL1Plan(
            d["in_features"],
            d["out_features"],
            act_bits=d.get("act_bits", 8),
            blocks=blocks,
            acc_dtype=d.get("acc_dtype", "int32"),
            max_abs_acc=d.get("max_abs_acc"),
        )
    if family != "weight":
        raise ValueError(f"unknown table family {family!r}")
    return LUTPlan(
        d["in_features"],
        d["out_features"],
        d["chunk_size"],
        _fmt_from_json(d["fmt"]),
        mode=d["mode"],
        out_bits=d["out_bits"],
        table_format=d.get("table_format"),
        blocks=blocks,
        acc_dtype=d.get("acc_dtype", "float32"),
        max_abs_acc=d.get("max_abs_acc"),
    )


@dataclasses.dataclass(frozen=True)
class ModelPlan:
    """Per-layer LUT plans keyed by the layer's ``"/"``-joined tree path.

    ``groups`` lists the fusable sibling sets (tuples of layer path keys)
    the plan was built around: every member of a group carries the *same*
    ``LUTPlan`` (the knapsack upgrades groups atomically), and
    ``convert_params`` emits each one as a single pre-stacked
    ``core.convert.LUTGroup`` node.

    ``copies`` records, per entry, the product of the weight's leading
    scan/expert dims — how many table SETS the converter builds for it
    (missing keys mean 1).  ``total_lut_bytes`` / ``total_shift_add_ops``
    scale by it, so a plan's totals match the bytes a conversion actually
    materialises (the pre-fix planner charged one ``(q, p)`` table per
    entry and could blow a budget by the expert count).

    JSON-serializable (``to_json``/``from_json``) so it rides along with
    checkpoints (``dist.checkpoint.save_checkpoint(..., aux=...)``) and
    reconverts identically after an elastic restore.
    """

    layers: Mapping[str, AnyPlan]
    budget_bytes: int | None = None
    groups: tuple = ()  # tuple[tuple[str, ...], ...] of layer path keys
    copies: Mapping[str, int] = dataclasses.field(default_factory=dict)

    @property
    def families(self) -> tuple[str, ...]:
        """Distinct table families present, in TABLE_FAMILIES order."""
        present = {p.table_family for p in self.layers.values()}
        return tuple(f for f in TABLE_FAMILIES if f in present)

    @property
    def total_lut_bytes(self) -> int:
        return sum(
            self.copies.get(k, 1) * p.total_lut_bytes for k, p in self.layers.items()
        )

    @property
    def total_shift_add_ops(self) -> int:
        return sum(
            self.copies.get(k, 1) * p.shift_add_ops for k, p in self.layers.items()
        )

    def to_json(self) -> dict:
        return {
            "budget_bytes": self.budget_bytes,
            "layers": {k: plan_to_json(p) for k, p in sorted(self.layers.items())},
            "groups": [list(g) for g in self.groups],
            "copies": {k: v for k, v in sorted(self.copies.items()) if v != 1},
        }

    @classmethod
    def from_json(cls, d: Mapping) -> "ModelPlan":
        return cls(
            layers={k: plan_from_json(v) for k, v in d["layers"].items()},
            budget_bytes=d.get("budget_bytes"),
            groups=tuple(tuple(g) for g in d.get("groups", [])),
            copies=dict(d.get("copies", {})),
        )

    def summary(self) -> str:
        return (
            f"ModelPlan: {len(self.layers)} layers "
            f"({len(self.groups)} fused groups, "
            f"families {'+'.join(self.families) or 'none'}), "
            f"{self.total_lut_bytes / 2**20:.1f} MiB tables, "
            f"{self.total_shift_add_ops:,} shift/add ops"
        )


def path_key(path: Sequence) -> str:
    return "/".join(str(p) for p in path)


def _copies(w) -> int:
    """Table instances one weight leaf expands to: the product of its
    leading (scan-layer / expert) dims.  A ``(q, p)`` linear is 1 table set;
    a scan-stacked ``(L, q, p)`` builds L; an expert stack ``(L, E, q, p)``
    builds L*E — the converter vmaps ``build_luts`` over every leading dim,
    so bytes scale by exactly this factor (the pre-fix planner charged 1)."""
    return int(math.prod(int(d) for d in w.shape[:-2]))


def iter_linear_layers(
    params: dict,
    min_features: int = 1,
    predicate: Callable[[tuple, dict], bool] | None = None,
    convert_experts: bool = False,
) -> Iterator[tuple[str, tuple[int, int], int]]:
    """Yield ``(path_key, (in_features, out_features), copies)`` for every
    linear node ``convert_params`` would convert (same eligibility rules);
    ``copies`` is the product of the leading scan/expert dims — the number
    of table sets the converter actually builds for the entry.

    With ``convert_experts=True`` the raw MoE expert-stack weights are
    enumerated too (as ``.../w_gate`` etc.), mirroring
    ``convert_params(convert_experts=True)`` — the converter raises if a
    plan carries entries it never consumes, so keep the two flags in sync.
    """
    # local imports: avoid an import cycle with repro.core.convert
    from repro.core.convert import (
        EXPERT_WEIGHT_KEYS,
        _is_expert_stack,
        _is_linear_node,
    )

    def eligible(path: tuple, node: dict) -> bool:
        q = node["w"].shape[-2]
        return q >= min_features and (predicate is None or predicate(path, node))

    def walk(path: tuple, node: Any):
        if _is_linear_node(node):
            if eligible(path, node):
                q, p = node["w"].shape[-2:]
                yield path_key(path), (int(q), int(p)), _copies(node["w"])
            return
        if not isinstance(node, dict):
            return
        if convert_experts and _is_expert_stack(node):
            for k, v in node.items():
                if k in EXPERT_WEIGHT_KEYS:
                    mpath = path + (k,)
                    if eligible(mpath, {"w": v}):
                        q, p = v.shape[-2:]
                        yield path_key(mpath), (int(q), int(p)), _copies(v)
                else:
                    yield from walk(path + (k,), v)
            return
        for k in node:
            yield from walk(path + (k,), node[k])

    yield from walk((), params)


def iter_sibling_groups(
    params: dict,
    min_features: int = 1,
    predicate: Callable[[tuple, dict], bool] | None = None,
    convert_experts: bool = False,
) -> Iterator[tuple[str, ...]]:
    """Yield fusable sibling groups as tuples of layer path keys — the same
    detection ``convert_params(group_siblings=True)`` runs (shared helpers),
    restricted to members that pass the eligibility rules.  With
    ``convert_experts=True``, same-shape expert-stack pairs (gate/up) are
    yielded too, mirroring the converter's expert pre-stacking."""
    from repro.core.convert import (
        EXPERT_WEIGHT_KEYS,
        _is_expert_stack,
        _is_linear_node,
        expert_sibling_groups,
        sibling_groups,
    )

    def eligible(path: tuple, node: dict) -> bool:
        q = node["w"].shape[-2]
        return q >= min_features and (predicate is None or predicate(path, node))

    def walk(path: tuple, node: Any):
        if not isinstance(node, dict) or _is_linear_node(node):
            return
        if _is_expert_stack(node):
            if convert_experts:
                for members in expert_sibling_groups(node):
                    if all(eligible(path + (m,), {"w": node[m]}) for m in members):
                        yield tuple(path_key(path + (m,)) for m in members)
            for k, v in node.items():
                if k not in EXPERT_WEIGHT_KEYS:
                    yield from walk(path + (k,), v)
            return
        for members in sibling_groups(node):
            if all(eligible(path + (m,), node[m]) for m in members):
                yield tuple(path_key(path + (m,)) for m in members)
        for k, v in node.items():
            yield from walk(path + (k,), v)

    yield from walk((), params)


def plan_model(
    params: dict,
    max_lut_bytes: int | float,
    fmt=None,
    modes: Sequence[str] = ("bitplane",),
    max_chunk: int | None = None,
    min_features: int = 1,
    predicate: Callable[[tuple, dict], bool] | None = None,
    signed: bool = True,
    group_siblings: bool = True,
    convert_experts: bool = False,
    radices: Sequence[int] = (1,),
    table_formats: Sequence[str | None] = (None,),
    families: Sequence[str] = ("weight",),
    tl1_act_bits: int | None = 8,
    tl1_acc_dtype: str = "int32",
    max_entries: int | None = None,
) -> ModelPlan:
    """Choose a per-layer plan for every eligible linear under a global budget.

    Greedy knapsack over each item's Pareto frontier: every item starts at
    its smallest-bytes plan; the budget is then spent on whichever single
    item upgrade buys the most shift/add reduction per byte (ties broken by
    smallest byte cost, then path order — fully deterministic).  The
    accuracy proxy is the format itself: binary16 bitplane plans are exact
    for fp16 inputs at *every* chunk size, so within one format the search
    reduces to bytes-vs-ops; narrower fixed-point formats trade accuracy and
    are selected by passing a different ``fmt``.

    Bytes and ops are charged per table SET actually built: an entry whose
    weight carries leading scan/expert dims (``(L, q, p)`` scan stacks,
    ``(L, E, q, p)`` expert stacks) costs its per-set bytes times the
    product of those dims, recorded on ``ModelPlan.copies`` — so a
    converted tree's ``ConvertReport.table_bytes`` (at the accounting
    ``out_bits`` width, i.e. fp16 tables) can never exceed the budget.

    With ``group_siblings=True`` (default) fusable sibling projections
    (QKV / K-V / gate-up — see ``core.convert.FUSABLE_SIBLINGS``; with
    ``convert_experts=True`` also expert gate/up stacks) form ONE knapsack
    item: their bytes and ops are accounted together and an upgrade moves
    every member at once, so the knapsack can never split a group onto
    different plans and silently defeat conversion-time fusion.  The group
    memberships are recorded on ``ModelPlan.groups``.

    ``radices`` widens the frontier with multi-bit mantissa-plane variants
    of a Float16 ``fmt`` (``Float16Format.mantissa_radix``) and
    ``table_formats`` with narrow table storage (``"i8"``/``"i16"``, where
    accuracy-safe) — both default to the paper's setting so the frontier
    only widens when a caller opts in.

    ``families`` widens the frontier across TABLE FAMILIES: with ``"tl1"``
    included, every item's frontier also carries the activation-side TL1
    point (ternary weights packed to base-3 indices, ``q*p/4`` persistent
    bytes, ``tl1_act_bits`` activation quantization) so each layer/group
    independently lands on weight-table vs TL1 under the one global byte
    budget.  TL1 is the smallest-bytes point by an order of magnitude;
    upgrades move individual items to weight-table plans wherever the
    budget buys the most shift/add reduction — so one model mixes families.

    Every candidate must additionally pass its *range certificate*
    (``repro.audit.ranges.layer_range_cert``): candidates whose proved
    worst-case |accumulator| exceeds the declared accumulator dtype's
    capacity (``tl1_acc_dtype`` for TL1 points, fp32 for weight tables)
    are rejected before the knapsack sees them, and the survivors are
    stamped with the proved bound (``max_abs_acc``) so kernels can assert
    the contract at trace time and checkpoints carry the proof.

    ``max_entries`` drops weight-table candidates with more entries per
    table than that — pass ``kernels.common.MAX_SELECT_ENTRIES`` to plan
    for the Pallas kernels, which raise on wider tables.

    Raises ``ValueError`` if even the minimal per-layer plans exceed
    ``max_lut_bytes``, if no candidate of some layer has at most
    ``max_entries`` entries, or if every candidate for some layer fails its
    accumulator certificate.
    """
    # call-time import: repro.audit imports this module (points builds
    # plans), so the certificate pass must not close the cycle at import.
    from repro.audit.ranges import layer_range_cert
    from repro.kernels.common import acc_capacity
    families = tuple(families)
    if not families or any(f not in TABLE_FAMILIES for f in families):
        raise ValueError(
            f"families must be a non-empty subset of {TABLE_FAMILIES}, "
            f"got {families}"
        )
    fmt = fmt if fmt is not None else Float16Format(signed=signed)
    if isinstance(fmt, Float16Format):
        fmt_variants = [
            dataclasses.replace(fmt, mantissa_radix=r) for r in sorted(set(radices))
        ]
    else:
        fmt_variants = [fmt]
    entries = list(
        iter_linear_layers(params, min_features, predicate, convert_experts)
    )
    shapes = {key: shape for key, shape, _ in entries}
    copies = {key: n for key, _, n in entries}
    groups: list[tuple[str, ...]] = (
        sorted(iter_sibling_groups(params, min_features, predicate, convert_experts))
        if group_siblings
        else []
    )
    in_group = {key for g in groups for key in g}
    # a knapsack item is a group (all members move together) or a lone layer;
    # its weight is the SUM of the members' table-set counts — a scan-stacked
    # or expert entry pays bytes/ops once per leading-dim instance, so an
    # expert stack is one atomic item spanning all E (or L*E) experts
    items: list[tuple[str, ...]] = groups + [
        (key,) for key in shapes if key not in in_group
    ]
    items.sort()
    mult = {item: sum(copies[k] for k in item) for item in items}

    frontiers: dict[tuple[str, ...], list[PlanPoint]] = {}
    frontier_cache: dict[tuple[int, int], list[PlanPoint]] = {}
    for item in items:
        q, p = shapes[item[0]]
        assert all(shapes[k] == (q, p) for k in item), item
        if (q, p) not in frontier_cache:
            pts = []
            if "weight" in families:
                pts += [
                    pt
                    for fv in fmt_variants
                    for pt in enumerate_plans(
                        q,
                        p,
                        fv,
                        modes=modes,
                        max_chunk=max_chunk,
                        table_formats=table_formats,
                    )
                    if max_entries is None or pt.plan.num_entries <= max_entries
                ]
            if "tl1" in families:
                pts.append(
                    PlanPoint.of(
                        TL1Plan(q, p, act_bits=tl1_act_bits, acc_dtype=tl1_acc_dtype)
                    )
                )
            # certificate gate: drop candidates whose proved |acc| bound
            # overflows their declared accumulator; stamp the survivors.
            kept, rejected = [], []
            for pt in pts:
                cert = layer_range_cert(pt.plan)
                if cert.max_abs_acc > acc_capacity(pt.plan.acc_dtype):
                    rejected.append((pt.plan, cert))
                else:
                    kept.append(
                        PlanPoint.of(
                            dataclasses.replace(
                                pt.plan, max_abs_acc=cert.max_abs_acc
                            )
                        )
                    )
            if not kept and rejected:
                plan, cert = rejected[0]
                raise ValueError(
                    f"no overflow-safe plan for {q}x{p}: e.g. "
                    f"{type(plan).__name__} proves |acc| <= "
                    f"{cert.max_abs_acc:.6g}, which overflows "
                    f"acc_dtype={plan.acc_dtype!r} (capacity "
                    f"{acc_capacity(plan.acc_dtype):.6g}; minimal safe "
                    f"dtype {cert.min_acc_dtype})"
                )
            frontier_cache[(q, p)] = tradeoff_curve(kept)
        frontier = frontier_cache[(q, p)]
        if not frontier:
            raise ValueError(f"no feasible LUT plan for {item[0]} ({q}x{p})")
        frontiers[item] = frontier

    choice = {item: 0 for item in items}
    spent = sum(mult[item] * frontiers[item][0].lut_bytes for item in items)
    if spent > max_lut_bytes:
        raise ValueError(
            f"budget {max_lut_bytes} bytes < minimal model footprint "
            f"{spent} bytes ({len(shapes)} layers)"
        )

    while True:
        best = None  # (ops_saved_per_byte, -bytes_added, item, frontier index)
        for item in items:
            fr = frontiers[item]
            cur = fr[choice[item]]
            for j in range(choice[item] + 1, len(fr)):
                d_bytes = mult[item] * (fr[j].lut_bytes - cur.lut_bytes)
                if spent + d_bytes > max_lut_bytes:
                    break  # frontier bytes increase monotonically
                d_ops = mult[item] * (cur.shift_add_ops - fr[j].shift_add_ops)
                score = (d_ops / d_bytes, -d_bytes)
                if best is None or score > best[:2]:
                    best = (*score, item, j)
        if best is None:
            break
        _, _, item, j = best
        spent += mult[item] * (
            frontiers[item][j].lut_bytes - frontiers[item][choice[item]].lut_bytes
        )
        choice[item] = j

    layers = {
        key: frontiers[item][choice[item]].plan for item in items for key in item
    }
    budget = None if math.isinf(max_lut_bytes) else int(max_lut_bytes)
    return ModelPlan(
        layers=dict(sorted(layers.items())),
        budget_bytes=budget,
        groups=tuple(groups),
        copies={k: v for k, v in sorted(copies.items()) if v != 1},
    )
