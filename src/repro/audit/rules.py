"""Structural invariant rules over traced programs and converted trees.

Each rule returns a list of :class:`Violation` (empty == the invariant
holds) instead of asserting, so the same predicates serve three callers:
the jaxpr acceptance tests, the ``python -m repro.audit`` CLI, and the CI
gate diffing the committed manifest.

Rule classes
------------
* :func:`multiplier_free_violations` — the paper's contract: no
  ``ragged_dot`` anywhere, and no ``dot_general`` / conv / ``mul`` whose
  operand is a planned weight (shape-suffix match against the plan's
  ``(q, p)`` projections) or a stored table leaf.  Scalar and
  activation-sized multiplies pass by construction — they match neither a
  weight nor a table shape.
* :func:`zero_copy_violations` — the PR 3 layout contract: a decode step
  never rebuilds a table at trace level, i.e. no ``concatenate`` (which
  ``stack`` lowers to), ``transpose``, or ``copy`` whose *output* is
  shaped like a stored table leaf.
* :func:`plan_consistency_violations` — the ``ModelPlan`` and the
  converted tree tell the same story: every plan entry is consumed by
  exactly the leaves it planned, families and per-layer plans match,
  materialised table bytes equal ``total_lut_bytes``, and any tuned
  ``blocks`` are legal under the kernels' VMEM budget.

Shape-suffix matching (not exact-shape matching) is what makes the rules
robust to stacking: a scan-stacked ``(L, q, p)`` dense fallback, an
expert-stacked ``(L, E, q, p)`` one, and a bare ``(q, p)`` weight all end
in the planned ``(q, p)`` — while the LUT pipeline's own small
contractions (plane-scale accumulates, rope rotations, attention scores)
match nothing.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Iterable, Sequence

from repro.audit.walker import as_eqns

# Primitives that multiply operands elementwise or as contractions.
_CONTRACTIONS = ("dot_general", "conv_general_dilated")
_ZERO_COPY_PRIMITIVES = ("concatenate", "transpose", "copy")


@dataclasses.dataclass(frozen=True)
class Violation:
    """One rule breach, serialisable into the audit manifest."""

    rule: str
    primitive: str
    detail: str

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d) -> "Violation":
        return cls(str(d["rule"]), str(d["primitive"]), str(d["detail"]))


def _has_suffix(shape: Sequence[int], suffix: Sequence[int]) -> bool:
    return len(shape) >= len(suffix) and tuple(shape[-len(suffix):]) == tuple(suffix)


def _matches_any(shape: Sequence[int], suffixes: Iterable[Sequence[int]]) -> bool:
    return any(_has_suffix(shape, s) for s in suffixes)


def planned_weight_shapes(mplan) -> frozenset[tuple[int, int]]:
    """Forbidden ``(q, p)`` suffixes for a plan: every planned projection's
    weight shape and its transpose (a dense fallback may present either)."""
    out = set()
    for plan in mplan.layers.values():
        out.add((plan.in_features, plan.out_features))
        out.add((plan.out_features, plan.in_features))
    return frozenset(out)


def table_leaf_shapes(tree) -> frozenset[tuple[int, ...]]:
    """Forbidden table suffixes: the trailing table-set dims of every stored
    ``LUTLinear`` / ``LUTGroup`` leaf (one set per scan/expert copy), with
    the group axis included for grouped leaves — exactly the shape a
    per-step re-stack or table transpose would produce."""
    from repro.core.convert import LUTGroup, LUTLinear

    out: set[tuple[int, ...]] = set()

    def walk(node):
        if isinstance(node, (LUTLinear, LUTGroup)):
            ndim = 2 if node.plan.table_family == "tl1" else 3
            if isinstance(node, LUTGroup):
                ndim += 1
            out.add(tuple(node.tables.shape[-ndim:]))
        elif isinstance(node, dict):
            for v in node.values():
                walk(v)

    walk(tree)
    return frozenset(out)


def multiplier_free_violations(
    jaxpr,
    *,
    weight_shapes: Iterable[Sequence[int]] = (),
    table_shapes: Iterable[Sequence[int]] = (),
    exempt_dims: Iterable[int] = (),
    min_operand_elems: int | None = None,
) -> list[Violation]:
    """The paper's contract: the program contains no multiplier over
    weight- or table-shaped operands.

    ``ragged_dot`` is always a violation (it exists only to contract
    expert weight stacks).  ``dot_general`` / conv equations are flagged
    when an operand shape ends in a ``weight_shapes`` suffix or (when
    ``min_operand_elems`` is given) when any operand reaches that element
    count — the threshold form the pre-audit tests used.  ``mul`` is
    flagged on weight- or table-shaped operands only, which is the
    allowlist for scalar/activation muls.  Operands carrying a dim listed
    in ``exempt_dims`` (e.g. the tied-embedding vocab) are skipped.
    """
    weight_shapes = tuple(tuple(s) for s in weight_shapes)
    table_shapes = tuple(tuple(s) for s in table_shapes)
    exempt = frozenset(exempt_dims)
    out = []
    for eqn in as_eqns(jaxpr):
        name = eqn.primitive.name
        if name == "ragged_dot":
            out.append(
                Violation("multiplier_free", name, "ragged expert contraction")
            )
            continue
        if name not in _CONTRACTIONS and name != "mul":
            continue
        shapes = [tuple(v.aval.shape) for v in eqn.invars]
        if exempt and any(d in exempt for s in shapes for d in s):
            continue
        if name in _CONTRACTIONS:
            hit = any(_matches_any(s, weight_shapes) for s in shapes)
            if not hit and min_operand_elems is not None:
                hit = max(math.prod(s) for s in shapes) >= min_operand_elems
        else:  # mul: only weight/table-shaped operands are forbidden
            forbidden = weight_shapes + table_shapes
            hit = any(_matches_any(s, forbidden) for s in shapes)
        if hit:
            out.append(Violation("multiplier_free", name, f"operands {shapes}"))
    return out


def zero_copy_violations(
    jaxpr,
    *,
    table_shapes: Iterable[Sequence[int]] = (),
    min_out_elems: int | None = None,
    primitives: Sequence[str] = _ZERO_COPY_PRIMITIVES,
) -> list[Violation]:
    """The PR 3 layout contract: the traced step never materialises a
    table-shaped value via ``concatenate`` (stack), ``transpose``, or
    ``copy`` — the stored pre-stacked leaves are consumed as-is.

    Flags equations whose *output* shape ends in a ``table_shapes`` suffix
    or (when ``min_out_elems`` is given) reaches that element count.
    """
    table_shapes = tuple(tuple(s) for s in table_shapes)
    out = []
    for eqn in as_eqns(jaxpr):
        if eqn.primitive.name not in primitives:
            continue
        shapes = [tuple(v.aval.shape) for v in eqn.outvars]
        hit = any(_matches_any(s, table_shapes) for s in shapes)
        if not hit and min_out_elems is not None:
            hit = max(math.prod(s) for s in shapes) >= min_out_elems
        if hit:
            out.append(
                Violation("zero_copy", eqn.primitive.name, f"outputs {shapes}")
            )
    return out


def plan_consistency_violations(mplan, tree, *, batch: int = 1) -> list[Violation]:
    """The plan and the converted tree agree.

    Checks, per the ``ModelPlan`` contract:
    * every plan entry is consumed by a converted leaf, and every leaf's
      layer appears in the plan (no silent dense leftovers);
    * each leaf carries the exact per-layer plan object (family included);
    * the bytes actually materialised across table leaves equal
      ``mplan.total_lut_bytes`` (the PR 5 copies accounting);
    * any tuned ``blocks`` riding a plan are legal under the kernels'
      tiling and VMEM budget (``kernels.lut_affine.autotune.blocks_fit_vmem``).
    """
    from repro.core.convert import LUTGroup, LUTLinear
    from repro.core.planner import path_key
    from repro.kernels.lut_affine.autotune import TunePoint, blocks_fit_vmem

    out = []
    consumed: dict[str, object] = {}
    table_bytes = 0

    def walk(node, path):
        nonlocal table_bytes
        if isinstance(node, LUTLinear):
            consumed[path_key(path)] = node
            table_bytes += node.tables.size * node.tables.dtype.itemsize
        elif isinstance(node, LUTGroup):
            for name in node.members:
                consumed[path_key(path[:-1] + (name,))] = node
            table_bytes += node.tables.size * node.tables.dtype.itemsize
        elif isinstance(node, dict):
            for k, v in node.items():
                walk(v, path + (k,))

    walk(tree, ())

    for key in sorted(set(mplan.layers) - set(consumed)):
        out.append(
            Violation(
                "plan_consistency", "never_consumed", f"plan entry {key!r}"
            )
        )
    for key in sorted(set(consumed) - set(mplan.layers)):
        out.append(
            Violation(
                "plan_consistency", "unplanned_leaf", f"converted leaf {key!r}"
            )
        )

    group_sizes: dict[str, int] = {}
    for group in mplan.groups:
        for key in group:
            group_sizes[key] = len(group)
    for key, node in sorted(consumed.items()):
        plan = mplan.layers.get(key)
        if plan is None:
            continue
        if node.plan != plan:
            out.append(
                Violation(
                    "plan_consistency",
                    "plan_mismatch",
                    f"{key!r}: leaf plan {node.plan} != planned {plan}",
                )
            )
        if plan.blocks is not None:
            pt = TunePoint.from_plan(plan, batch, G=group_sizes.get(key, 1))
            if not blocks_fit_vmem(pt, plan.blocks):
                out.append(
                    Violation(
                        "plan_consistency",
                        "blocks_over_vmem",
                        f"{key!r}: blocks {plan.blocks} bust the VMEM "
                        f"budget at point {pt}",
                    )
                )

    if table_bytes != mplan.total_lut_bytes:
        out.append(
            Violation(
                "plan_consistency",
                "byte_mismatch",
                f"materialised {table_bytes} table bytes != plan "
                f"total_lut_bytes {mplan.total_lut_bytes}",
            )
        )
    return out
