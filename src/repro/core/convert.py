"""Model -> TableNet conversion pass.

Walks a trained parameter tree and replaces every eligible linear node
({"w": 2-D array} produced by ``models.layers.linear_spec``) with its LUT
tables, exactly as the paper prescribes post-training.  The zoo's
:func:`repro.models.layers.linear` then executes those layers via the LUT
path, so a converted model serves **multiplier-free** (in the paper's
arithmetic sense — see DESIGN.md §2) with no other code changes.

Converted layout
----------------
A converted node is a registered pytree class carrying the tables *and an
explicit plan record* (chunk size / number format / mode) as static
metadata — execution never re-infers the plan from table shapes (shape
sniffing is genuinely ambiguous once fixed-point plans enter the picture:
an unsigned fixed-point chunk-7 bitplane table and a signed-fp16 chunk-1
table both have 2**7 entries).

* :class:`LUTLinear` — one projection: ``tables (..., k, entries, p)``.
* :class:`LUTGroup` — fusable sibling projections (QKV with equal head
  counts, K/V, gate/up) **pre-stacked at conversion time** into one
  ``tables (..., G, k, entries, p)`` leaf, replacing the member keys with
  a single ``"a+b"`` key.  Serving indexes the stored group directly — no
  per-decode-step stack/concat of table-sized operands ever appears under
  jit (asserted at the jaxpr level in ``tests/test_grouped_layout.py``).

Non-affine recurrences (SSD / WKV — data-dependent transition weights) and
raw tensors (embeddings, routers, norm scales) are left untouched; MoE
expert stacks are converted per-expert via ``convert_experts=True``
(vmapped table build) under the same eligibility rules
(``min_features``/``predicate``) the planner applies.  Same-shape expert
pairs (``w_gate``/``w_up``) pre-stack into one :class:`LUTGroup` whose
leaf is ``(..., E, G, k, entries, p)`` — the exact array
``kernels.lut_affine.lut_affine_experts`` consumes after the layer scan
slices the leading dim — and ``models.moe.moe_ffn`` executes converted
expert leaves via the ragged LUT path (codes packed once per token; the
``ragged_dot`` calls disappear from the decode program).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Union

import jax
import jax.numpy as jnp

from repro.core.lut import LUTPlan, build_luts, quantize_tables
from repro.core.lut_tl1 import TL1Plan, build_tl1_tables
from repro.core.planner import ModelPlan, path_key
from repro.core.quantize import Float16Format

AnyPlan = Union[LUTPlan, TL1Plan]

# Sibling key sets that execute against the SAME input at their call sites
# (models.layers.attention / models.layers.mlp / models.encdec) and are
# therefore fusable into one grouped dispatch.  Detection takes the maximal
# same-shape subset, so GQA (wq wider than wk/wv) still fuses K/V.
FUSABLE_SIBLINGS = (("wq", "wk", "wv"), ("w_gate", "w_up"))

EXPERT_WEIGHT_KEYS = ("w_gate", "w_up", "w_down")


@jax.tree_util.register_pytree_with_keys_class
@dataclasses.dataclass(eq=False)
class LUTLinear:
    """A converted projection: kernel-ready tables + its conversion plan.

    ``plan`` is pytree *aux data* (static under jit), so the execution path
    reads chunk/format/mode directly instead of sniffing table shapes.
    """

    # weight family: (..., k, entries, p) table entries.
    # tl1 family: (..., kb, p) uint8 packed base-3 weight-pair indices.
    tables: Any
    plan: AnyPlan
    b: Any = None  # (..., p) or None
    # Weight family: scalar power-of-2 dequant scale when
    # ``plan.table_format`` stores the tables narrow (i8/i16); None for
    # full-width tables.  TL1 family: the absmean ternary weight scale
    # (always present).  A leaf (not aux): it is data derived from the
    # weights, and it must ride checkpoints.
    scale: Any = None

    def tree_flatten_with_keys(self):
        return (
            (
                (jax.tree_util.GetAttrKey("tables"), self.tables),
                (jax.tree_util.GetAttrKey("b"), self.b),
                (jax.tree_util.GetAttrKey("scale"), self.scale),
            ),
            self.plan,
        )

    @classmethod
    def tree_unflatten(cls, plan, children):
        tables, b, scale = children
        return cls(tables, plan, b, scale)


@jax.tree_util.register_pytree_with_keys_class
@dataclasses.dataclass(eq=False)
class LUTGroup:
    """Pre-stacked fusable sibling projections sharing one plan.

    ``tables`` holds every member's tables stacked on a group axis just
    before the chunk axis — ``(..., G, k, entries, p)`` — which is exactly
    the layout ``kernels.lut_affine.lut_affine_grouped`` consumes, so a
    grouped decode step reads the stored leaf with zero copies.

    ``b`` is ``None`` (no member has a bias), a stacked ``(..., G, p)``
    array (every member has one), or a per-member tuple with ``None``
    holes (mixed) — mixed-bias groups still fuse.
    """

    tables: Any  # (..., G, k, entries, p); tl1: (..., G, kb, p) uint8
    plan: AnyPlan
    members: tuple  # sibling keys in call-site order, e.g. ("wk", "wv")
    b: Any = None  # None | (..., G, p) | tuple[(..., p) | None, ...]
    # Weight family: ONE scalar dequant scale shared by every member (the
    # group leaf is a single stacked array, quantized as one); None for
    # full-width tables.  TL1 family: per-member ternary scales, stacked
    # ``(..., G)`` (each member's absmean fit is its own).
    scale: Any = None

    def tree_flatten_with_keys(self):
        return (
            (
                (jax.tree_util.GetAttrKey("tables"), self.tables),
                (jax.tree_util.GetAttrKey("b"), self.b),
                (jax.tree_util.GetAttrKey("scale"), self.scale),
            ),
            (self.plan, self.members),
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        plan, members = aux
        tables, b, scale = children
        return cls(tables, plan, members, b, scale)

    def member_bias(self, g: int):
        if self.b is None:
            return None
        if isinstance(self.b, tuple):
            return self.b[g]
        return self.b[..., g, :]


@dataclasses.dataclass(frozen=True)
class ConvertReport:
    converted: int
    skipped: int
    weight_bytes: int
    table_bytes: int
    grouped: int = 0  # number of LUTGroup nodes emitted


def _is_linear_node(node: Any) -> bool:
    # 2-D = plain linear; 3-D = scan-stacked (L, q, p) — both convertible
    return (
        isinstance(node, dict)
        and "w" in node
        and hasattr(node["w"], "ndim")
        and node["w"].ndim in (2, 3)
        and set(node) <= {"w", "b"}
    )


def _is_expert_stack(node: Any) -> bool:
    return (
        isinstance(node, dict)
        and {"w_gate", "w_up", "w_down", "router"} <= set(node)
        and hasattr(node["w_gate"], "ndim")
        and node["w_gate"].ndim in (3, 4)
    )


def sibling_groups(node: dict) -> list[tuple[str, ...]]:
    """Fusable sibling sets present in ``node``: for each candidate key set
    in :data:`FUSABLE_SIBLINGS`, the same-``w``-shape classes with >= 2
    members (shape equality includes any leading scan/layer dims).  Shared
    with the planner so grouping decisions never drift between the two."""
    out: list[tuple[str, ...]] = []
    for base in FUSABLE_SIBLINGS:
        present = [n for n in base if n in node and _is_linear_node(node[n])]
        by_shape: dict[tuple, list[str]] = {}
        for n in present:
            by_shape.setdefault(tuple(node[n]["w"].shape), []).append(n)
        for members in by_shape.values():
            if len(members) > 1:
                out.append(tuple(members))
    return out


def expert_sibling_groups(node: dict) -> list[tuple[str, ...]]:
    """Fusable sibling sets among the RAW expert-stack weights of ``node``
    (an ``_is_expert_stack`` dict): same-shape classes of the candidate key
    sets, shape equality including the leading layer/expert dims — the
    expert-stack analogue of :func:`sibling_groups` (members are bare
    ``(..., E, q, p)`` arrays, not ``{"w": ...}`` linear nodes).  Shared
    with the planner so grouping decisions never drift."""
    out: list[tuple[str, ...]] = []
    for base in FUSABLE_SIBLINGS:
        present = [
            n for n in base if n in EXPERT_WEIGHT_KEYS and hasattr(node.get(n), "ndim")
        ]
        by_shape: dict[tuple, list[str]] = {}
        for n in present:
            by_shape.setdefault(tuple(node[n].shape), []).append(n)
        for members in by_shape.values():
            if len(members) > 1:
                out.append(tuple(members))
    return out


def group_key(members: tuple) -> str:
    """Tree key a :class:`LUTGroup` is stored under (e.g. ``"wk+wv"``)."""
    return "+".join(members)


def _per_set(build, ws: list, lead: int):
    """``build(ws) -> (tables, scale)`` one leading (layer) index at a time
    when the weights carry ``lead`` leading scan dims, stacking the results.
    ``build`` runs jitted, so its fp32 intermediates fuse, and only one
    set's are alive at once: a model-width stack converts within device
    memory.  Identical to building the whole stack (every table set and its
    scale depend on that set's weights only)."""
    build = jax.jit(build)

    def sets(ws, lead):
        if lead == 0:
            return build(ws)
        parts = [sets([w[i] for w in ws], lead - 1) for i in range(ws[0].shape[0])]
        tables = jnp.stack([t for t, _ in parts])
        scale = None if parts[0][1] is None else jnp.stack([s for _, s in parts])
        return tables, scale

    return sets(ws, lead)


def _build_tables(w, plan: LUTPlan, dtype):
    """build_luts vmapped over any leading (expert) dims."""

    def fn(m):
        return build_luts(m.astype(jnp.float32), plan)

    for _ in range(w.ndim - 2):
        fn = jax.vmap(fn)
    return fn(w).astype(dtype)


def _build_tl1(w):
    """build_tl1_tables vmapped over any leading (expert) dims.

    Returns ``(packed (..., kb, p) uint8, scale (...) f32)`` — one ternary
    scale per weight matrix, shaped like the leading dims."""

    fn = build_tl1_tables
    for _ in range(w.ndim - 2):
        fn = jax.vmap(fn)
    return fn(w.astype(jnp.float32))


def convert_params(
    params: dict,
    chunk_size: int = 1,
    min_features: int = 1,
    predicate: Callable[[tuple, dict], bool] | None = None,
    table_dtype=jnp.float32,
    convert_experts: bool = False,
    signed: bool = True,  # LM activations are signed; paper models may use False
    plan: Optional[ModelPlan] = None,
    group_siblings: bool = True,
) -> tuple[dict, ConvertReport]:
    """Returns (converted tree, report).  ``predicate(path, node)`` can veto
    individual layers (default: convert everything eligible).

    With ``plan`` (a :class:`repro.core.planner.ModelPlan`, e.g. from
    ``plan_model``) each layer uses its *own* plan, looked up by tree path;
    layers absent from the plan are skipped — but a plan entry that the
    converter never consumes (a path the tree lacks, the predicate vetoes,
    or an expert entry without ``convert_experts=True``) **raises**, so
    planner/converter eligibility can never silently disagree.

    ``group_siblings=True`` (the default) emits fusable sibling projections
    as one pre-stacked :class:`LUTGroup` per group: always under the
    uniform plan, and exactly the groups ``plan.groups`` declares under a
    planned conversion (``plan_model`` never splits a group across plans).
    Pass ``group_siblings=False`` for the flat per-projection layout.
    """
    stats = {"converted": 0, "skipped": 0, "w_bytes": 0, "t_bytes": 0, "groups": 0}
    fmt = Float16Format(signed=signed)
    used_plan_keys: set[str] = set()
    declared_groups = (
        {frozenset(g) for g in plan.groups} if plan is not None else None
    )

    def member_plan(path: tuple, node: dict) -> Optional[AnyPlan]:
        """The plan this linear converts under, or None to leave it dense."""
        w = node["w"]
        q, p = w.shape[-2:]
        if q < min_features or (predicate and not predicate(path, node)):
            return None
        if plan is None:
            return LUTPlan(q, p, chunk_size, fmt, mode="bitplane")
        layer_plan = plan.layers.get(path_key(path))
        if layer_plan is None:
            return None
        if (layer_plan.in_features, layer_plan.out_features) != (q, p):
            raise ValueError(
                f"plan for {path_key(path)} is "
                f"{layer_plan.in_features}x{layer_plan.out_features}, "
                f"layer is {q}x{p}"
            )
        used_plan_keys.add(path_key(path))
        return layer_plan

    def finalize_tables(tables, layer_plan: LUTPlan, trailing: int):
        """(stored tables, scale): narrow-quantize when the plan asks for it.

        ``trailing`` = dims of one dispatched table set; leading (scan)
        dims keep per-set scales so the leaf stays scan-sliceable."""
        if layer_plan.table_format is None:
            return tables.astype(table_dtype), None
        return quantize_tables(tables, layer_plan.table_format, trailing)

    def convert_one(node: dict, layer_plan: AnyPlan, expert: bool = False) -> LUTLinear:
        w = node["w"]

        def build(ws):
            if isinstance(layer_plan, TL1Plan):
                return _build_tl1(ws[0])
            tables = _build_tables(ws[0], layer_plan, jnp.float32)
            return finalize_tables(tables, layer_plan, 3 + expert)

        tables, scale = _per_set(build, [w], w.ndim - 2 - expert)
        stats["converted"] += 1
        stats["w_bytes"] += w.size * w.dtype.itemsize
        stats["t_bytes"] += tables.size * tables.dtype.itemsize
        return LUTLinear(tables=tables, plan=layer_plan, b=node.get("b"), scale=scale)

    def convert_group(
        path: tuple, node: dict, members: tuple, expert: bool = False
    ) -> Optional[LUTGroup]:
        """One LUTGroup for ``members``, or None when they can't share a
        plan (then they convert individually, like before grouping)."""
        key_tuple = frozenset(path_key(path + (m,)) for m in members)
        declared = declared_groups is not None and key_tuple in declared_groups
        if declared_groups is not None and not declared:
            return None  # planned conversion: only plan-declared groups fuse
        plans = [member_plan(path + (m,), node[m]) for m in members]
        if any(p is None for p in plans):
            if declared:
                raise ValueError(
                    f"plan declares group {group_key(members)} at "
                    f"{path_key(path)} but not every member is convertible"
                )
            return None
        if any(p != plans[0] for p in plans[1:]):
            # a hand-edited plan split the group; plan_model never does
            raise ValueError(
                f"group {group_key(members)} at {path_key(path)} has "
                f"mismatched member plans — grouped siblings must share one"
            )
        if isinstance(plans[0], TL1Plan):

            def build(ws):
                built = [_build_tl1(w) for w in ws]
                # stack G just before the packed-chunk axis: (..., G, kb, p);
                # ternary scales are per member, stacked to (..., G)
                tables = jnp.stack([t for t, _ in built], axis=built[0][0].ndim - 2)
                return tables, jnp.stack([s for _, s in built], axis=-1)

        else:

            def build(ws):
                member_tables = [_build_tables(w, plans[0], jnp.float32) for w in ws]
                # quantize the STACKED leaf as one, so the whole group shares
                # one dequant scale (the group executes as a single dispatch)
                return finalize_tables(
                    jnp.stack(member_tables, axis=member_tables[0].ndim - 3),
                    plans[0],
                    4 + expert,
                )

        ws = [node[m]["w"] for m in members]
        tables, scale = _per_set(build, ws, ws[0].ndim - 2 - expert)
        stats["converted"] += len(members)
        for m in members:
            w = node[m]["w"]
            stats["w_bytes"] += w.size * w.dtype.itemsize
        stats["t_bytes"] += tables.size * tables.dtype.itemsize
        biases = [node[m].get("b") for m in members]
        if all(b is not None for b in biases):
            b = jnp.stack(biases, axis=biases[0].ndim - 1)
        elif any(b is not None for b in biases):
            b = tuple(biases)  # mixed-bias group: per-member leaves
        else:
            b = None
        stats["groups"] += 1
        return LUTGroup(
            tables=tables, plan=plans[0], members=members, b=b, scale=scale
        )

    def convert_expert_member(path: tuple, key: str, w3) -> Any:
        # same eligibility/plan rules as plain linears (member_plan), so
        # planner and converter can never disagree on expert stacks
        layer_plan = member_plan(path + (key,), {"w": w3})
        if layer_plan is None:
            stats["skipped"] += 1
            return w3
        return convert_one({"w": w3}, layer_plan, expert=True)

    def walk(path: tuple, node: Any):
        if _is_linear_node(node):
            layer_plan = member_plan(path, node)
            if layer_plan is None:
                stats["skipped"] += 1
                return node
            return convert_one(node, layer_plan)
        if not isinstance(node, dict):
            return node
        if convert_experts and _is_expert_stack(node):
            # same grouping machinery as dense siblings: wrap the raw
            # (..., E, q, p) stacks as linear nodes so convert_group's
            # plan/eligibility checks apply unchanged; the stacked leaf is
            # (..., E, G, k, entries, p) — lut_affine_experts' layout
            egrouped: dict[str, LUTGroup] = {}
            econsumed: set[str] = set()
            if group_siblings:
                wrapped = {
                    k: {"w": v} for k, v in node.items() if k in EXPERT_WEIGHT_KEYS
                }
                for members in expert_sibling_groups(node):
                    g = convert_group(path, wrapped, members, expert=True)
                    if g is not None:
                        egrouped[group_key(members)] = g
                        econsumed |= set(members)
            eout: dict[str, Any] = {}
            for k, v in node.items():
                if k in econsumed:
                    gk = next(gk for gk, g in egrouped.items() if k in g.members)
                    if gk not in eout:
                        eout[gk] = egrouped[gk]
                elif k in EXPERT_WEIGHT_KEYS:
                    eout[k] = convert_expert_member(path, k, v)
                else:
                    eout[k] = walk(path + (k,), v)
            return eout
        grouped: dict[str, LUTGroup] = {}
        consumed: set[str] = set()
        if group_siblings:
            for members in sibling_groups(node):
                g = convert_group(path, node, members)
                if g is not None:
                    grouped[group_key(members)] = g
                    consumed |= set(members)
        out: dict[str, Any] = {}
        for k, v in node.items():
            if k in consumed:
                gk = next(gk for gk, g in grouped.items() if k in g.members)
                if gk not in out:
                    out[gk] = grouped[gk]
                continue
            out[k] = walk(path + (k,), v)
        return out

    out = walk((), params)
    if plan is not None:
        unused = sorted(set(plan.layers) - used_plan_keys)
        if unused:
            raise ValueError(
                "plan entries the converter never consumed (planner/converter "
                f"eligibility mismatch — check predicate/min_features/"
                f"convert_experts): {unused}"
            )
    report = ConvertReport(
        stats["converted"],
        stats["skipped"],
        stats["w_bytes"],
        stats["t_bytes"],
        stats["groups"],
    )
    return out, report


def conversion_summary(report: ConvertReport) -> str:
    ratio = report.table_bytes / max(report.weight_bytes, 1)
    return (
        f"converted {report.converted} linears ({report.skipped} skipped, "
        f"{report.grouped} pre-stacked groups): "
        f"{report.weight_bytes / 2**20:.1f} MiB weights -> "
        f"{report.table_bytes / 2**20:.1f} MiB tables ({ratio:.0f}x)"
    )
