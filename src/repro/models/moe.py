"""Mixture-of-experts FFN: top-k routing + grouped expert execution.

Distribution (DESIGN.md §4): dispatch is *local to each data shard* via
``jax.shard_map`` — routing, sort and the grouped expert evaluation never
cross the data axis; expert weights are TP-sharded on d_ff over the model
axis (expert-TP, not EP, so arbitrary expert counts never constrain the
mesh) and the down-projection's partial sums reduce with one psum over
"model" — the same collective a dense TP MLP pays.  Measured on the
fake-device mesh: the naive GSPMD formulation instead all-gathers the full
(T*k, d) dispatch per layer.  The router's load-balance aux loss is
pmean'd over the data AND model axes inside the same shard_map, so the
returned scalar is the global batch mean and genuinely replicated (the
``P()`` out-spec is sound).

Expert execution dispatches per projection on the parameter leaf:

* raw ``(E, q, p)`` arrays     -> ``lax.ragged_dot`` (dense grouped GEMM)
* ``core.convert.LUTLinear``   -> the ragged LUT path (TableNet)
* ``core.convert.LUTGroup``    -> same, both gate/up in one dispatch

so ``convert_params(convert_experts=True)`` trees serve multiplier-free:
the input decomposition of each token is expert-independent, so LUT codes
are packed ONCE per token (then gathered into the expert-sorted order) and
``kernels.lut_affine.lut_affine_experts`` — or its jnp oracle under GSPMD
— replaces the ragged_dot calls entirely.  Mixed trees (a plan converting
only some of gate/up/down) execute coherently, each projection on its own
path.  TP sharding of LUT experts: gate/up tables shard their output dim
(= d_ff) over "model" exactly like the dense weights; the down tables
shard their CHUNK axis (the d_ff contraction lives in the chunks), each
shard packs its local h slice under a chunk-aligned local plan, and the
same psum reduces the partial sums — when d_ff doesn't split into
whole chunks per shard, expert TP is dropped (replicated tables,
redundant compute) rather than served wrong.

Qwen2-MoE-style shared experts run as a dense SwiGLU branch added to the
routed output, and the router uses the standard load-balancing auxiliary
loss (Switch §2.2), returned alongside the output.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.convert import LUTGroup, LUTLinear
from repro.core.lut import LUTPlan, pack_codes, plane_scales
from repro.core.lut_tl1 import TL1Plan, build_act_lut, quantize_acts, unpack_indices
from repro.kernels.common import check_acc_contract
from repro.models.layers import Ctx, ExecCfg, mlp, mlp_specs
from repro.models.params import PSpec


def moe_specs(cfg: ModelConfig) -> dict:
    d, f, E = cfg.d_model, cfg.moe_d_ff, cfg.num_experts
    s = {
        "router": PSpec((d, E), ("embed", None), dtype=jnp.float32),
        "w_gate": PSpec((E, d, f), ("experts", "embed", "mlp")),
        "w_up": PSpec((E, d, f), ("experts", "embed", "mlp")),
        "w_down": PSpec((E, f, d), ("experts", "mlp", "embed")),
    }
    if cfg.num_shared_experts:
        s["shared"] = mlp_specs(cfg, d_ff=cfg.num_shared_experts * cfg.moe_d_ff)
        s["shared_gate"] = PSpec((d, 1), ("embed", None), dtype=jnp.float32)
    return s


def _route(x: jax.Array, router_w: jax.Array, cfg: ModelConfig):
    """(T, d) -> combine weights (T, k), expert ids (T, k), aux loss scalar."""
    logits = x.astype(jnp.float32) @ router_w
    probs = jax.nn.softmax(logits, axis=-1)
    weights, idx = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    weights = weights / jnp.sum(weights, -1, keepdims=True)
    # Switch-style load-balance loss: E * sum_e f_e * P_e
    E = cfg.num_experts
    f_e = jnp.mean(jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=1), axis=0)
    P_e = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(f_e * P_e)
    return weights.astype(x.dtype), idx, aux


# ---------------------------------------------------------------------------
# Per-projection expert dispatch (dense ragged_dot | ragged LUT)
# ---------------------------------------------------------------------------


def _member_node(experts: dict, name: str):
    """Resolve a projection by name, whether stored per-name or inside a
    pre-stacked expert :class:`LUTGroup` (``"w_gate+w_up"`` keys)."""
    if name in experts:
        return experts[name]
    for node in experts.values():
        if isinstance(node, LUTGroup) and name in node.members:
            return node
    raise KeyError(name)


def _local_plan(plan, tables: jax.Array):
    """The packing plan for a possibly chunk-axis-TP-sliced table leaf: a
    shard holding ``k_local`` of the ``k`` chunks packs a ``k_local * m``
    feature slice (exact: LUT affine is linear in the table chunks, and the
    slicing is only enabled when chunk boundaries align with the shards).

    TL1 leaves never chunk-shard (``_down_chunks_shardable`` forces the TP
    drop), and their packed-chunk axis sits at ``-2``, not ``-3`` — so the
    plan passes through untouched."""
    if plan.table_family == "tl1":
        return plan
    k_local = tables.shape[-3]
    if k_local == plan.num_chunks:
        return plan
    return dataclasses.replace(plan, in_features=k_local * plan.chunk_size)


def _ragged_lut(
    tables: jax.Array,  # (E, G, k, entries, p)
    plan: LUTPlan,
    codes: jax.Array,  # (T, n, k) expert-sorted
    group_sizes: jax.Array,  # (E,)
    ex: ExecCfg,
    scale: jax.Array | None = None,  # narrow-table dequant scale
) -> jax.Array:
    """(G, T, p) float32 — every token row against ITS expert's tables."""
    check_acc_contract("lut_affine_experts", plan, "float32")
    scales = jnp.asarray(plane_scales(plan), jnp.float32)
    if scale is not None:  # power-of-2 dequant folds into the plane scales
        scales = scales * scale
    shift = plan.index_bits if plan.mode == "bitplane_shift" else 0
    if ex.use_pallas:
        from repro.kernels.lut_affine.ops import lut_affine_experts

        return lut_affine_experts(
            codes,
            tables,
            scales,
            group_sizes,
            blocks=plan.blocks,
            shift_bits=shift,
            plan=plan,
        )
    from repro.kernels.lut_affine.ref import lut_affine_experts_ref

    return lut_affine_experts_ref(
        codes, tables, scales, group_sizes, shift_bits=shift
    )


def _ragged_tl1(
    tables: jax.Array,  # (E, G, kb, p) uint8 packed base-3 indices
    plan: TL1Plan,
    acts: jax.Array,  # (T, 4*kb) expert-sorted activation codes
    group_sizes: jax.Array,  # (E,)
    scale: jax.Array | None = None,  # (E, G) per-expert ternary scales
    act_scale: jax.Array | None = None,  # (T, 1) expert-sorted, int8 mode only
) -> jax.Array:
    """(G, T, p) float32 — TL1 twin of :func:`_ragged_lut`.

    The activation LUT is per TOKEN (the inverse of the weight family, where
    tables are per expert and codes per token), so the ragged structure only
    selects which expert's packed index matrix each sorted row gathers from.
    Runs as a jnp oracle on every path — the transient ``(T, 2kb, 9)`` LUT is
    small and the gather is the whole computation, so there is no separate
    experts Pallas kernel for this family."""
    check_acc_contract(
        "ragged_tl1", plan, "int32" if plan.act_bits is not None else "float32"
    )
    E, G = tables.shape[0], tables.shape[1]
    T = acts.shape[0]
    expert_of = jnp.repeat(jnp.arange(E), group_sizes, total_repeat_length=T)
    idx = unpack_indices(tables)  # (E, G, 2kb, p)
    rows = jnp.take(idx, expert_of, axis=0)  # (T, G, 2kb, p)
    lut = build_act_lut(acts)[:, None]  # (T, 1, 2kb, 9)
    lut = jnp.broadcast_to(lut, rows.shape[:-1] + (lut.shape[-1],))
    g = jnp.take_along_axis(lut, rows, axis=-1)  # (T, G, 2kb, p)
    acc = jnp.int32 if jnp.issubdtype(g.dtype, jnp.integer) else jnp.float32
    out = jnp.moveaxis(jnp.sum(g.astype(acc), axis=-2), 0, 1)  # (G, T, p)
    out = out.astype(jnp.float32)
    if act_scale is not None:
        out = out * act_scale[None]  # (1, T, 1)
    if scale is not None:
        out = out * jnp.moveaxis(scale[expert_of], 0, 1)[..., None]  # (G, T, 1)
    return out


def _moe_local(
    x, experts: dict, *, cfg: ModelConfig, ex: ExecCfg, psum_axes, mean_axes
):
    """Per-shard expert compute. x: (T_local, d); tables/weights may be TP
    slices.  Dispatches per projection on the leaf type, so dense, fully
    converted, and mixed expert trees all execute coherently."""
    k = cfg.num_experts_per_tok
    weights, idx, aux = _route(x, experts["router"], cfg)
    flat = idx.reshape(-1)  # (T*k,)
    order = jnp.argsort(flat)
    token_of = order // k
    group_sizes = jnp.bincount(flat, length=cfg.num_experts)

    # LUT input decomposition is expert-independent: pack x ONCE per token,
    # then gather the packed codes into the expert-sorted (T*k) order — the
    # same gather the dense path applies to the raw activations.
    pack_cache: dict = {}  # keyed by plan; TL1 entries hold (codes, act_scale)

    def sorted_codes(plan: LUTPlan, src: jax.Array, gather: bool) -> jax.Array:
        if gather:  # src is (T, d): pack per token, gather to (T*k, n, kc)
            if plan not in pack_cache:
                pack_cache[plan] = pack_codes(src, plan)
            return jnp.take(pack_cache[plan], token_of, axis=0)
        return pack_codes(src, plan)  # src already expert-sorted (h)

    def sorted_tl1(plan: TL1Plan, src: jax.Array, gather: bool):
        """(codes, act_scale) in expert-sorted row order — quantization is
        expert-independent, so it runs once per token like the packing."""
        if gather:
            if plan not in pack_cache:
                pack_cache[plan] = quantize_acts(src, plan)
            codes, ascale = pack_cache[plan]
            codes = jnp.take(codes, token_of, axis=0)
            if ascale is not None:
                ascale = jnp.take(ascale, token_of, axis=0)
            return codes, ascale
        return quantize_acts(src, plan)

    def project_tl1(node, name: str, src: jax.Array, gather: bool) -> jax.Array:
        plan = node.plan
        codes, ascale = sorted_tl1(plan, src, gather)
        if isinstance(node, LUTGroup):
            g = node.members.index(name)
            tables, scale = node.tables[:, g : g + 1], node.scale[:, g : g + 1]
        else:
            tables, scale = node.tables[:, None], node.scale[:, None]
        y = _ragged_tl1(
            tables, plan, codes, group_sizes, scale=scale, act_scale=ascale
        )
        return y[0].astype(x.dtype)

    def project(name: str, src: jax.Array, gather: bool) -> jax.Array:
        """One expert projection over the expert-sorted rows."""
        node = _member_node(experts, name)
        if isinstance(node, (LUTGroup, LUTLinear)) and isinstance(
            node.plan, TL1Plan
        ):
            return project_tl1(node, name, src, gather)
        if isinstance(node, LUTGroup):
            g = node.members.index(name)
            plan = _local_plan(node.plan, node.tables)
            codes = sorted_codes(plan, src, gather)
            y = _ragged_lut(
                node.tables[:, g : g + 1],
                plan,
                codes,
                group_sizes,
                ex,
                scale=node.scale,
            )
            return y[0].astype(x.dtype)
        if isinstance(node, LUTLinear):
            plan = _local_plan(node.plan, node.tables)
            codes = sorted_codes(plan, src, gather)
            y = _ragged_lut(
                node.tables[:, None], plan, codes, group_sizes, ex, scale=node.scale
            )[0]
            return y.astype(x.dtype)
        rows = jnp.take(src, token_of, axis=0) if gather else src
        return jax.lax.ragged_dot(rows, node, group_sizes)

    gate_node = _member_node(experts, "w_gate")
    up_node = _member_node(experts, "w_up")
    if isinstance(gate_node, LUTGroup) and gate_node is up_node:
        # pre-stacked gate/up pair: ONE fused ragged dispatch for both
        plan = _local_plan(gate_node.plan, gate_node.tables)
        if isinstance(plan, TL1Plan):
            codes, ascale = sorted_tl1(plan, x, gather=True)
            gu = _ragged_tl1(
                gate_node.tables,
                plan,
                codes,
                group_sizes,
                scale=gate_node.scale,
                act_scale=ascale,
            )
        else:
            codes = sorted_codes(plan, x, gather=True)
            gu = _ragged_lut(
                gate_node.tables, plan, codes, group_sizes, ex, scale=gate_node.scale
            )
        order_g = {m: i for i, m in enumerate(gate_node.members)}
        g = gu[order_g["w_gate"]].astype(x.dtype)
        u = gu[order_g["w_up"]].astype(x.dtype)
    else:
        g = project("w_gate", x, gather=True)
        u = project("w_up", x, gather=True)
    h = jax.nn.silu(g) * u  # (T*k, f_local)
    y = project("w_down", h, gather=False)  # partial over f_local
    if psum_axes:
        y = jax.lax.psum(y, psum_axes)
    if mean_axes:
        aux = jax.lax.pmean(aux, mean_axes)
    combine = weights.reshape(-1)[order][:, None].astype(y.dtype)
    out = jnp.zeros_like(x).at[token_of].add(y * combine)
    return out, aux


# ---------------------------------------------------------------------------
# TP sharding of the expert parameter tree
# ---------------------------------------------------------------------------


def _down_chunks_shardable(plan, tp_size: int) -> bool:
    """Chunk-axis TP slices are exact only when every shard holds whole
    chunks covering exactly its d_ff slice (no ragged tail chunk).  TL1
    packed bytes interleave two chunks per byte and the activation LUT is
    per token, so TL1 down projections never chunk-shard — expert TP drops
    to replicated tables instead."""
    if plan.table_family == "tl1":
        return False
    return tp_size > 1 and plan.in_features % (tp_size * plan.chunk_size) == 0


def _lut_node_spec(node, tables_spec: P):
    """Node-shaped in_spec for a LUT leaf bundle: the table leaf gets
    ``tables_spec``; the scalar dequant scale (present only for narrow
    table formats) is replicated; expert biases are never emitted by
    conversion, so ``b`` stays the empty subtree."""
    return dataclasses.replace(
        node, tables=tables_spec, b=None, scale=None if node.scale is None else P()
    )


def _expert_specs(experts: dict, tp: tuple) -> dict:
    """shard_map in_specs for the expert tree: one spec subtree per node.
    Gate/up shard their output (d_ff) dim — the table p axis — over the
    model axis exactly like the dense weights; the down projection shards
    its contraction: the weight's d_ff dim when dense, the table chunk axis
    when converted."""
    tpa = tp[0] if tp else None
    specs: dict = {}
    for key, node in experts.items():
        if key == "router":
            specs[key] = P(None, None)
        elif isinstance(node, (LUTGroup, LUTLinear)):
            # ndim-generic over both families: weight tables are
            # (E, [G,] k, entries, p), TL1 packed leaves (E, [G,] kb, p).
            axes = [None] * node.tables.ndim
            if key == "w_down" and node.plan.table_family == "weight":
                axes[-3] = tpa  # (..., k, entries, d): shard chunks (= d_ff)
            else:  # gate/up shard the output dim (p = f); TL1 down never
                axes[-1] = tpa  # TP-shards (tp was already dropped above)
            specs[key] = _lut_node_spec(node, P(*axes))
        elif key == "w_down":  # (E, f, d)
            specs[key] = P(None, tpa, None)
        else:  # raw (E, d, f) gate/up
            specs[key] = P(None, None, tpa)
    return specs


def moe_ffn(p: dict, x: jax.Array, ctx: Ctx):
    """(B, S, d) -> (B, S, d), aux_loss. shard_map'd when a mesh is active."""
    cfg, sh = ctx.cfg, ctx.shard
    B, S, d = x.shape
    xt = x.reshape(B * S, d)
    experts = {k: v for k, v in p.items() if k not in ("shared", "shared_gate")}
    if sh.mesh is None:
        out, aux = _moe_local(
            xt, experts, cfg=cfg, ex=ctx.ex, psum_axes=(), mean_axes=()
        )
    else:
        dp = sh.data_axes  # e.g. ("pod", "data")
        tp = sh.model_axes  # ("model",)
        down = _member_node(experts, "w_down")
        if isinstance(down, (LUTLinear, LUTGroup)) and not _down_chunks_shardable(
            down.plan, sh.axis_size(*tp) if tp else 0
        ):
            # chunk boundaries don't align with the shards: replicate the
            # expert tables (redundant compute) rather than serve wrong
            tp = ()
        # shard_map blocks must divide evenly; tiny decode batches (e.g.
        # long_500k's B=1) replicate over data and compute redundantly
        if (B * S) % max(sh.axis_size(*dp), 1) != 0:
            dp = ()
        tok_spec = P(dp, None) if dp else P(None, None)
        fn = functools.partial(
            _moe_local,
            cfg=cfg,
            ex=ctx.ex,
            psum_axes=tp,
            mean_axes=tuple(dp) + tuple(tp),
        )
        out, aux = jax.shard_map(
            fn,
            mesh=sh.mesh,
            in_specs=(tok_spec, _expert_specs(experts, tp)),
            out_specs=(tok_spec, P()),
            check_vma=False,
        )(xt, experts)
    out = out.reshape(B, S, d)
    if "shared" in p:
        gate = jax.nn.sigmoid(x.astype(jnp.float32) @ p["shared_gate"]).astype(x.dtype)
        out = out + gate * mlp(p["shared"], x, ctx)
    return sh.constrain(out, "batch", None, None), aux
