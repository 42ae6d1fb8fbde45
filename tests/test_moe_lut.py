"""Multiplier-free MoE expert execution.

Covers the ragged LUT expert path end to end: converted expert trees
(pre-stacked gate/up ``LUTGroup`` + ``w_down`` ``LUTLinear``) reproduce the
dense grouped-GEMM experts through ``moe_ffn``, through ``generate``, and
through the ``BatchingEngine`` (identical greedy token streams — the
acceptance bar), mixed dense/LUT trees execute coherently on every
projection combination, and the jitted decode step's program contains NO
``ragged_dot`` and no ``dot_general`` over expert-weight-sized operands
(multiplier-free, asserted at the jaxpr level like
``tests/test_grouped_layout.py`` does for attention).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.audit import multiplier_free_violations
from repro.configs.base import get_config
from repro.core.convert import LUTGroup, LUTLinear, convert_params
from repro.core.planner import plan_model
from repro.kernels.common import MAX_SELECT_ENTRIES
from repro.models.layers import Ctx, ExecCfg
from repro.models.model import model_forward, model_specs
from repro.models.moe import moe_ffn, moe_specs
from repro.models.params import init_params
from repro.serve import (
    BatchingEngine,
    Request,
    generate,
    make_cache,
    make_decode_step,
)

pytestmark = pytest.mark.slow  # expert conversion + decode compiles: ~60s


def _moe_setup(seed=3):
    cfg = get_config("qwen2_moe_a2_7b", reduced=True)
    ctx = Ctx(cfg, ex=ExecCfg(remat="none"))
    params = init_params(model_specs(cfg), jax.random.PRNGKey(seed))
    return cfg, ctx, params


def _ffn_setup(seed=0):
    cfg = get_config("qwen2_moe_a2_7b", reduced=True)
    p = init_params(moe_specs(cfg), jax.random.PRNGKey(seed))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 6, cfg.d_model)) * 0.5
    return cfg, p, x


def _rel_err(got, want):
    g, w = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return np.abs(g - w).max() / (np.abs(w).max() + 1e-6)


# ---------------------------------------------------------------------------
# moe_ffn level: dense == LUT experts (oracle and Pallas), all mixes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("chunk", [1, 2])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_moe_ffn_lut_experts_match_dense(chunk, use_pallas):
    cfg, p, x = _ffn_setup()
    ctx = Ctx(cfg, ex=ExecCfg(remat="none", use_pallas=use_pallas))
    want, aux_want = moe_ffn(p, x, Ctx(cfg, ex=ExecCfg(remat="none")))
    if use_pallas:
        # the kernels serve at most MAX_SELECT_ENTRIES-entry tables; the fp16
        # plans under that cap are bitplane_shift, here with ``chunk``
        # mantissa bits per plane
        mplan = plan_model(
            p,
            float("inf"),
            modes=("bitplane_shift",),
            radices=(chunk,),
            convert_experts=True,
            max_entries=MAX_SELECT_ENTRIES,
        )
        lut, rep = convert_params(p, plan=mplan, convert_experts=True)
    else:
        lut, rep = convert_params(p, chunk_size=chunk, convert_experts=True)
    assert isinstance(lut["w_gate+w_up"], LUTGroup)  # pre-stacked pair
    assert isinstance(lut["w_down"], LUTLinear)
    got, aux_got = moe_ffn(lut, x, ctx)
    # routing runs on the raw router weights: aux loss is identical and the
    # output differs only by the fp16 input quantisation of the experts
    # (+ the converted shared-expert branch)
    np.testing.assert_allclose(float(aux_got), float(aux_want), rtol=1e-6)
    assert _rel_err(got, want) < 0.02


def test_moe_ffn_mixed_dense_lut_members_execute_coherently():
    """The old detection probed only w_gate: a plan converting only w_down
    slipped a pytree node into ragged_dot.  Every projection combination
    must now execute, each member on its own path."""
    cfg, p, x = _ffn_setup(seed=5)
    ctx = Ctx(cfg, ex=ExecCfg(remat="none"))
    want, _ = moe_ffn(p, x, ctx)

    combos = [
        ("w_down",),  # the regression: down-only conversion
        ("w_gate", "w_up"),  # pre-stacked pair, dense down
        ("w_gate",),  # a lone gate: no group, dense up/down
        ("w_gate", "w_up", "w_down"),
    ]
    for members in combos:
        # expert-stack members only (the shared-expert MLP has 2-D w_down)
        def pred(path, node, m=members):
            return path[-1] in m and node["w"].ndim >= 3

        mp = plan_model(
            params=p,
            max_lut_bytes=float("inf"),
            max_chunk=1,
            predicate=pred,
            convert_experts=True,
        )
        lut, rep = convert_params(
            p, plan=mp, predicate=pred, convert_experts=True
        )
        assert rep.converted == len(members), members
        got, _ = moe_ffn(lut, x, ctx)
        assert _rel_err(got, want) < 0.02, members


def test_moe_ffn_group_only_gate_up_share_one_packing():
    """The pre-stacked pair's fused dispatch is bit-identical to executing
    the two members separately against their table slices."""
    cfg, p, x = _ffn_setup(seed=7)
    ctx = Ctx(cfg, ex=ExecCfg(remat="none"))
    lut, _ = convert_params(p, chunk_size=1, convert_experts=True)
    fused, _ = moe_ffn(lut, x, ctx)
    # split the stored group into two lone LUTLinear members
    group = lut["w_gate+w_up"]
    split = {k: v for k, v in lut.items() if k != "w_gate+w_up"}
    for g, name in enumerate(group.members):
        split[name] = LUTLinear(tables=group.tables[:, g], plan=group.plan)
    unfused, _ = moe_ffn(split, x, ctx)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(unfused))


# ---------------------------------------------------------------------------
# Engine-level acceptance: identical greedy streams, multiplier-free jaxpr
# ---------------------------------------------------------------------------

_PROMPTS = ((1, 2, 3, 4), (5, 6, 7), (9, 10, 11, 12, 13))


def _run_engine(params, ctx, max_new=4):
    eng = BatchingEngine(params, ctx, num_slots=2, max_len=32)
    reqs = [
        Request(uid=i, prompt=jnp.asarray(p, jnp.int32), max_new=max_new)
        for i, p in enumerate(_PROMPTS)
    ]
    for r in reqs:
        eng.submit(r)
    eng.run()
    return {r.uid: r.generated for r in reqs}


def test_engine_moe_lut_equals_dense_greedy():
    """A tiny qwen2-moe config served with convert_experts=True produces
    greedy token streams identical to dense experts."""
    cfg, ctx, params = _moe_setup()
    lut, rep = convert_params(params, chunk_size=1, convert_experts=True)
    assert rep.grouped > 0
    gctx = dataclasses.replace(
        ctx, ex=dataclasses.replace(ctx.ex, lut_grouped=True)
    )
    dense = _run_engine(params, ctx)
    lut_streams = _run_engine(lut, gctx)
    assert dense == lut_streams


def test_generate_moe_lut_matches_dense_greedy():
    cfg, ctx, params = _moe_setup(seed=11)
    lut, _ = convert_params(params, chunk_size=1, convert_experts=True)
    tokens = jnp.asarray([[1, 2, 3, 4, 5, 6]], jnp.int32)
    want = generate(params, ctx, tokens, max_new=4, max_len=32)
    got = generate(lut, ctx, tokens, max_new=4, max_len=32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_moe_decode_step_jaxpr_is_multiplier_free():
    """The acceptance bar: the jitted decode step over a converted-experts
    tree lowers to a program with NO ragged_dot anywhere and no dot_general
    touching an operand as large as even one expert-stack weight (the
    router / shared-gate / attention-score contractions are small and
    allowed; all projections execute as LUT gathers)."""
    cfg, _, params = _moe_setup()
    lut, rep = convert_params(params, chunk_size=1, convert_experts=True)
    ctx = Ctx(cfg, ex=ExecCfg(remat="none", lut_grouped=True))
    decode = make_decode_step(ctx)
    cache = make_cache(cfg, 1, 16, ctx)
    jaxpr = jax.make_jaxpr(decode)(lut, cache, jnp.zeros((1, 1), jnp.int32))

    E, d, f = cfg.num_experts, cfg.d_model, cfg.moe_d_ff
    min_expert_w = E * d * f  # elements of one (E, d, f) expert projection
    offenders = multiplier_free_violations(
        jaxpr, min_operand_elems=min_expert_w
    )
    assert not offenders, (
        f"decode_step still multiplies over expert weights: {offenders} "
        f"(threshold {min_expert_w} elems)"
    )
