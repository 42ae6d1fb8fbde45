"""Both families convert a mixture-of-experts tree whole, routed experts
included, and the engine serves the weight-table tree: the program's
Qwen1.5-MoE layout at toy widths, on the CPU."""
import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import families, spec
from bench.tests import small

EXPERT_KEYS = ("w_gate", "w_up", "w_down")
# 4 attention projections, 3 of the shared experts, the untied head and the
# 3 routed expert stacks
CONVERTED = 11


def _moe():
    from repro.configs.base import get_config
    from repro.models.model import model_specs
    from repro.models.params import init_params

    cfg = get_config("qwen2_moe_a2_7b", reduced=True)
    dense = init_params(model_specs(cfg), jax.random.PRNGKey(3))
    return cfg, jax.tree.map(lambda a: a.astype(jnp.bfloat16), dense)


def _standin(family: str) -> dict:
    """The family's plan settings, as granite's configuration file states
    them, with the converted count of the MoE tree."""
    c = spec.load_cell({"wtab": "wtab-batch", "tl1": "tl1-batch"}[family]).config
    return {"family": family, family: c[family], "converted_linears": CONVERTED}


@pytest.fixture(scope="module", params=["wtab", "tl1"])
def built(request):
    cfg, dense = _moe()
    c = _standin(request.param)
    fam = importlib.import_module(f"bench.families.{request.param}")
    params, ex, info = fam.build(dense, cfg, c, 0, small.PEAKS["hbm_bytes"])
    return request.param, cfg, params, ex, info


def _node_of(node: dict, key: str):
    """The node that holds projection ``key``: its own or its group's."""
    return next(v for k, v in node.items() if key in k.split("+"))


def test_every_routed_projection_is_converted(built):
    from repro.core.convert import LUTGroup, LUTLinear

    family, cfg, params, _, _ = built
    ffn = params["blocks"]["ffn"]
    assert "router" in ffn
    for key in EXPERT_KEYS:
        node = _node_of(ffn, key)
        assert isinstance(node, (LUTGroup, LUTLinear)), (family, key, type(node))
        assert node.tables.shape[:2] == (cfg.num_layers, cfg.num_experts)
    experts = families.expert_nodes(params)
    covered = {m for key, v in ffn.items() if any(v is n for n in experts)
               for m in key.split("+")}
    assert covered == set(EXPERT_KEYS)
    shared = families.table_nodes(ffn["shared"])
    assert len(shared) >= 2 and not any(s is n for s in shared for n in experts)
    assert 0 < families.nbytes(experts) < families.stored_bytes(params)


def test_row_bytes_count_the_routed_rows(built):
    family, cfg, params, _, _ = built
    fam = importlib.import_module(f"bench.families.{family}")
    k = cfg.num_experts_per_tok
    top1 = dataclasses.replace(cfg, num_experts_per_tok=1)
    one = fam.row_bytes(params, top1)
    experts = {"router": None, **{
        key: n for key, n in params["blocks"]["ffn"].items()
        if any(n is e for e in families.expert_nodes(params))}}
    per_routed_row = fam.row_bytes(experts, top1)
    assert per_routed_row > 0
    assert fam.row_bytes(params, cfg) == one + (k - 1) * per_routed_row
    if family == "wtab":
        # gate+up: 2 layers x (8 planes x 16 chunks + 2 members x 32 outputs) x 4 B;
        # w_down: 2 layers x (8 planes x 8 chunks + 64 outputs) x 4 B
        assert per_routed_row == 2 * 4 * (8 * 16 + 2 * 32) + 2 * 4 * (8 * 8 + 64)


def test_engine_serves_the_weight_table_tree_to_completion():
    from repro.models.layers import Ctx
    from repro.serve import BatchingEngine, Request

    from bench.families import wtab

    cfg, dense = _moe()
    params, ex, _ = wtab.build(dense, cfg, _standin("wtab"), 0,
                               small.PEAKS["hbm_bytes"])
    engine = BatchingEngine(params, Ctx(cfg, ex=ex), num_slots=2, max_len=32)
    req = Request(uid=0, prompt=np.arange(5, 11, dtype=np.int32), max_new=4)
    engine.submit(req)
    steps = 0
    while engine.step():
        steps += 1
        assert steps < 50
    assert req.done and len(req.generated) == 4
    assert all(0 <= t < cfg.vocab_size for t in req.generated)
