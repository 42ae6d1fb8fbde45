"""Program-side set-up of each execution family: how the dense weights
become what the engine serves, and the bytes its table kernels read.  A
configuration file names its family under ``family``; the module of that
name here provides ``build`` and ``row_bytes``.

Both families plan and convert every projection the program can serve
from tables, routed experts included: the planner and the converter run
with ``convert_experts=True``, which stacks a layer's experts into one
node, ``(layers, experts, ...)`` tables, with gate and up of every expert
in one group where their shapes agree.  Such a node sits beside the
layer's ``router`` (:func:`expert_nodes`).  What shares one scale there,
as ``repro.core.convert`` builds it, and so what a reference module has
to reproduce:

- ``wtab``: one power-of-two table scale per layer and node: every
  routed expert of the layer, and both members of a gate/up group, share
  it (a dense projection's table set is one matrix, or one group);
- ``tl1``: one ternary scale per layer, expert and member, as for a dense
  projection's matrix; activation codes are per row, as for a dense
  projection: per token into gate/up, per routed row into ``w_down``.
"""
from __future__ import annotations


def _kinds():
    from repro.core.convert import LUTGroup, LUTLinear

    return (LUTLinear, LUTGroup)


def table_nodes(params) -> list:
    """Every converted projection node (``LUTLinear`` / ``LUTGroup``)."""
    import jax

    kinds = _kinds()
    return [
        n for n in jax.tree.leaves(params, is_leaf=lambda n: isinstance(n, kinds))
        if isinstance(n, kinds)
    ]


def expert_nodes(params) -> list:
    """The converted routed-expert stacks: the table nodes beside a
    ``router``.  Shared experts sit one level down and are not among them."""
    if not isinstance(params, dict):
        return []
    here = [v for v in params.values() if isinstance(v, _kinds())]
    return (here if "router" in params else []) + [
        n for v in params.values() for n in expert_nodes(v)]


def routed_rows(params, cfg) -> list:
    """``(node, rows)`` for every table node: the rows it computes for one
    token row, ``cfg.num_experts_per_tok`` for a routed-expert stack, else 1."""
    experts = {id(n) for n in expert_nodes(params)}
    return [(n, cfg.num_experts_per_tok if id(n) in experts else 1)
            for n in table_nodes(params)]


def nbytes(nodes) -> int:
    """Bytes of the nodes' leaves as they are stored."""
    import jax

    return sum(a.size * a.dtype.itemsize for n in nodes for a in jax.tree.leaves(n))


def stored_bytes(params) -> int:
    """Bytes of the converted projections' leaves as they are stored."""
    return nbytes(table_nodes(params))


def exec_cfg():
    from repro.models.layers import ExecCfg

    return ExecCfg(remat="none", use_pallas=True, lut_grouped=True)
