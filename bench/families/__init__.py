"""Program-side set-up of each execution family: how the dense weights
become what the engine serves, and the bytes its table kernels read.  A
configuration file names its family under ``family``; the module of that
name here provides ``build`` and ``row_bytes``."""
from __future__ import annotations


def table_nodes(params) -> list:
    """Every converted projection node (``LUTLinear`` / ``LUTGroup``)."""
    import jax

    from repro.core.convert import LUTGroup, LUTLinear

    kinds = (LUTLinear, LUTGroup)
    return [
        n for n in jax.tree.leaves(params, is_leaf=lambda n: isinstance(n, kinds))
        if isinstance(n, kinds)
    ]


def stored_bytes(params) -> int:
    """Bytes of the converted projections' leaves as they are stored."""
    import jax

    return sum(
        a.size * a.dtype.itemsize
        for n in table_nodes(params) for a in jax.tree.leaves(n)
    )


def exec_cfg():
    from repro.models.layers import ExecCfg

    return ExecCfg(remat="none", use_pallas=True, lut_grouped=True)
