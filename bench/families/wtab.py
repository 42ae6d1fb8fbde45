"""The paper's weight-side tables through ``lut_affine_grouped``.

The plan follows the repository's one-chip smoke run: 8-bit signed fixed
point codes with ``act_frac`` fractional bits, bitplane mode, tables of at
most ``MAX_SELECT_ENTRIES`` entries stored as ``table_format`` integers,
planned under the HBM left beside the dense parameters, the cache and
``HEADROOM``.  Set-up refuses a plan that differs from the configuration
file, routed experts' entries among them, or leaves a projection dense.
"""
from __future__ import annotations

import time

from bench.families import exec_cfg, routed_rows

HEADROOM = 4 * 2**30  # HBM kept free of tables: activations, temporaries


def _plan(dense, cfg, conf, cache_bytes, hbm_bytes):
    import jax

    from repro.core.planner import plan_model
    from repro.core.quantize import FixedPointFormat
    from repro.kernels.common import MAX_SELECT_ENTRIES

    param_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(dense))
    budget = hbm_bytes - param_bytes - cache_bytes - HEADROOM
    return plan_model(
        dense,
        budget,
        fmt=FixedPointFormat(conf["act_bits"], conf["act_frac"], signed=True),
        modes=(conf["mode"],),
        table_formats=(conf["table_format"],),
        max_entries=MAX_SELECT_ENTRIES,
        convert_experts=True,
    )


def build(dense, cfg, c: dict, cache_bytes: int, hbm_bytes: float):
    """(served params, ExecCfg, info) from the dense tree."""
    from repro.core.convert import convert_params

    conf = c["wtab"]
    t0 = time.perf_counter()
    mplan = _plan(dense, cfg, conf, cache_bytes, hbm_bytes)
    params, report = convert_params(dense, plan=mplan, convert_experts=True)
    for path, p in mplan.layers.items():
        got = (p.chunk_size, p.mode, p.table_format, p.fmt.total_bits, p.fmt.frac_bits)
        want = (conf["chunk"], conf["mode"], conf["table_format"],
                conf["act_bits"], conf["act_frac"])
        if got != want:
            raise SystemExit(f"wtab plan of {path} is {got}, the configuration {want}")
    if report.converted != c["converted_linears"]:
        raise SystemExit(
            f"wtab converted {report.converted} projections, "
            f"the configuration states {c['converted_linears']}"
        )
    return params, exec_cfg(), {
        "plan": mplan.summary(),
        "table_bytes": int(mplan.total_lut_bytes),
        "convert_s": time.perf_counter() - t0,
    }


def row_bytes(params, cfg) -> int:
    """Codes read and outputs written per token row, over every dispatch:
    int32 codes of each bitplane and chunk, float32 outputs, once for each
    row a node computes (``routed_rows``)."""
    total = 0
    for n, rows in routed_rows(params, cfg):
        layers = n.tables.shape[0]
        members = len(getattr(n, "members", ("one",)))
        plan = n.plan
        total += rows * layers * (
            4 * plan.num_planes * plan.num_chunks + 4 * members * plan.out_features
        )
    return total
