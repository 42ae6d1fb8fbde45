"""TL1 activation-side tables through ``lut_tl1_grouped``: ternary
weights, per-token ``act_bits`` activation codes."""
from __future__ import annotations

import math
import time

from bench.families import exec_cfg, routed_rows


def build(dense, cfg, c: dict, cache_bytes: int, hbm_bytes: float):
    from repro.core.convert import convert_params
    from repro.core.planner import plan_model

    conf = c["tl1"]
    t0 = time.perf_counter()
    mplan = plan_model(
        dense, math.inf, families=("tl1",), tl1_act_bits=conf["act_bits"],
        convert_experts=True,
    )
    params, report = convert_params(dense, plan=mplan, convert_experts=True)
    if report.converted != c["converted_linears"]:
        raise SystemExit(
            f"tl1 converted {report.converted} projections, "
            f"the configuration states {c['converted_linears']}"
        )
    return params, exec_cfg(), {
        "plan": mplan.summary(),
        "table_bytes": int(mplan.total_lut_bytes),
        "convert_s": time.perf_counter() - t0,
    }


def row_bytes(params, cfg) -> int:
    """int32 activation codes and scale in, float32 outputs out, once for
    each row a node computes (``routed_rows``)."""
    total = 0
    for n, rows in routed_rows(params, cfg):
        layers = n.tables.shape[0]
        members = len(getattr(n, "members", ("one",)))
        plan = n.plan
        total += rows * layers * (
            4 * plan.padded_in + 4 + 4 * members * plan.out_features
        )
    return total
