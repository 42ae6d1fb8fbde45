"""Converting experts and stacking layers by kind change nothing for the
granite cells: the same plan, tables and bytes as planning and converting
without experts, and the same metrics; a new cell gets the whole-step
metrics, and not the two that read every table held as read each step."""
import importlib
import json
import math
import os
import shutil

import jax
import numpy as np
import pytest

from bench import families, spec, weights
from bench.tests import small

METRICS = {
    "wtab-batch": (["output_tok_s", "itl_p95_ms", "setup_s"],
                   ["decode_step_ms", "decode_rest_ms", "lut_roofline.decode",
                    "device_idle", "mfu"]),
    "tl1-batch": (["output_tok_s", "setup_s"],
                  ["decode_step_ms", "decode_rest_ms", "lut_roofline.decode",
                   "device_idle", "mfu"]),
}


def _without_experts(dense, c):
    """The plan and tree as ``plan_model`` / ``convert_params`` give them
    without ``convert_experts``."""
    from repro.core.convert import convert_params
    from repro.core.planner import plan_model
    from repro.core.quantize import FixedPointFormat
    from repro.kernels.common import MAX_SELECT_ENTRIES

    from bench.families.wtab import HEADROOM

    if c["family"] == "wtab":
        conf = c["wtab"]
        param_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(dense))
        mplan = plan_model(
            dense, small.PEAKS["hbm_bytes"] - param_bytes - HEADROOM,
            fmt=FixedPointFormat(conf["act_bits"], conf["act_frac"], signed=True),
            modes=(conf["mode"],), table_formats=(conf["table_format"],),
            max_entries=MAX_SELECT_ENTRIES)
    else:
        mplan = plan_model(dense, math.inf, families=("tl1",),
                           tl1_act_bits=c["tl1"]["act_bits"])
    params, report = convert_params(dense, plan=mplan)
    return mplan, params, report


@pytest.mark.parametrize("name", sorted(METRICS))
def test_plan_tables_and_bytes_are_those_without_experts(name):
    c = small.cell(name).config
    arch = spec.arch(c)
    mcfg = arch.program_config(c)
    dense = weights.make(arch.weight_layout(c), spec.stacks(c), 2**35 + 9)
    fam = importlib.import_module(f"bench.families.{c['family']}")
    params, _, info = fam.build(dense, mcfg, c, 0, small.PEAKS["hbm_bytes"])
    mplan, want, report = _without_experts(dense, c)
    assert info["plan"] == mplan.summary()
    assert info["table_bytes"] == int(mplan.total_lut_bytes)
    assert report.converted == c["converted_linears"]
    assert jax.tree.structure(params) == jax.tree.structure(want)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), params, want)
    assert families.expert_nodes(params) == []
    # every node computes one row per token row, as before experts counted
    assert fam.row_bytes(params, mcfg) == sum(
        fam.row_bytes({"k": n}, mcfg) for n in families.table_nodes(params))
    assert families.stored_bytes(params) == families.nbytes(families.table_nodes(want))


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_granite_cells_keep_their_metrics(name):
    cell = spec.load_cell(name)
    e2e, per_layer = METRICS[name]
    assert [m["name"] for m in cell.end_to_end] == e2e
    assert [m["name"] for m in cell.per_layer] == per_layer


def test_a_new_cell_gets_the_whole_step_metrics(tmp_path):
    root = tmp_path / "root"
    for rel in ("bench/configs/granite_8b-tl1.json", "bench/traffic/batch.json"):
        os.makedirs(root / os.path.dirname(rel), exist_ok=True)
        shutil.copy(os.path.join(spec.ROOT, rel), root / rel)
    bm = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    bm["workloads"].append({"name": "toy-batch", "config": "granite_8b-tl1",
                            "traffic": "batch", "chips": 1, "why": "a toy"})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    cell = spec.load_cell("toy-batch", root=str(root))
    assert [m["name"] for m in cell.per_layer] == [
        "decode_step_ms", "device_idle", "mfu"]
    assert [m["name"] for m in cell.end_to_end] == ["output_tok_s", "setup_s"]
