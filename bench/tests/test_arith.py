"""The roofline and MFU arithmetic, against counts made by hand for
granite_8b at 4 layers: 3,489,660,928 bytes of weight tables (4 bytes per
weight: 16 int8 entries per 4-element chunk), 218,103,808 bytes of TL1
indices (a quarter byte per weight), and 1,073,741,824 multiply-adds per
token row (872,415,232 in the block projections, 201,326,592 in the tied
head)."""
import math

import jax
import jax.numpy as jnp
import pytest

from bench import families, spec
from bench.archs import llama
from bench.tests.test_trace import events_for_decode

TABLE_BYTES = {"granite_8b-wtab": 3_489_660_928, "granite_8b-tl1": 218_103_808}
V5E = {"bf16_flops": 197e12, "hbm_bw": 819e9, "hbm_bytes": 16e9}


def config(name):
    return spec.load_cell({"granite_8b-wtab": "wtab-batch",
                           "granite_8b-tl1": "tl1-batch"}[name]).config


def abstract_dense(c):
    L = c["num_hidden_layers"]
    tree = {}
    for path, (shape, _) in llama.weight_layout(c).items():
        shp = ((L,) + shape) if path.startswith("blocks/") else shape
        node = tree
        *head, last = path.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = jax.ShapeDtypeStruct(shp, jnp.bfloat16)
    return tree


def converted(name):
    """The converted tree's shapes, as the program's planner and converter
    make them, without materialising a byte."""
    from repro.core.convert import convert_params
    from repro.core.planner import plan_model

    c = config(name)
    dense = abstract_dense(c)
    if c["family"] == "wtab":
        from bench.families import wtab

        mcfg = llama.program_config(c)
        mplan = wtab._plan(dense, mcfg, c["wtab"], 0, V5E["hbm_bytes"])
    else:
        mplan = plan_model(dense, math.inf, families=("tl1",), tl1_act_bits=8)
    return mplan, jax.eval_shape(lambda d: convert_params(d, plan=mplan)[0], dense)


def test_linear_work_is_the_hand_count():
    for name in TABLE_BYTES:
        w = llama.linear_work(config(name))
        assert w["linears"] == 872_415_232
        assert w["head"] == 201_326_592
        assert w["linears"] + w["head"] == 1_073_741_824


@pytest.mark.parametrize("name", sorted(TABLE_BYTES))
def test_table_bytes_are_the_hand_count(name):
    mplan, tree = converted(name)
    nodes = families.table_nodes(tree)
    assert len(nodes) == 5  # wq, wk+wv, wo, w_gate+w_up, w_down
    tables = sum(n.tables.size * n.tables.dtype.itemsize for n in nodes)
    assert tables == mplan.total_lut_bytes == TABLE_BYTES[name]
    # the stored leaves add only the per-layer scales
    assert 0 < families.stored_bytes(tree) - tables < 1024


class FakeRun:
    def __init__(self, events, info, counters=None, slots=8, window_s=10.0):
        self.events, self.info, self.slots = events, info, slots
        self.peaks, self.window_s = V5E, window_s
        self.counters = counters or {}


def test_roofline_share_of_the_wtab_decode_step():
    read = spec.reader("metrics", "lut_roofline.decode")
    info = {"stored_bytes": TABLE_BYTES["granite_8b-wtab"], "row_bytes": 0,
            "linear_work": llama.linear_work(config("granite_8b-wtab"))}
    # 80 ms of table kernels in each of 3 decode steps
    ev = events_for_decode(steps=3, kernel_ms=80.0, other_ms=5.0)
    bound_s = TABLE_BYTES["granite_8b-wtab"] / 819e9  # bytes bound it
    assert bound_s > 2 * 8 * 872_415_232 / 197e12
    assert read(FakeRun(ev, info)) == pytest.approx(100 * bound_s / 0.080)
    assert read(FakeRun(ev, info)) == pytest.approx(5.326, abs=1e-3)


def test_roofline_bound_by_work_when_bytes_are_few():
    read = spec.reader("metrics", "lut_roofline.decode")
    info = {"stored_bytes": 1000, "row_bytes": 0,
            "linear_work": llama.linear_work(config("granite_8b-tl1"))}
    ev = events_for_decode(steps=2, kernel_ms=1.0, other_ms=1.0)
    flops = 2 * 64 * 872_415_232
    assert read(FakeRun(ev, info, slots=64)) == pytest.approx(
        100 * flops / 197e12 / 1e-3)


def test_mfu_counts_prefilled_and_served_rows():
    read = spec.reader("metrics", "mfu")
    info = {"linear_work": llama.linear_work(config("granite_8b-tl1"))}
    run = FakeRun(None, info, {"prefill_tokens": 1000, "output_tokens": 3000},
                  window_s=20.0)
    want = 100 * 2 * 1_073_741_824 * 4000 / 20.0 / 197e12
    assert read(run) == pytest.approx(want)
    assert read(run) == pytest.approx(0.2180, abs=1e-4)
