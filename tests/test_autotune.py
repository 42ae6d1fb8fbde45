"""Autotuner contract: candidate legality, analytic determinism, ModelPlan
attachment, and the baseline write/check drift cycle CI runs."""
import dataclasses
import json

import jax
import pytest

from repro.configs.base import get_config
from repro.core.planner import ModelPlan, plan_model
from repro.kernels.common import MAX_SELECT_ENTRIES
from repro.kernels.lut_affine import autotune
from repro.kernels.lut_affine.autotune import (
    TunePoint,
    analytic_cost,
    attach_tuned_blocks,
    candidate_blocks,
    check_baseline,
    points_from_model_plan,
    search_blocks,
    write_baseline,
)
from repro.models.model import model_specs
from repro.models.params import init_params


@pytest.fixture(scope="module")
def mplan():
    cfg = get_config("granite_8b", reduced=True)
    params = init_params(model_specs(cfg), jax.random.PRNGKey(0))
    uniform = plan_model(params, float("inf"), max_chunk=2)
    return plan_model(
        params,
        uniform.total_lut_bytes // 2,
        max_chunk=2,
        modes=("bitplane", "bitplane_shift"),
        radices=(1, 2, 4),
        table_formats=(None, "i8"),
        max_entries=MAX_SELECT_ENTRIES,
    )


# ---------------------------------------------------------------------------
# candidates + cost model
# ---------------------------------------------------------------------------


def test_candidates_are_legal():
    pt = TunePoint(B=4, k=96, entries=32, p=300, n=3, G=2, table_bytes=1)
    cands = candidate_blocks(pt)
    assert cands
    for bb, bp, bk in cands:
        assert bb % 8 == 0
        assert bp % 128 == 0
        # the chunk tile is the codes' lane axis: the whole axis when short
        assert bk == pt.k
        # double-buffered blocks respect the kernel's VMEM budget
        assert autotune.vmem_bytes(pt, (bb, bp, bk)) <= autotune.VMEM_BUDGET
    long = dataclasses.replace(pt, k=1000)
    assert {bk for _, _, bk in candidate_blocks(long)} <= {128, 256, 512, 1024}


def test_candidates_exclude_vmem_busting_tiles():
    # 65536-entry fp32 tables: one (bp=128, bk=1) tile alone is 32 MiB
    pt = TunePoint(B=8, k=4, entries=65536, p=128, n=11, table_bytes=4)
    assert candidate_blocks(pt) == []
    assert search_blocks(pt) is None  # defer to the runtime heuristic


def test_search_is_deterministic_pure_function_of_point():
    pt = TunePoint(B=2, k=64, entries=32, p=64, n=3, table_bytes=1)
    winners = {search_blocks(pt, mode="analytic") for _ in range(5)}
    assert len(winners) == 1
    blk = winners.pop()
    assert blk in candidate_blocks(pt)
    # the winner really is the argmin of the analytic cost
    best = min(analytic_cost(pt, c) for c in candidate_blocks(pt))
    assert analytic_cost(pt, blk) == best


def test_unknown_mode_raises():
    pt = TunePoint(B=2, k=4, entries=8, p=16, n=2)
    with pytest.raises(ValueError, match="unknown autotune mode"):
        search_blocks(pt, mode="wallclock")


def test_point_json_round_trip():
    pt = TunePoint(B=2, k=64, entries=32, p=64, n=3, G=2, table_bytes=1)
    assert TunePoint.from_json(pt.to_json()) == pt


# ---------------------------------------------------------------------------
# ModelPlan attachment
# ---------------------------------------------------------------------------


def test_attach_tuned_blocks_sets_every_layer(mplan):
    tuned = attach_tuned_blocks(mplan, batch=2)
    assert set(tuned.layers) == set(mplan.layers)
    for key, plan in tuned.layers.items():
        assert plan.blocks is not None, key
        assert dataclasses.replace(plan, blocks=None) == dataclasses.replace(
            mplan.layers[key], blocks=None
        )
    # grouped members get identical plans after tuning, so groups still fuse
    for group in tuned.groups:
        plans = {tuned.layers[k] for k in group}
        assert len(plans) == 1


def test_tuned_plan_json_round_trip(mplan):
    tuned = attach_tuned_blocks(mplan, batch=2)
    back = ModelPlan.from_json(tuned.to_json())
    assert dict(back.layers) == dict(tuned.layers)
    key = next(iter(back.layers))
    assert isinstance(back.layers[key].blocks, tuple)


# ---------------------------------------------------------------------------
# baseline write / drift check (the CI cycle)
# ---------------------------------------------------------------------------


def test_write_then_check_baseline_round_trip(mplan, tmp_path):
    points = points_from_model_plan(mplan, batch=2)
    assert points  # dedup keeps at least one shape point
    path = str(tmp_path / "autotune.json")
    payload = write_baseline(path, points)
    assert payload["mode"] == "analytic"
    assert check_baseline(path) == []


def test_check_baseline_flags_drift(mplan, tmp_path):
    points = points_from_model_plan(mplan, batch=2)
    path = str(tmp_path / "autotune.json")
    write_baseline(path, points)
    with open(path) as f:
        payload = json.load(f)
    payload["points"][0]["blocks"] = [999, 999, 999]
    with open(path, "w") as f:
        json.dump(payload, f)
    errs = check_baseline(path)
    assert len(errs) == 1
    assert "999" in errs[0]


def test_cli_write_and_check(mplan, tmp_path):
    plan_path = str(tmp_path / "plan.json")
    with open(plan_path, "w") as f:
        json.dump(mplan.to_json(), f)
    base = str(tmp_path / "autotune.json")
    assert (
        autotune.main(
            ["write", "--baseline", base, "--plan", plan_path, "--batch", "2"]
        )
        == 0
    )
    assert autotune.main(["check", "--baseline", base]) == 0


@pytest.mark.slow  # converts + compiles decode twice: ~1 min on CPU
def test_tuned_plan_rides_checkpoint_and_streams_match_untuned(mplan, tmp_path):
    """plan -> checkpoint aux -> restore -> serve: the tuned plan survives
    byte-for-byte, and because blocks only retile the kernel, greedy token
    streams are identical to the same plan without blocks."""
    import numpy as np

    from repro.core.convert import convert_params
    from repro.dist.checkpoint import load_aux, save_checkpoint
    from repro.models.layers import Ctx, ExecCfg
    from repro.serve import generate

    cfg = get_config("granite_8b", reduced=True)
    params = init_params(model_specs(cfg), jax.random.PRNGKey(0))
    tuned = attach_tuned_blocks(mplan, batch=1)

    ckpt = str(tmp_path / "ckpt")
    save_checkpoint(ckpt, 1, params, aux={"model_plan": tuned.to_json()})
    restored = ModelPlan.from_json(load_aux(ckpt, 1)["model_plan"])
    assert dict(restored.layers) == dict(tuned.layers)

    untuned = dataclasses.replace(
        tuned,
        layers={
            k: dataclasses.replace(p, blocks=None) for k, p in tuned.layers.items()
        },
    )
    ex = ExecCfg(remat="none", use_pallas=True, lut_grouped=True)
    ctx = Ctx(cfg, ex=ex)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 6), 0, cfg.vocab_size)
    lut_t, rep_t = convert_params(params, plan=restored)
    lut_u, rep_u = convert_params(params, plan=untuned)
    assert rep_t.converted == rep_u.converted > 0
    got = generate(lut_t, ctx, tokens, max_new=3)
    want = generate(lut_u, ctx, tokens, max_new=3)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_committed_baseline_has_no_drift():
    """The baseline in the repo must match a fresh re-search (the CI step)."""
    import pathlib

    repo = pathlib.Path(__file__).resolve().parents[1]
    baseline = repo / "benchmarks" / "baselines" / "autotune.json"
    assert check_baseline(str(baseline)) == []
