# ruff: noqa: E402 -- BackendConfig.apply() must run before any jax import
import os

from repro.launch.backend import BackendConfig

BackendConfig(platform="cpu", host_device_count=512).apply()
# ^^ MUST precede any jax-importing module: jax locks the device count at
# first init.  Only the dry-run sees 512 placeholder devices; pinning the
# CPU platform keeps it (and every --all child, which inherits the
# environment) off any TPU on the host.

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell against the production meshes, record memory/cost/collective
analysis for the roofline.

  PYTHONPATH=src python -m repro.launch.dryrun --all [--resume]
  PYTHONPATH=src python -m repro.launch.dryrun \\
      --arch granite_8b --shape train_4k --mesh single

``--all`` drives one subprocess per cell (isolation: a pathological cell
cannot poison the rest) and appends records to results/dryrun.json.
"""
import argparse
import dataclasses
import json
import subprocess
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ARCH_NAMES, get_config
from repro.dist.sharding import ShardCtx
from repro.launch import hlo_analysis as H
from repro.launch.inputs import (
    SHAPES,
    cell_is_runnable,
    decode_input_specs,
    prefill_input_specs,
    shape_case,
    train_input_specs,
)
from repro.launch.mesh import make_production_mesh
from repro.models.layers import Ctx, ExecCfg
from repro.models.model import model_specs
from repro.models.params import abstract_params
from repro.serve import abstract_cache, make_decode_step, make_prefill_step
from repro.train.trainer import TrainConfig, make_train_step

RESULTS = os.path.join(os.path.dirname(__file__), "..", "..", "..", "results")


def _sharded_inputs(specs: dict, ctx: Ctx):
    out = {}
    for k, s in specs.items():
        axes = ("batch",) + (None,) * (len(s.shape) - 1)
        out[k] = jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=ctx.shard.sharding(axes, s.shape)
        )
    return out


def _abstract_state(cfg, ctx: Ctx, dtype):
    params = abstract_params(
        model_specs(cfg), default_dtype=dtype, sharding_fn=ctx.shard.param_sharding
    )
    return params


def _abstract_opt(params):
    mom = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32, sharding=s.sharding),
        params,
    )
    return {
        "m": mom,
        "v": jax.tree.map(lambda s: s, mom),
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }


def abstract_lut_params(cfg, ctx: Ctx, chunk_size: int = 1,
                        fsdp_tables: bool = False):
    """Shape/sharding stand-ins for a TableNet-converted parameter tree:
    eval_shape through the conversion pass, placed by
    ``dist.sharding.converted_shardings`` (tables sharded on their output
    dim like the weights they replace)."""
    from repro.core.convert import convert_params
    from repro.dist.sharding import converted_shardings

    std = _abstract_state(cfg, ctx, jnp.bfloat16)
    shapes = jax.eval_shape(
        lambda p: convert_params(p, chunk_size=chunk_size, table_dtype=jnp.bfloat16)[0],
        std,
    )
    shardings = converted_shardings(shapes, std, ctx.shard, fsdp_tables=fsdp_tables)
    return jax.tree.map(
        lambda leaf, s: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=s),
        shapes,
        shardings,
    )


def lower_cell(
    arch: str,
    shape: str,
    mesh_kind: str,
    exec_overrides: dict | None = None,
    cfg_overrides: dict | None = None,
    case_overrides: dict | None = None,
    rules: str = "default",
    params_mode: str = "standard",
):
    """Returns (lowered, compiled, ctx, case, cfg)."""
    from repro.dist.sharding import RULE_SETS

    cfg = get_config(arch)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    case = shape_case(shape)
    if case_overrides:
        case = dataclasses.replace(case, **case_overrides)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    ex_kw = dict(remat="full" if case.kind == "train" else "none")
    ex_kw.update(exec_overrides or {})
    microbatches = ex_kw.pop("microbatches", 1)  # TrainConfig knob, not ExecCfg
    lut_fsdp = ex_kw.pop("lut_fsdp", False)
    ctx = Ctx(cfg, shard=ShardCtx(mesh, RULE_SETS[rules]), ex=ExecCfg(**ex_kw))

    if case.kind == "train":
        params = _abstract_state(cfg, ctx, jnp.float32)
        opt = _abstract_opt(params)
        batch = _sharded_inputs(train_input_specs(cfg, case), ctx)
        tc = TrainConfig(microbatches=microbatches)
        step = make_train_step(ctx, tc)
        lowered = jax.jit(step, donate_argnums=(0, 1)).lower(params, opt, batch)
    elif case.kind == "prefill":
        params = (abstract_lut_params(cfg, ctx, fsdp_tables=lut_fsdp)
                  if params_mode == "lut"
                  else _abstract_state(cfg, ctx, jnp.bfloat16))
        cache = abstract_cache(cfg, case.global_batch, case.seq_len, ctx)
        inputs = _sharded_inputs(prefill_input_specs(cfg, case), ctx)
        ctx = dataclasses.replace(ctx, ex=dataclasses.replace(ctx.ex, logits="last"))
        step = make_prefill_step(ctx)
        lowered = jax.jit(step, donate_argnums=(2,)).lower(params, inputs, cache)
    else:  # decode
        params = (abstract_lut_params(cfg, ctx, fsdp_tables=lut_fsdp)
                  if params_mode == "lut"
                  else _abstract_state(cfg, ctx, jnp.bfloat16))
        cache = abstract_cache(cfg, case.global_batch, case.seq_len, ctx)
        tokens = _sharded_inputs(decode_input_specs(cfg, case), ctx)["tokens"]
        step = make_decode_step(ctx)
        lowered = jax.jit(step, donate_argnums=(1,)).lower(params, cache, tokens)

    compiled = lowered.compile()
    return lowered, compiled, ctx, case, cfg



def _raw_costs(compiled) -> "np.ndarray":
    """[flops, hbm_bytes, link_bytes] of one compiled per-device module."""
    ca = compiled.cost_analysis() or {}
    flops = float(ca.get("flops", 0.0))
    hbm = float(ca.get("bytes accessed", 0.0))
    if not hbm:
        ma = compiled.memory_analysis()
        hbm = sum(
            getattr(ma, k, 0)
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes")
        )
    link = H.collective_stats(compiled.as_text()).link_bytes
    return np.array([flops, hbm, link], dtype=np.float64)


def _probe(arch, shape, mesh_kind, exec_overrides, cfg_ov, case_ov=None,
           rules="default", params_mode="standard"):
    ex = dict(exec_overrides or {})
    ex["inner_unroll"] = True  # chunk-scan bodies must appear nc times
    _, compiled, _, _, _ = lower_cell(
        arch, shape, mesh_kind, ex, cfg_overrides=cfg_ov, case_overrides=case_ov,
        rules=rules, params_mode=params_mode,
    )
    return _raw_costs(compiled)


def corrected_costs(arch, shape, mesh_kind, exec_overrides=None,
                    rules="default", params_mode="standard"):
    """XLA cost analysis counts lax.scan bodies ONCE — reconstruct true
    totals by depth-differencing probe compiles (DESIGN.md §6)."""
    cfg = get_config(arch)
    L = cfg.num_layers
    probes = {}

    def P(name, cfg_ov, case_ov=None):
        probes[name] = _probe(arch, shape, mesh_kind, exec_overrides, cfg_ov,
                              case_ov, rules=rules, params_mode=params_mode)
        return probes[name]

    if cfg.family == "encdec":
        f11 = P("e1d1", {"encoder_layers": 1, "num_layers": 1})
        f21 = P("e2d1", {"encoder_layers": 2, "num_layers": 1})
        f12 = P("e1d2", {"encoder_layers": 1, "num_layers": 2})
        total = f11 + (cfg.encoder_layers - 1) * (f21 - f11) + (L - 1) * (f12 - f11)
    elif cfg.family == "hybrid":
        from repro.models.hybrid import segments

        f1, f2 = P("d1", {"num_layers": 1}), P("d2", {"num_layers": 2})
        g = cfg.shared_attn_every
        f6, f7 = P("d6", {"num_layers": g}), P("d7", {"num_layers": g + 1})
        mamba = f2 - f1
        shared = f7 - f6 - mamba
        n_shared = len(segments(cfg)) - 1
        total = f1 + (L - 1) * mamba + n_shared * shared
    elif cfg.family == "ssm" and shape_case(shape).kind != "decode":
        # rwkv: the heavy intra-chunk math lives INSIDE a chunk scan; the
        # depth probes unroll it (inner_unroll) — exact but compile-heavy,
        # so long sequences probe at S=4096 and scale (every rwkv cost is
        # linear in S at fixed chunk size; same chunk picked for both)
        case = shape_case(shape)
        S = case.seq_len
        case_ov = {"seq_len": 4096} if S > 4096 else None
        scale = S / 4096 if S > 4096 else 1.0
        f1 = P("d1", {"num_layers": 1}, case_ov)
        f2 = P("d2", {"num_layers": 2}, case_ov)
        total = (f1 + (L - 1) * (f2 - f1)) * scale
    else:
        f1, f2 = P("d1", {"num_layers": 1}), P("d2", {"num_layers": 2})
        total = f1 + (L - 1) * (f2 - f1)
    total = np.maximum(total, 0.0)
    return total, {k: v.tolist() for k, v in probes.items()}


def analyse(compiled, cfg, case, mesh_kind: str, corrected=None, probes=None) -> dict:
    chips = 512 if mesh_kind == "multi" else 256
    raw = _raw_costs(compiled)
    flops, hbm_bytes, link_bytes = (corrected if corrected is not None else raw)
    ma = compiled.memory_analysis()
    mem = {}
    if ma is not None:
        mem = {
            "argument_mib": getattr(ma, "argument_size_in_bytes", 0) / 2**20,
            "output_mib": getattr(ma, "output_size_in_bytes", 0) / 2**20,
            "temp_mib": getattr(ma, "temp_size_in_bytes", 0) / 2**20,
            "alias_mib": getattr(ma, "alias_size_in_bytes", 0) / 2**20,
        }
    coll = H.collective_stats(compiled.as_text())
    # the fake CPU devices stand in for v5e chips: roofline at v5e peaks
    terms = H.roofline_terms(flops, hbm_bytes, link_bytes, device_kind=H.V5E)
    mflops = H.model_flops(cfg, case)
    useful = mflops / chips / flops if flops else 0.0
    return {
        "chips": chips,
        "flops_per_device": float(flops),
        "hbm_bytes_per_device": float(hbm_bytes),
        "link_bytes_per_device": float(link_bytes),
        "raw_uncorrected": raw.tolist(),
        "probes": probes or {},
        "collectives": coll.to_dict(),
        "memory": mem,
        "terms": terms,
        "model_flops_total": mflops,
        "useful_flops_ratio": useful,
    }


def run_cell(arch: str, shape: str, mesh_kind: str, exec_overrides=None,
             rules: str = "default", params_mode: str = "standard",
             tag: str = "") -> dict:
    cfg = get_config(arch)
    case = shape_case(shape)
    ok, reason = cell_is_runnable(cfg, case)
    rec = {"arch": arch, "shape": shape, "mesh": mesh_kind, "kind": case.kind}
    if tag:
        rec["tag"] = tag
    if rules != "default":
        rec["rules"] = rules
    if params_mode != "standard":
        rec["params_mode"] = params_mode
    if not ok:
        return dict(rec, status="skipped", reason=reason)
    t0 = time.time()
    try:
        lowered, compiled, ctx, case, cfg = lower_cell(
            arch, shape, mesh_kind, exec_overrides, rules=rules,
            params_mode=params_mode,
        )
        corrected, probes = corrected_costs(
            arch, shape, mesh_kind, exec_overrides, rules=rules,
            params_mode=params_mode,
        )
    except Exception as e:
        return dict(
            rec, status="failed", error=f"{type(e).__name__}: {e}",
            trace=traceback.format_exc()[-2000:],
        )
    rec.update(analyse(compiled, cfg, case, mesh_kind, corrected, probes))
    rec["compile_s"] = round(time.time() - t0, 1)
    rec["status"] = "ok"
    return rec


def _load(path: str) -> list:
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    return []


def _driver(args):
    """Spawn one subprocess per cell; append results incrementally."""
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    done = {
        (r["arch"], r["shape"], r["mesh"])
        for r in _load(args.out)
        if r.get("status") in ("ok", "skipped")
    }
    meshes = args.meshes.split(",")
    cells = [
        (a, s.name, m)
        for a in (args.archs.split(",") if args.archs else ARCH_NAMES)
        for s in SHAPES
        for m in meshes
    ]
    for arch, shape, mesh_kind in cells:
        if args.resume and (arch, shape, mesh_kind) in done:
            print(f"[skip-done] {arch} {shape} {mesh_kind}")
            continue
        print(f"[cell] {arch} {shape} {mesh_kind} ...", flush=True)
        cmd = [
            sys.executable, "-m", "repro.launch.dryrun",
            "--arch", arch, "--shape", shape, "--mesh", mesh_kind,
            "--out", args.out, "--append",
        ]
        env = dict(os.environ, PYTHONPATH=os.environ.get("PYTHONPATH", "src"))
        r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                           timeout=args.timeout)
        if r.returncode != 0:
            results = _load(args.out)
            results.append({
                "arch": arch, "shape": shape, "mesh": mesh_kind,
                "status": "crashed", "error": (r.stderr or "")[-2000:],
            })
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)
            err = (r.stderr or "").strip().splitlines()[-1] if r.stderr else "?"
            print(f"  CRASHED: {err}")
        else:
            print("  " + (r.stdout.strip().splitlines()[-1] if r.stdout else "ok"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--archs", help="comma list for --all")
    ap.add_argument("--meshes", default="single,multi")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--append", action="store_true")
    ap.add_argument("--timeout", type=int, default=3000)
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--exec", default=None,
                    help='JSON ExecCfg overrides, e.g. {"remat":"dots"}')
    ap.add_argument("--rules", default="default", choices=["default", "no_fsdp"])
    ap.add_argument("--params", default="standard", choices=["standard", "lut"])
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    if args.all:
        _driver(args)
        return

    overrides = json.loads(args.exec) if args.exec else None
    rec = run_cell(args.arch, args.shape, args.mesh, overrides,
                   rules=args.rules, params_mode=args.params, tag=args.tag)
    if args.append:
        results = [
            r for r in _load(args.out)
            if not (r["arch"] == rec["arch"] and r["shape"] == rec["shape"]
                    and r["mesh"] == rec["mesh"]
                    and r.get("tag", "") == rec.get("tag", ""))
        ]
        results.append(rec)
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    if rec["status"] == "ok":
        t = rec["terms"]
        print(
            f"{rec['arch']} {rec['shape']} {rec['mesh']}: OK "
            f"compute={t['compute_s']:.4f}s memory={t['memory_s']:.4f}s "
            f"coll={t['collective_s']:.4f}s dom={t['dominant']} "
            f"frac={t['roofline_fraction']:.3f} compile={rec['compile_s']}s"
        )
    else:
        print(f"{rec['arch']} {rec['shape']} {rec['mesh']}: {rec['status']} "
              f"{rec.get('reason', rec.get('error', ''))}")
        if rec["status"] == "failed":
            print(rec.get("trace", ""), file=sys.stderr)
            sys.exit(1)


if __name__ == "__main__":
    main()
