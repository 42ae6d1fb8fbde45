"""Batched LUT-mode serving: the device-resident scheduler over a
TableNet-converted LM — per-layer planned conversion + grouped (fused
QKV / gate-up) decode, batched multi-slot admission and fused on-device
sampling.

  PYTHONPATH=src python examples/serve_lut.py [--arch granite_8b] \
      [--requests 6] [--temperature 0.8] [--top-k 40] [--admit per-slot]

Runs in <30s on CPU with the defaults.
"""
import argparse
import time

import jax

from repro.configs.base import get_config
from repro.core.convert import convert_params, conversion_summary
from repro.core.planner import plan_model
from repro.models.layers import Ctx, ExecCfg, SampleCfg
from repro.models.model import model_specs
from repro.models.params import init_params
from repro.serve import BatchingEngine, Request


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite_8b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--budget-frac", type=float, default=0.5,
                    help="LUT byte budget as a fraction of the uniform plan")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; > 0 samples on device")
    ap.add_argument("--top-k", type=int, default=0,
                    help="with --temperature: restrict draws to the top k")
    ap.add_argument("--seed", type=int, default=0, help="sampling PRNG seed")
    ap.add_argument("--admit", default="batched",
                    choices=("batched", "per-slot"),
                    help="admission schedule (token streams are identical)")
    args = ap.parse_args()

    cfg = get_config(args.arch, reduced=True)
    # grouped LUT decode: one fused dispatch per same-shape projection group
    ctx = Ctx(cfg, ex=ExecCfg(remat="none", lut_grouped=True))
    params = init_params(model_specs(cfg), jax.random.PRNGKey(0))

    uniform = plan_model(params, float("inf"), max_chunk=2)
    budget = int(uniform.total_lut_bytes * args.budget_frac)
    plan = plan_model(params, budget, max_chunk=2)
    print(f"serving {cfg.name} (reduced) in planned LUT mode")
    print("  " + plan.summary()
          + f" (budget {budget / 2**20:.0f} MiB of"
          f" {uniform.total_lut_bytes / 2**20:.0f} MiB uniform)")
    lut_params, report = convert_params(params, plan=plan)
    print("  " + conversion_summary(report))

    if args.temperature > 0:
        mode = "top_k" if args.top_k > 0 else "temperature"
        sample = SampleCfg(mode=mode, temperature=args.temperature,
                           top_k=args.top_k)
    else:
        sample = SampleCfg()
    print(f"  sampling: {sample.mode}, admission: {args.admit}")
    eng = BatchingEngine(lut_params, ctx, num_slots=args.slots, max_len=64,
                         sample=sample, seed=args.seed, admit=args.admit)
    key = jax.random.PRNGKey(1)
    reqs = []
    for i in range(args.requests):
        key, k = jax.random.split(key)
        plen = int(jax.random.randint(k, (), 3, 10))
        prompt = jax.random.randint(k, (plen,), 0, cfg.vocab_size)
        r = Request(uid=i, prompt=prompt, max_new=args.max_new)
        reqs.append(r)
        eng.submit(r)

    t0 = time.perf_counter()
    steps = 0
    while eng.step():
        steps += 1
    dt = time.perf_counter() - t0
    total = sum(len(r.generated) for r in reqs)
    dev = jax.devices()[0]
    print(f"{len(reqs)} requests on {args.slots} slots: {steps} decode steps, "
          f"{total} tokens in {dt:.1f}s ({total / dt:.1f} tok/s on "
          f"{dev.platform} {dev.device_kind}, jnp oracle; "
          f"{eng.readbacks} host readbacks)")
    for r in reqs:
        print(f"  req {r.uid}: prompt {list(map(int, r.prompt))} -> {r.generated}")


if __name__ == "__main__":
    main()
