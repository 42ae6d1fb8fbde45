"""Hillclimb profiler: per-layer cost breakdown of one cell via depth probes.

  PYTHONPATH=src python tools/perf_probe.py granite_8b train_4k single \
      [--rules no_fsdp] [--exec '{"remat":"dots"}'] [--params lut]
Prints the per-LAYER collective ops (d2 - d1 diff), and per-layer
flops/bytes — the "profile" the optimization loop reads.
"""
# ruff: noqa: E402 -- the backend is configured before any jax import
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.launch.backend import BackendConfig

# 512 fake CPU devices, pinned to the CPU platform so the probe never opens
# a TPU on the host
BackendConfig(platform="cpu", host_device_count=512).apply()

from repro.launch import hlo_analysis as H
from repro.launch.dryrun import _raw_costs, lower_cell


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("arch")
    ap.add_argument("shape")
    ap.add_argument("mesh", nargs="?", default="single")
    ap.add_argument("--rules", default="default")
    ap.add_argument("--params", default="standard")
    ap.add_argument("--exec", default=None)
    ap.add_argument("--depths", default="1,2")
    args = ap.parse_args()
    ex = json.loads(args.exec) if args.exec else {}
    ex["inner_unroll"] = True

    d1, d2 = (int(x) for x in args.depths.split(","))
    stats = {}
    for d in (d1, d2):
        _, compiled, _, _, _ = lower_cell(
            args.arch, args.shape, args.mesh, ex,
            cfg_overrides={"num_layers": d}, rules=args.rules,
            params_mode=args.params,
        )
        stats[d] = (
            _raw_costs(compiled),
            H.collective_stats(compiled.as_text()).by_op,
        )

    (c1, ops1), (c2, ops2) = stats[d1], stats[d2]
    dd = d2 - d1
    print(f"== per-layer (depth {d2} - depth {d1}) ==")
    print(f"flops/layer      : {(c2[0] - c1[0]) / dd / 1e9:10.2f} GF")
    print(f"hbm bytes/layer  : {(c2[1] - c1[1]) / dd / 2**30:10.2f} GiB")
    print(f"link bytes/layer : {(c2[2] - c1[2]) / dd / 2**20:10.2f} MiB")
    print("-- per-layer collectives --")
    for op in sorted(set(ops1) | set(ops2)):
        a = ops1.get(op, {"count": 0, "link_bytes": 0})
        b = ops2.get(op, {"count": 0, "link_bytes": 0})
        dc = (b["count"] - a["count"]) / dd
        db = (b["link_bytes"] - a["link_bytes"]) / dd / 2**20
        print(f"  {op:20s} {dc:6.1f} ops/layer  {db:10.2f} MiB/layer")
    print("-- depth-1 totals (embed/head/loss overhead) --")
    print(
        f"flops {c1[0] / 1e9:.2f} GF, hbm {c1[1] / 2**30:.2f} GiB, "
        f"link {c1[2] / 2**20:.2f} MiB"
    )
    for op, rec in sorted(ops1.items()):
        print(f"  {op:20s} {rec['count']:5d} ops {rec['link_bytes']/2**20:10.2f} MiB")


if __name__ == "__main__":
    main()
