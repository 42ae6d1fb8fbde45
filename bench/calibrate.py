"""Readings that set the limit of ``correct``, on the chip, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,13 --seconds <s>

For each seed: one run of the cell as the benchmark makes it (set-up, the
window at the cell's own load, the sample of served requests), then the
comparison that decides ``correct`` twice: on the served tokens, and on
the control's, the tokens that the reference one precision step down ranks
first at the same positions (``check.control_targets``), each with its
``correct`` and numbers.  One JSON line per seed.  The compiled programs are shared by the seeds, so only the
first pays the compiles.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if sys.path and os.path.abspath(sys.path[0]) == BENCH:
    sys.path.pop(0)
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from bench import run, spec
    from bench.peaks import peaks

    cell = spec.load_cell(args.workload)
    devices = run.require_chip(cell.chips)
    run.use_compile_cache()
    t_start = T_START
    for seed in (int(s) for s in args.seeds.split(",")):
        result, sent = run.measure(cell, seed, args.seconds, False,
                                   peaks(devices[0].device_kind), devices, t_start)
        t = time.perf_counter()
        ok, got = run.compare(cell, seed, sent)
        control_ok, control = run.compare(cell, seed, sent, control=True)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": ok, "check": got,
                          "control_correct": control_ok, "control": control,
                          "check_s": time.perf_counter() - t,
                          "metrics": result["metrics"]}), flush=True)
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
