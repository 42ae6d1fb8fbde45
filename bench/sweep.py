"""Find the highest arrival rate an open-loop cell sustains, on the chip.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 1,2,3

One set-up, then for each rate a window of ``--seconds`` at that rate,
drained before the next.  Each prints one JSON line: requests due, the
queue left at the window's close, tokens/s and the 50th and 90th
percentiles of time to first token.  The knee is the highest rate at
which the queue does not grow over the window; the cell's traffic file
then fixes its rate at about four fifths of it.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True, help="comma-separated, per second")
    args = ap.parse_args(argv)

    import numpy as np

    from bench import loop, run, spec, traffic
    from bench.peaks import peaks

    cell = spec.load_cell(args.workload)
    if cell.traffic["loop"] != "open":
        raise SystemExit("sweep: the cell's traffic is not an open loop")
    devices = run.require_chip(cell.chips)
    run.use_compile_cache()
    engine, _ = run.setup(cell, args.seed, peaks(devices[0].device_kind))
    print(json.dumps({"setup_s": time.perf_counter() - T_START}), flush=True)
    queue_at_close = []
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(cell.traffic, rate_per_s=rate)
        # one seed throughout: its shared prefix is the one set-up registered
        items = traffic.schedule(mix, cell.config["vocab_size"], args.seed)
        rec, (t0, t1), limit = loop.open_(
            engine, items, args.seconds, mix["drain_s"],
            on_close=lambda: queue_at_close.append(len(engine.queue)))
        late = loop.first_token_lateness(rec, t0, t1, limit)
        print(json.dumps({
            "rate": rate, "due": len(late), "queue_at_close": queue_at_close[-1],
            "tok_s": loop.tokens_in(rec, t0, t1) / (t1 - t0),
            "ttft_p50_ms": float(np.percentile(late, 50, method="higher")) * 1e3,
            "ttft_p90_ms": float(np.percentile(late, 90, method="higher")) * 1e3,
            "failed": int(np.sum(~np.isfinite(late))),
        }), flush=True)
        run.drain(engine)
    return 0


if __name__ == "__main__":
    sys.exit(main())
