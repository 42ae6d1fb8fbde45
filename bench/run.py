"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json`` ``workloads``) is a model configuration served
under a traffic mix.  The run makes dense weights from ``--seed`` on the
device, converts them to the configuration's tables through the program's
planner, and drives ``repro.serve.BatchingEngine`` with the mix for
``--seconds``, after a set-up that compiles and executes every program the
window uses.  With ``--trace 0`` it reports the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics from a profiler trace of the
window.  Then it frees the engine and compares a sample of the served
tokens with the plain reference (``bench/check.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``check``, each number compared beside its limit.
The same numbers close standard error.  Without a TPU whose peaks the
benchmark knows, or with fewer chips than the cell asks for, the run exits
non-zero before any phase and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if sys.path and os.path.abspath(sys.path[0]) == BENCH:
    sys.path.pop(0)  # bench/trace.py must not shadow the standard library
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import spec  # noqa: E402


def log(msg: str):
    print(f"bench: {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Run:
    """What the metric readers read."""

    rec: object  # loop.Recorder
    t0: float
    t1: float
    limit: float  # open loop: drain limit for first tokens
    setup_s: float
    counters: dict
    info: dict
    peaks: dict
    slots: int
    events: object = None  # trace.Events, --trace 1 only

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0


def require_chip(chips: int):
    """The TPU devices of a kind in the peaks table, or exit non-zero."""
    import jax

    from bench.peaks import PEAKS

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu" or d.device_kind not in PEAKS:
        raise SystemExit(
            f"bench: needs a TPU whose peaks are known ({sorted(PEAKS)}); JAX "
            f"found {d.platform!r} {d.device_kind!r} x{len(devs)}"
        )
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def use_compile_cache():
    """JAX's persistent compilation cache: where ``JAX_COMPILATION_CACHE_DIR``
    says, or else at a fixed path inside the checkout."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def cache_bytes(mcfg, mix) -> int:
    import jax
    import jax.numpy as jnp

    from repro.models.params import abstract_params
    from repro.serve import cache_specs

    specs = cache_specs(mcfg, mix["slots"], mix["max_len"],
                        page_size=mix.get("page_size"))
    tree = abstract_params(specs, default_dtype=jnp.bfloat16)
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))


def drain(engine):
    while engine.step():
        pass


def _gc_timer(pauses: list):
    """A ``gc.callbacks`` entry that appends (seconds, generation) of each
    collection to ``pauses``."""
    began = [0.0]

    def timer(phase, info):
        if phase == "start":
            began[0] = time.perf_counter()
        else:
            pauses.append((time.perf_counter() - began[0], info["generation"]))

    return timer


def setup(cell, seed: int, peaks: dict):
    """Weights, tables, engine, and one execution of every program the
    window uses.  Returns (engine, info)."""
    import importlib

    import jax
    import numpy as np

    from bench import families, traffic, weights
    from repro.models.layers import Ctx
    from repro.serve import BatchingEngine, Request

    c, mix = cell.config, cell.traffic
    traffic.check(mix)
    arch = spec.arch(c)
    mcfg = arch.program_config(c)
    fam = importlib.import_module(f"bench.families.{c['family']}")
    t = time.perf_counter()
    dense = weights.make(arch.weight_layout(c), c["num_hidden_layers"], seed)
    jax.block_until_ready(dense)
    init_s = time.perf_counter() - t
    params, ex, info = fam.build(dense, mcfg, c, cache_bytes(mcfg, mix),
                                 peaks["hbm_bytes"])
    del dense
    jax.block_until_ready(params)
    gc.collect()
    info.update(
        init_s=init_s,
        stored_bytes=families.stored_bytes(params),
        row_bytes=fam.row_bytes(params),
        linear_work=arch.linear_work(c),
    )
    t = time.perf_counter()
    engine = BatchingEngine(
        params, Ctx(mcfg, ex=ex), num_slots=mix["slots"], max_len=mix["max_len"],
        page_size=mix.get("page_size"),
    )
    rng = traffic.rng_for(seed, "warmup")
    prefix = traffic.shared_prefix(mix, c["vocab_size"], seed)
    if len(prefix):  # the traffic needs it: prefilled and registered once
        engine.submit(Request(uid=-1, prompt=prefix, max_new=1))
        drain(engine)
    for i, b in enumerate(traffic.buckets(mix)):
        tail = rng.integers(0, c["vocab_size"], b).astype(np.int32)
        prompt = np.concatenate([prefix, tail])
        engine.submit(Request(uid=-2 - i, prompt=prompt, max_new=2))
        drain(engine)
    info["warmup_s"] = time.perf_counter() - t
    return engine, info


def measure(cell, seed: int, seconds: float, trace_on: bool, peaks: dict,
            devices, t_start: float):
    """Set up, run the window, read the metrics.  Returns the result line
    without its check, and the requests the window served."""
    import jax

    from bench import loop, traffic

    c, mix = cell.config, cell.traffic
    engine, info = setup(cell, seed, peaks)
    items = traffic.schedule(mix, c["vocab_size"], seed)
    t = time.perf_counter()
    gc.collect()
    log(f"a full garbage collection over {len(gc.get_objects())} objects took "
        f"{time.perf_counter() - t:.3f} s")
    # Set-up's objects leave the collector's scans, as a server freezes them
    # after loading: an automatic full collection in the window then scans
    # only what the window made, not every object JAX holds.
    gc.freeze()
    pauses = []
    timer = _gc_timer(pauses)
    gc.callbacks.append(timer)
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s: " + json.dumps(
        {k: info[k] for k in ("init_s", "convert_s", "warmup_s", "plan")}))

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, d, **kw: compiles.append(ev)
        if ev.endswith("backend_compile_duration") else None
    )
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace_on else None
    if trace_on:
        jax.profiler.start_trace(tdir)
    stopped = []

    def close():
        if trace_on and not stopped:
            jax.profiler.stop_trace()
            stopped.append(True)
        if timer in gc.callbacks:
            gc.callbacks.remove(timer)
        log(f"{len(compiles)} compiles in the window; {len(pauses)} garbage "
            f"collections, the longest {max(pauses, default=(0.0, 0))} (s, generation)")

    if mix["loop"] == "closed":
        rec, (t0, t1) = loop.closed(engine, items, mix["backlog"], seconds,
                                    on_close=close)
        limit = t1
    else:
        rec, (t0, t1), limit = loop.open_(engine, items, seconds, mix["drain_s"],
                                          on_close=close)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    gc.unfreeze()
    del engine  # the reference runs once the program's state is gone
    gc.collect()

    counters = {
        "output_tokens": loop.tokens_in(rec, t0, t1),
        "prefill_tokens": loop.prefilled_in(rec, t1),
        "prompt_tokens_admitted": loop.admitted_in(rec, t0, t1),
    }
    run = Run(rec, t0, t1, limit, setup_s, counters, info, peaks, mix["slots"])
    d0 = jax.devices()[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    breakdown = None
    if trace_on:
        from bench import trace

        t = time.perf_counter()
        run.events = trace.load(trace.find_xplane(tdir))
        shutil.rmtree(tdir, ignore_errors=True)
        w0, w1 = run.events.window
        device["busy_s"] = trace.busy_ns(run.events) / 1e9
        device["window_s"] = (w1 - w0) / 1e9
        breakdown = {"device_ops": trace.top_ops(run.events),
                     "idle_gaps": trace.idle_by_span(run.events)}
        wanted = [(m, spec.reader("metrics", m["name"])) for m in cell.per_layer]
        log(f"trace read in {time.perf_counter() - t:.3f} s")
    else:
        wanted = [(m, spec.reader("e2e", m["name"])) for m in cell.end_to_end]
    metrics = {}
    for m, read in wanted:
        v = read(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    if mix["loop"] == "open":
        due = [s for s in rec.sent if t0 <= s.due < t1]
        attempted = len(due)
        failed = sum(1 for s in due if not s.times or s.times[0] > limit)
    else:
        attempted = sum(1 for s in rec.sent if s.times and s.times[0] <= t1)
        failed = 0
    result = {"correct": None, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    return result, rec.sent


def compare(cell, seed: int, sent, control: bool = False) -> tuple[bool, dict]:
    """``correct`` and the numbers compared, each beside its limit.  With
    ``control`` the control's tokens are judged in place of the served
    ones (``bench/calibrate.py``, the tests)."""
    from bench import check, traffic

    c, mix = cell.config, cell.traffic
    t = time.perf_counter()
    picks = check.pick(sent, mix["sample"], seed, traffic.rng_for)
    tokens, targets, mask = check.batch(picks, mix["sample"], mix["max_len"])
    if control:
        targets = check.control_targets(c, seed, tokens)
    ok, compared = check.judge(c, seed, tokens, targets, mask)
    log(f"check took {time.perf_counter() - t:.3f} s over {int(mask.sum())} served "
        f"tokens of {len(picks)} requests")
    return ok, compared


def run_cell(cell, seed: int, seconds: float, trace_on: bool, peaks: dict,
             devices, t_start: float) -> dict:
    result, sent = measure(cell, seed, seconds, trace_on, peaks, devices, t_start)
    result["correct"], result["check"] = compare(cell, seed, sent)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    devices = require_chip(cell.chips)
    from bench.peaks import peaks

    use_compile_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      peaks(devices[0].device_kind), devices, T_START)
    for name, v in result["check"].items():
        print(f"check {name} {v['value']!r} limit {v['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
