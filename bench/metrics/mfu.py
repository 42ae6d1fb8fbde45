"""The whole step's share of the chip's bf16 peak: twice the multiply-adds
per token row (block projections and tied head) times the token rows the
window really computed (prompt tokens prefilled and tokens served), over
the window's seconds and the peak.  Padding rows, attention and the cache
are not counted as useful work."""


def read(run):
    work = run.info["linear_work"]
    rows = run.counters["prefill_tokens"] + run.counters["output_tokens"]
    flops = 2.0 * (work["linears"] + work["head"]) * rows
    return 100.0 * flops / run.window_s / run.peaks["bf16_flops"]
