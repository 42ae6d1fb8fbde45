"""A cell cut to a size that a CPU test run can hold: granite's layout at
toy widths, two slots, short requests.  The Pallas kernels run in
interpret mode."""
from bench import spec

CELLS = {"wtab-batch": "batch", "tl1-batch": "batch", "tl1-chat": "chat"}
WIDTHS = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
              num_key_value_heads=2, head_dim=16, vocab_size=512,
              num_hidden_layers=2, vocab_pad_multiple=16)
SHORT = {
    "prompt_len": {"dist": "uniform", "lo": 4, "hi": 12},
    "output_len": {"dist": "lognormal", "median": 8, "sigma": 0.5, "lo": 4, "hi": 16},
    "slots": 2, "sample": 2,
}
MIXES = {
    "batch": dict(SHORT, loop="closed", backlog=2, max_len=64, shared_prefix=0,
                  requests=4000),
    "chat": dict(SHORT, loop="open", rate_per_s=4.0, drain_s=30, max_len=96,
                 page_size=8, shared_prefix=32, requests=64),
}
# Widest logit gap allowed at this size, from CPU readings on three seeds
# (program / control): weight tables 0.020-0.047 / 0.64-0.72, TL1
# 0.0017-0.010 / 0.157-0.241.  Logits here spread over about 0.16, against
# about 1.3 at granite's width, so the chip's limits do not carry over.
LIMIT = {"wtab": 0.15, "tl1": 0.05}
PEAKS = {"bf16_flops": 197e12, "hbm_bw": 819e9, "hbm_bytes": 16e9}


# A cell that the benchmark does not run yet (PERF.md, Open questions): the
# TL1 configuration under the chat mix, with its time to first token.
PENDING = {"tl1-chat": ("tl1-batch", [
    {"name": "ttft_p90_ms", "unit": "ms", "better": "lower", "source": "host_clock"}])}


def cell(name: str):
    base, extra = PENDING.get(name, (name, []))
    c = spec.load_cell(base)
    c.name = name
    c.end_to_end = c.end_to_end + extra
    c.config = dict(c.config, **WIDTHS)
    c.config["limits"] = {"logit_gap": LIMIT[c.config["family"]]}
    c.traffic = dict(MIXES[CELLS[name]], name=CELLS[name])
    return c
