"""One seeded generator for every traffic mix.

A mix is a data file ``bench/traffic/<name>.json``.  Its keys:

- ``loop``: ``"closed"`` (a backlog of ``backlog`` queued requests is kept
  in front of the engine) or ``"open"`` (Poisson arrivals at ``rate_per_s``);
- ``slots``, ``max_len`` and, for a paged cache, ``page_size``: the engine;
- ``shared_prefix``: tokens of one prefix that every prompt starts with,
  prefilled and registered during set-up (0 for none);
- ``prompt_len``: the tokens after the shared prefix, and ``output_len``:
  ``{"dist": "uniform", "lo", "hi"}`` or ``{"dist": "lognormal", "median"
  or "mean", "sigma", "lo", "hi"}`` (a lognormal's ``mean`` is its median
  times ``exp(sigma**2 / 2)``; rounded lengths are clipped to ``lo``-``hi``);
- ``requests``: how many requests the schedule holds.  An open-loop mix
  holds what its rate brings in one window (``rate_per_s`` x the window),
  so every seed has every request due inside it;
- ``sample``: how many finished requests the correctness check compares;
- ``drain_s`` (open loop): how long after the window a request due in it
  may take to its first token before it counts as failed.

The lengths and the gaps between arrivals are drawn at evenly spaced
quantiles and shuffled once, by a fixed stream that no seed moves: every
seed sends the same lengths at the same times.  The seed draws every token
id (and the weights), never how much work there is.  A seeded order was
tried first: the batch window consumes about 12 of the schedule's
requests, which ones decided how many admissions fell into it, and
``output_tok_s`` spread 5.5% across seeds against 0.6% between two runs of
one seed (PERF.md).
"""
from __future__ import annotations

import dataclasses
import statistics

import numpy as np


@dataclasses.dataclass
class Item:
    uid: int
    prompt: np.ndarray  # (S,) int32, shared prefix included
    max_new: int
    offset_s: float  # due time after the window opens (open loop)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at evenly spaced quantiles of ``spec``, sorted."""
    u = _quantiles(n)
    if spec["dist"] == "uniform":
        lo, hi = spec["lo"], spec["hi"]
        v = lo + np.floor(u * (hi - lo + 1))
    elif spec["dist"] == "lognormal":
        sigma = spec["sigma"]
        median = spec["median"] if "median" in spec else spec["mean"] * np.exp(-sigma**2 / 2)
        z = np.array([statistics.NormalDist().inv_cdf(float(x)) for x in u])
        v = np.clip(np.round(median * np.exp(sigma * z)),
                    spec["lo"], spec["hi"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return v.astype(np.int64)


def gaps(rate: float, n: int) -> np.ndarray:
    """``n`` exponential gaps between arrivals at ``rate`` per second."""
    return -np.log1p(-_quantiles(n)) / rate


def rng_for(seed: int, stream: str) -> np.random.Generator:
    words = [ord(ch) for ch in stream]
    return np.random.default_rng(np.random.SeedSequence([int(seed), *words]))


def shared_prefix(mix: dict, vocab: int, seed: int) -> np.ndarray:
    n = mix.get("shared_prefix", 0)
    return rng_for(seed, "prefix").integers(0, vocab, n).astype(np.int32)


def schedule(mix: dict, vocab: int, seed: int) -> list[Item]:
    n = mix["requests"]
    order = rng_for(0, "order")  # the same order for every seed
    plen = order.permutation(lengths(mix["prompt_len"], n))
    olen = order.permutation(lengths(mix["output_len"], n))
    if mix["loop"] == "open":
        offs = np.cumsum(order.permutation(gaps(mix["rate_per_s"], n)))
    else:
        offs = np.zeros(n)
    prefix = shared_prefix(mix, vocab, seed)
    toks = rng_for(seed, "tokens")
    items = []
    for i in range(n):
        turn = toks.integers(0, vocab, int(plen[i])).astype(np.int32)
        items.append(Item(i, np.concatenate([prefix, turn]), int(olen[i]),
                          float(offs[i])))
    return items


def buckets(mix: dict) -> list[int]:
    """Prefill widths the engine can reach under this mix: the prompt
    tails after the shared prefix that the schedule holds, rounded up as
    the engine pads them (powers of two from 4, capped at ``max_len``)."""
    out = set()
    for n in set(lengths(mix["prompt_len"], mix["requests"]).tolist()):
        b = 4
        while b < n:
            b *= 2
        out.add(min(b, mix["max_len"]))
    return sorted(out)


def longest_request(mix: dict) -> int:
    """Prompt and output tokens of the longest request the schedule can
    hold: its longest prompt beside its longest output."""
    n = mix["requests"]
    hi = lengths(mix["prompt_len"], n).max() + lengths(mix["output_len"], n).max()
    return mix.get("shared_prefix", 0) + int(hi)


def check(mix: dict) -> None:
    if longest_request(mix) - 1 > mix["max_len"]:
        raise ValueError(
            f"the longest request needs {longest_request(mix) - 1} cache "
            f"positions; max_len is {mix['max_len']}"
        )
    if mix["loop"] not in ("open", "closed"):
        raise ValueError(f"loop must be open or closed: {mix['loop']!r}")
    if mix.get("page_size") and mix.get("shared_prefix", 0) % mix["page_size"]:
        raise ValueError("the shared prefix must fill whole pages")
