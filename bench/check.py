"""The comparison that decides ``correct``.

After the window has closed and the program's state is freed, a sample of
the requests the window served, drawn from the seed with the longest among
them, goes through the plain reference of the configuration's
architecture (``bench/reference/<model_type>.py``): once over
each prompt followed by its served tokens.  At every position where the
engine served a token, the number read is how far that token's logit lies
below the reference's best logit there.  The widest such gap over the
sample is held against the configuration's limit ``limits.logit_gap``.
Greedy tokens only: the cells serve greedily.

The control (``control_targets``) puts the reference one precision step
down in the program's place: at the same positions of the same prompts and
served tokens, the token it ranks first takes the served token's place, and
:func:`judge` decides on it as on a run.  It is run by
``bench/calibrate.py`` and the tests, never by a benchmark run.
"""
from __future__ import annotations

import numpy as np

from bench import spec


def pick(sent: list, n: int, seed: int, stream) -> list:
    """Up to ``n`` served requests: finished ones first, the one with the
    most served tokens always among them, the rest drawn from the seed."""
    served = [s for s in sent if len(s.req.generated) > 0]
    done = [s for s in served if s.req.done]
    rest = [s for s in served if not s.req.done]
    pool = done if len(done) >= n else done + rest
    if not pool:
        return []
    longest = max(pool, key=lambda s: len(s.req.generated))
    others = [s for s in pool if s is not longest]
    rng = stream(seed, "sample")
    take = rng.permutation(len(others))[: n - 1]
    return [longest] + [others[i] for i in sorted(take)]


def batch(picks: list, n: int, T: int):
    """Tokens (n, T), next-token targets (n, T) and a mask of the positions
    at which the engine served a token."""
    tokens = np.zeros((n, T), np.int32)
    targets = np.zeros((n, T), np.int32)
    mask = np.zeros((n, T), bool)
    for i, s in enumerate(picks):
        prompt = np.asarray(s.item.prompt, np.int32)
        gen = np.asarray(s.req.generated, np.int32)
        seq = np.concatenate([prompt, gen[:-1]])
        if len(seq) > T:
            raise ValueError(f"request {s.item.uid}: {len(seq)} tokens exceed {T}")
        P = len(prompt)
        tokens[i, : len(seq)] = seq
        targets[i, P - 1 : P - 1 + len(gen)] = gen
        mask[i, P - 1 : P - 1 + len(gen)] = True
    return tokens, targets, mask


def widest_gap(c: dict, seed: int, tokens, targets, mask) -> tuple[float, int]:
    """Widest gap of a served token below the reference's best logit, and
    the number of served tokens outside the vocabulary."""
    bad = int(np.sum(mask & ((targets < 0) | (targets >= c["vocab_size"]))))
    t = np.where(mask, np.clip(targets, 0, c["vocab_size"] - 1), 0)
    ref = spec.reference(c)
    h = ref.hidden(c, seed, tokens)
    best, at, _ = ref.head_stats(c, seed, h, t)
    gap = np.asarray(best) - np.asarray(at)
    return float(np.max(gap[mask])) if mask.any() else float("inf"), bad


def control_targets(c: dict, seed: int, tokens) -> np.ndarray:
    """At every position, the token that the reference one precision step
    below the configuration's ranks first."""
    ref = spec.reference(c)
    low = ref.hidden(c, seed, tokens, lower=True)
    _, _, top = ref.head_stats(c, seed, low, np.zeros(tokens.shape, np.int32))
    return np.asarray(top)


def judge(c: dict, seed: int, tokens, targets, mask) -> tuple[bool, dict]:
    """``correct`` and the numbers compared, each beside its limit."""
    gap, bad = widest_gap(c, seed, tokens, targets, mask)
    compared = {
        "logit_gap": {"value": gap, "limit": c["limits"]["logit_gap"]},
        "bad_tokens": {"value": bad, "limit": 0},
    }
    ok = all(v["value"] <= v["limit"] for v in compared.values()) and bool(mask.any())
    return ok, compared
