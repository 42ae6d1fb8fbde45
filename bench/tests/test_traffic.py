"""The generator: one seed, one schedule; every seed, the same work."""
import numpy as np
import pytest

from bench import spec, traffic

VOCAB = 49152
MIXES = ["batch", "chat"]


def mix(name):
    return spec.load_json(f"{spec.BENCH}/traffic/{name}.json")


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    a = traffic.schedule(mix(name), VOCAB, 2**33 + 5)
    b = traffic.schedule(mix(name), VOCAB, 2**33 + 5)
    assert len(a) == len(b) == mix(name)["requests"]
    for x, y in zip(a, b):
        assert (x.uid, x.max_new, x.offset_s) == (y.uid, y.max_new, y.offset_s)
        np.testing.assert_array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("name", MIXES)
def test_seeds_send_the_same_work_with_other_tokens(name):
    m = mix(name)
    a = traffic.schedule(m, VOCAB, 1)
    b = traffic.schedule(m, VOCAB, 2**31 + 11)
    assert [(len(x.prompt), x.max_new, x.offset_s) for x in a] == [
        (len(x.prompt), x.max_new, x.offset_s) for x in b]
    assert not np.array_equal(a[0].prompt, b[0].prompt)
    lens = [x.max_new for x in a]
    assert lens != sorted(lens)  # shuffled, not sorted


@pytest.mark.parametrize("name", MIXES)
def test_lengths_and_prefix_match_the_file(name):
    m = mix(name)
    items = traffic.schedule(m, VOCAB, 7)
    n = m["shared_prefix"]
    prefix = traffic.shared_prefix(m, VOCAB, 7)
    assert len(prefix) == n
    turns = np.array([len(x.prompt) - n for x in items])
    outs = np.array([x.max_new for x in items])
    for got, want in ((turns, m["prompt_len"]), (outs, m["output_len"])):
        assert got.min() >= want["lo"] and got.max() <= want["hi"]
        if want["dist"] == "uniform":
            assert got.min() == want["lo"] and got.max() == want["hi"]
        elif "mean" in want:  # a published mean, as the source states it
            assert got.mean() == pytest.approx(want["mean"], rel=0.02)
        else:
            assert np.median(got) == pytest.approx(want["median"], rel=0.02)
    for x in items:
        np.testing.assert_array_equal(x.prompt[:n], prefix)
        assert x.prompt.dtype == np.int32 and x.prompt.max() < VOCAB
        assert len(x.prompt) + x.max_new - 1 <= m["max_len"]
    if m["loop"] == "open":
        offs = np.array([x.offset_s for x in items])
        assert np.all(np.diff(offs) > 0)
        rate = len(offs) / offs[-1]
        assert rate == pytest.approx(m["rate_per_s"], rel=0.05)


def test_buckets_are_the_engine_widths():
    assert traffic.buckets(mix("batch")) == [4, 8, 16, 32, 64, 128]
    assert traffic.buckets(mix("chat")) == [8, 16, 32, 64]


def test_uniform_quantiles_cover_every_length_evenly():
    v = traffic.lengths({"dist": "uniform", "lo": 16, "hi": 64}, 49 * 4)
    assert np.array_equal(np.bincount(v)[16:], np.full(49, 4))
