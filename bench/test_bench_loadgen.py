"""The traffic generator: seeded, within its stated ranges, and the same
work for every seed."""
import numpy as np
import pytest

from bench import loadgen

BIG_SEED = 2 ** 31 + 12345
MIXES = ["decode-4k", "offline-chat"]


def _sig(t):
    return [(r.tokens.tolist(), r.gen) for r in t.first + t.requests]


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests(mix):
    m = loadgen.load_mix(mix)
    a = loadgen.generate(m, BIG_SEED, 1000)
    b = loadgen.generate(m, BIG_SEED, 1000)
    assert _sig(a) == _sig(b)
    assert _sig(a) != _sig(loadgen.generate(m, BIG_SEED + 1, 1000))


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_within_ranges(mix):
    m = loadgen.load_mix(mix)
    t = loadgen.generate(m, 7, 50000)
    out = m["output"]
    for r in t.requests:
        assert out["min"] <= r.gen <= out["max"]
        assert r.tokens.min() >= 0 and r.tokens.max() < 50000
    lo, hi = m["prompt"]["min"], m["prompt"]["max"]
    assert all(lo <= len(r.tokens) <= hi for r in t.requests)
    assert len(t.first) == m["clients"]
    f = m["first_output"]
    assert all(f["min"] <= r.gen <= min(f["max"], out["max"])
               for r in t.first)


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_serves_the_same_sizes(mix):
    """--seed draws the token ids; the lengths and their order are the
    mix's own."""
    m = loadgen.load_mix(mix)
    a, b = (loadgen.generate(m, s, 1000) for s in (3, BIG_SEED))
    sizes = lambda rs: [(len(r.tokens), r.gen) for r in rs]
    assert sizes(a.requests) == sizes(b.requests)
    assert sizes(a.first) == sizes(b.first)
    assert not np.array_equal(a.requests[0].tokens, b.requests[0].tokens)


def test_seed_words_split_large_seeds():
    assert loadgen.seed_words(5) == [5]
    assert loadgen.seed_words(2 ** 32 + 3) == [3, 1]
    with pytest.raises(ValueError):
        loadgen.seed_words(-1)
