"""The traffic generator: seeded, exact prompt lengths, no shared blocks,
the same sizes and gaps in every seed, and Poisson arrivals."""
import collections

import numpy as np
import pytest

from bench import loadgen
from repro.tokenizer.bpe import default_tokenizer

CODE = {"loop": "open", "rate_per_s": 10.0, "ramp_s": 2, "drain_s": 3,
        "sizes_seed": 1,
        "prompt_tokens": {"dist": "lognormal", "median": 1500, "sigma": 0.8,
                          "min": 32, "max": 4096},
        "output_tokens": {"dist": "lognormal", "median": 13, "sigma": 1.0,
                          "min": 2, "max": 256}}
DOCQA = {"loop": "closed", "clients": 4, "requests_per_client": 3,
         "sizes_seed": 1,
         "prompt_tokens": {"dist": "lognormal", "median": 4096,
                           "sigma": 0.5, "min": 2048, "max": 8192},
         "output_tokens": {"dist": "uniform", "min": 32, "max": 128}}


@pytest.fixture(scope="module")
def builder():
    return loadgen.PromptBuilder(default_tokenizer())


def _key(t):
    return [(r.segment, round(r.due, 9), r.prompt_ids, r.max_new)
            for r in t.requests]


@pytest.mark.parametrize("spec", [CODE, DOCQA], ids=["open", "closed"])
def test_same_seed_same_requests(builder, spec):
    big = 2**31 + 12345
    a = loadgen.build(spec, big, 5, None, builder)
    b = loadgen.build(spec, big, 5, None, builder)
    c = loadgen.build(spec, big + 1, 5, None, builder)
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)


@pytest.mark.parametrize("spec", [CODE, DOCQA], ids=["open", "closed"])
def test_every_seed_serves_the_same_sizes(builder, spec):
    """Seeds differ in order, words and arrival times: the window holds the
    same multiset of (prompt length, output length) pairs."""
    def sizes(seed):
        t = loadgen.build(spec, seed, 5, None, builder)
        return collections.Counter(
            (len(r.prompt_ids), r.max_new) for r in t.requests
            if r.segment in ("window", "pool"))
    assert sizes(1) == sizes(2**31 + 7)


def test_every_seed_serves_the_same_gaps(builder):
    """Each window request keeps its gap before it and its sizes from
    seed to seed; only the order of the requests moves."""
    def requests(seed):
        t = loadgen.build(CODE, seed, 5, None, builder)
        window = [r for r in t.requests if r.segment == "window"]
        dues = [CODE["ramp_s"]] + [r.due for r in window]
        return [(round(b - a, 6), len(r.prompt_ids), r.max_new)
                for a, b, r in zip(dues, dues[1:], window)]
    a, b = requests(1), requests(2**31 + 7)
    assert a != b
    assert collections.Counter(a) == collections.Counter(b)


def test_open_loop_fills_each_segment(builder):
    t = loadgen.build(CODE, 7, 5, None, builder)
    n = collections.Counter(r.segment for r in t.requests)
    assert n == {"ramp": 20, "window": 50, "tail": 30}
    window = [r for r in t.requests if r.segment == "window"]
    assert all(2.0 <= r.due < 7.0 for r in window)
    dues = [r.due for r in t.requests]
    assert dues == sorted(dues)


def test_arrivals_are_poisson():
    """Gaps between arrivals are exponential: their coefficient of
    variation is about 1, and bursts (gaps under a tenth of the mean)
    come as often as an exponential has them."""
    spec = dict(CODE, rate_per_s=50.0, ramp_s=0, drain_s=0,
                prompt_tokens={"dist": "uniform", "min": 8, "max": 8},
                output_tokens={"dist": "uniform", "min": 2, "max": 2})
    t = loadgen.build(spec, 3, 200, None,
                      loadgen.PromptBuilder(default_tokenizer()))
    gaps = np.diff([r.due for r in t.requests if r.segment == "window"])
    assert len(gaps) == 9999
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.05)
    short = np.mean(gaps < 0.1 * gaps.mean())
    assert short == pytest.approx(1 - np.exp(-0.1), abs=0.015)


def test_lengths_follow_their_distribution():
    rng = np.random.default_rng(5)
    got = loadgen.draw_lengths(CODE["prompt_tokens"], 20000, rng)
    assert got.min() >= 32 and got.max() <= 4096
    assert np.median(got) == pytest.approx(1500, rel=0.03)
    assert np.mean(got == 4096) == pytest.approx(0.105, abs=0.01)
    u = loadgen.draw_lengths(DOCQA["output_tokens"], 20000, rng)
    assert set(np.unique(u)) == set(range(32, 129))


def test_prompts_tokenize_to_their_length_and_share_no_block(builder):
    tok = builder.tok
    t = loadgen.build(CODE, 11, 5, None, builder)
    for r in t.requests[:20]:
        assert tok.encode(r.text) == r.prompt_ids
    firsts = {tuple(r.prompt_ids[:8]) for r in t.requests}
    assert len(firsts) == len(t.requests)
    lens = [len(r.prompt_ids) for r in t.requests]
    assert min(lens) >= 32 and max(lens) <= 4096
