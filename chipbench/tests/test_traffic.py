import math
import statistics

import numpy as np
import pytest

from harness.traffic import RequestStream, lengths, poisson_times

MIX = {"loop": "open",
       "prompt": {"dist": "lognormal", "median": 61, "sigma": 0.5,
                  "min": 16, "max": 128},
       "output": {"dist": "lognormal", "median": 179, "sigma": 0.6,
                  "min": 16, "max": 1024},
       "block": 32}


def test_lognormal_lengths_are_the_strata_middles_clipped():
    d = MIX["prompt"]
    x = lengths(d, 4096, np.random.default_rng(1), 32)
    assert x.min() >= 16 and x.max() == 128       # the clip bites
    assert np.median(x) == pytest.approx(61, abs=2)
    z = statistics.NormalDist().inv_cdf(0.5 / 32)
    assert x.min() == max(16, round(61 * math.exp(0.5 * z)))
    y = lengths(MIX["output"], 32, np.random.default_rng(1), 32)
    z = statistics.NormalDist().inv_cdf(1 - 0.5 / 32)
    assert y.max() == round(179 * math.exp(0.6 * z))   # the block's top


def test_uniform_lengths_cover_the_range_evenly():
    d = {"dist": "uniform", "min": 16, "max": 64}
    x = lengths(d, 49 * 200, np.random.default_rng(2), 49)
    counts = np.bincount(x - 16, minlength=49)
    assert x.min() == 16 and x.max() == 64
    assert counts.min() == counts.max() == 200   # one per stratum per block


def test_every_block_holds_one_draw_per_stratum():
    d = {"dist": "uniform", "min": 0, "max": 15}
    x = lengths(d, 64, np.random.default_rng(5), 16)
    for b in range(4):
        assert sorted(x[16 * b:16 * b + 16]) == list(range(16))


def test_poisson_rate():
    t = poisson_times(2.5, 8000, np.random.default_rng(3), 16)
    assert np.all(np.diff(t) > 0)
    assert 8000 / t[-1] == pytest.approx(2.5, rel=1e-9)
    assert t[15] == pytest.approx(16 / 2.5)         # a block's span


def test_stream_is_a_function_of_the_seed():
    big = 2 ** 33 + 17                      # seeds beyond 32 bits
    a = [RequestStream(MIX, big, 151936, 2.0).pop() for _ in range(1)]
    s1, s2 = (RequestStream(MIX, big, 151936, 2.0) for _ in range(2))
    r1 = [s1.pop() for _ in range(40)]
    r2 = [s2.pop() for _ in range(40)]
    assert a[0].t == r1[0].t
    assert all(x.t == y.t and x.max_new_tokens == y.max_new_tokens
               and np.array_equal(x.prompt, y.prompt) for x, y in zip(r1, r2))
    r3 = [RequestStream(MIX, big + 1, 151936, 2.0).pop() for _ in range(1)]
    assert not np.array_equal(r3[0].prompt, r1[0].prompt)


def test_stream_tokens_span_the_vocabulary():
    s = RequestStream(MIX, 9, 151936, 2.0)
    toks = np.concatenate([s.pop().prompt for _ in range(400)])
    assert toks.min() >= 0 and toks.max() < 151936
    assert toks.max() > 140000                  # not a small-vocab draw


def test_seeds_offer_the_same_sizes_in_another_order():
    a = RequestStream(MIX, 1, 100, 2.0)
    b = RequestStream(MIX, 2, 100, 2.0)
    ra = [a.pop() for _ in range(160)]             # five whole blocks
    rb = [b.pop() for _ in range(160)]
    for key in (lambda r: r.max_new_tokens, lambda r: r.prompt.size):
        assert sorted(map(key, ra)) == sorted(map(key, rb))
        assert [key(r) for r in ra] != [key(r) for r in rb]
    assert ra[-1].t == pytest.approx(rb[-1].t)


def test_a_mix_with_sizes_seed_offers_every_seed_the_same_sequence():
    mix = dict(MIX, sizes_seed=0)
    a = RequestStream(mix, 1, 151936, 2.0)
    b = RequestStream(mix, 2 ** 33 + 5, 151936, 2.0)
    ra = [a.pop() for _ in range(80)]
    rb = [b.pop() for _ in range(80)]
    assert [(r.t, r.max_new_tokens, r.prompt.size) for r in ra] == \
        [(r.t, r.max_new_tokens, r.prompt.size) for r in rb]
    assert not any(np.array_equal(x.prompt, y.prompt)
                   for x, y in zip(ra, rb))          # the ids follow the seed
    c = [RequestStream(MIX, 0, 151936, 2.0).pop() for _ in range(1)]
    assert (c[0].t, c[0].max_new_tokens, c[0].prompt.size) == \
        (ra[0].t, ra[0].max_new_tokens, ra[0].prompt.size)


def test_closed_loop_needs_no_rate_and_open_loop_does():
    RequestStream(dict(MIX, loop="closed"), 1, 100).pop()
    with pytest.raises(ValueError):
        RequestStream(MIX, 1, 100)
