"""The seeded traffic generator: the same seed gives the same requests,
every seed the same sizes, the clips hold, and medians sit near their
parameter."""
import math
import statistics

import pytest

from bench_smoke import committed
from bench.traffic_gen import (closed_batch, lognormal_lengths, lognormal_median,
                               steps_of)

MIXES = ["bench/traffic/serve-gen.json", "bench/traffic/serve-prompt.json"]
BIG_SEED = 2**31 + 1234567


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests(mix):
    t = committed(mix)
    a = closed_batch(t, seed=BIG_SEED, index=3, vocab=49155)
    b = closed_batch(t, seed=BIG_SEED, index=3, vocab=49155)
    c = closed_batch(t, seed=BIG_SEED + 1, index=3, vocab=49155)
    assert a == b
    assert a != c


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_gets_the_same_sizes(mix):
    t = committed(mix)
    batches = [closed_batch(t, seed=s, index=i, vocab=100)
               for s, i in ((1, 0), (BIG_SEED, 5))]
    sizes = [sorted((len(r.prompt), r.max_new_tokens) for r in b) for b in batches]
    # the same (prompt, output) pairs, so every batch lasts as many steps
    assert sizes[0] == sizes[1]
    assert ([len(r.prompt) for r in batches[0]]
            != [len(r.prompt) for r in batches[1]])     # in another order


@pytest.mark.parametrize("mix", MIXES)
def test_clips_and_medians(mix):
    t = committed(mix)
    reqs = closed_batch(t, seed=7, index=0, vocab=1000)
    assert len(reqs) == t["batch"]
    for key, lens in (("prompt", [len(r.prompt) for r in reqs]),
                      ("output", [r.max_new_tokens for r in reqs])):
        spec = t[key]
        assert min(lens) >= spec["min"] and max(lens) <= spec["max"]
        median = lognormal_median(spec)
        assert abs(statistics.median(lens) - median) <= 0.05 * median
    assert all(0 <= tok < 1000 for r in reqs for tok in r.prompt)


def test_lognormal_quantiles_are_heavy_tailed():
    lens = lognormal_lengths({"median": 100, "sigma": 0.6, "min": 1, "max": 10**6}, 1000)
    assert lens == sorted(lens)
    assert lens[950] > 2.5 * lens[500]       # the 95th percentile is 2.7x the median


def test_steps_of_a_request():
    r = closed_batch(committed(MIXES[0]), seed=1, index=0, vocab=10)[0]
    assert steps_of(r) == len(r.prompt) + r.max_new_tokens - 1


def test_the_window_never_overruns_the_cache():
    from bench.traffic_gen import longest_sequence

    cfg = committed("bench/configs/granite-3-2b.json")
    for mix in MIXES:
        assert longest_sequence(committed(mix)) <= cfg["serve"]["max_len"]


def test_a_mean_sets_the_median():
    spec = {"mean": 58.45, "sigma": 0.8}
    assert lognormal_median(spec) == pytest.approx(58.45 * math.exp(-0.32))
    lens = lognormal_lengths({**spec, "min": 1, "max": 10**6}, 4000)
    assert statistics.mean(lens) == pytest.approx(58.45, rel=0.02)
