"""The traffic generator is a function of the mix and the seed."""
import itertools

import numpy as np
import pytest

import traffic

SEEDS = (7, 2**31 + 11, 3_000_000_019)


@pytest.mark.parametrize("mix", ["sharegpt", "chat"])
@pytest.mark.parametrize("seed", SEEDS)
def test_requests_repeat_for_a_seed(mix, seed):
    m = traffic.Mix.load(mix)
    a = list(itertools.islice(traffic.requests(m, seed, 1000), 40))
    b = list(itertools.islice(traffic.requests(m, seed, 1000), 40))
    for x, y in zip(a, b):
        assert x["max_new"] == y["max_new"]
        np.testing.assert_array_equal(x["prompt"], y["prompt"])


@pytest.mark.parametrize("mix", ["sharegpt", "chat"])
def test_every_seed_serves_the_same_sizes_in_another_order(mix):
    m = traffic.Mix.load(mix)
    n = m.strata
    blocks = {}
    for seed in SEEDS:
        reqs = list(itertools.islice(traffic.requests(m, seed, 1000), n))
        blocks[seed] = ([len(r["prompt"]) for r in reqs],
                        [r["max_new"] for r in reqs])
    firsts = [blocks[s] for s in SEEDS]
    for p, o in firsts:
        assert sorted(p) == sorted(m.prompt.quantiles(n).tolist())
        assert sorted(o) == sorted(m.output.quantiles(n).tolist())
        assert max(p) <= m.buckets[-1]
        assert max(p) + max(o) <= m.cache_len
    assert len({tuple(p) for p, _ in firsts}) == len(SEEDS)


@pytest.mark.parametrize("seed", SEEDS)
def test_arrivals_repeat_and_keep_the_rate(seed):
    m = traffic.Mix.load("chat")
    a = list(itertools.islice(traffic.arrival_gaps(m, seed, 2.5), 64))
    b = list(itertools.islice(traffic.arrival_gaps(m, seed, 2.5), 64))
    assert a == b
    assert np.mean(a) == pytest.approx(1 / 2.5, rel=1e-9)
    other = list(itertools.islice(traffic.arrival_gaps(m, seed + 1, 2.5),
                                  64))
    assert other != a and sorted(other) == pytest.approx(sorted(a))


def test_a_mix_whose_ring_would_wrap_is_refused():
    raw = dict(traffic.Mix.load("sharegpt").raw, cache_len=1024)
    with pytest.raises(ValueError, match="cache_len"):
        traffic.Mix.from_dict("bad", raw)
