"""The generator: the same seed gives the same inputs, another seed the
same work in another order, and the moments are as the mixes state."""

import numpy as np
import pytest

from bench.traffic import gen

CHAT = {"rate_per_s": 8.0, "token_zipf": 1.0,
        "prompt_len": {"dist": "lognormal", "mean": 161, "sigma": 1.0,
                       "min": 4, "max": 384},
        "output_len": {"dist": "lognormal", "mean": 338, "sigma": 1.0,
                       "min": 2, "max": 640}}
BIG_SEED = 2 ** 31 + 12345


def flat(reqs):
    return ([r.due_s for r in reqs], [r.prompt.tolist() for r in reqs],
            [r.max_new for r in reqs])


@pytest.mark.parametrize("seed", [0, 7, BIG_SEED, 3 * 2 ** 64 + 5, -4])
def test_same_seed_same_inputs(seed):
    a = gen.requests(CHAT, 30.0, seed, 32000, 1024)
    b = gen.requests(CHAT, 30.0, seed, 32000, 1024)
    assert flat(a) == flat(b)


def test_other_seed_same_work_other_order():
    a = gen.requests(CHAT, 30.0, 1, 32000, 1024)
    b = gen.requests(CHAT, 30.0, BIG_SEED, 32000, 1024)
    assert len(a) == len(b) == 240
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert [r.max_new for r in a] != [r.max_new for r in b]
    # the gaps of both are the same quantile set, one left out of each
    ga, gb = np.diff([r.due_s for r in a]), np.diff([r.due_s for r in b])
    assert np.isin(np.round(ga, 9), np.round(gb, 9)).sum() >= len(ga) - 1


def test_untruncated_lognormal_has_the_stated_mean():
    q = gen.lognormal_quantiles(200_000, 161.0, 1.0)
    assert q.mean() == pytest.approx(161.0, rel=2e-3)
    assert np.median(q) == pytest.approx(161.0 * np.exp(-0.5), rel=1e-3)


def test_truncated_lengths_stay_in_range_and_keep_their_mean():
    r = gen.lengths(CHAT["prompt_len"], 10_000, gen.rng(3, "x"))
    assert r.min() >= 4 and r.max() <= 384
    # the conditioned mean, worked out once from the quantile set
    assert r.mean() == pytest.approx(113.5, abs=1.0)


def test_poisson_gaps_have_the_stated_rate():
    t = gen.arrival_times(8.0, 20_000, gen.rng(5, "arrivals"))
    assert t[0] == 0.0 and np.all(np.diff(t) >= 0)
    gaps = np.diff(t)
    assert gaps.mean() == pytest.approx(1 / 8.0, rel=0.02)
    # exponential: the standard deviation equals the mean
    assert gaps.std() == pytest.approx(1 / 8.0, rel=0.05)


def test_zipf_tokens_follow_one_over_rank():
    t = gen.zipf_tokens((400_000,), 32000, 1.0, gen.rng(9, "tokens"))
    counts = np.bincount(t, minlength=32000)
    assert t.min() >= 0 and t.max() < 32000
    assert counts[0] / counts[1] == pytest.approx(2.0, rel=0.05)
    assert counts[0] / counts[3] == pytest.approx(4.0, rel=0.08)
    h = np.log(32000) + 0.5772
    assert counts[0] / t.size == pytest.approx(1 / h, rel=0.03)


def test_requests_refuse_a_mix_longer_than_the_server():
    with pytest.raises(ValueError):
        gen.requests(CHAT, 30.0, 1, 32000, 512)
