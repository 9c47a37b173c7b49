"""The yardstick's arithmetic: percentiles, interval unions, FLOP and
byte counts against hand-worked GPT-2 medium numbers, the generator."""

import json
import math
import os

import numpy as np
import pytest

from benchmark.families import gpt2
from benchmark.harness import flops, stats, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("q", [0, 10, 50, 90, 99, 100])
@pytest.mark.parametrize("n", [1, 2, 7, 100])
def test_percentile_is_numpys(q, n):
    xs = np.random.default_rng(n).normal(size=n).tolist()
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_of_nothing_and_of_failures():
    assert stats.percentile([], 90) is None
    # a failed request is over any value: it sorts last, and a tail
    # that reaches it is infinite
    assert stats.percentile([1.0] * 95 + [math.inf] * 5, 90) == 1.0
    assert stats.percentile([1.0] * 80 + [math.inf] * 20, 90) == math.inf


def test_spread_is_quartile_distance_over_median():
    assert stats.spread([10, 11, 12, 13, 14]) == pytest.approx(2 / 12)
    assert stats.spread([5.0]) is None


def test_union_and_uncovered():
    assert stats.union_seconds([(0, 2), (1, 3), (5, 6)]) == 4
    assert stats.union_seconds([]) == 0
    # a collective from 0 to 10, compute over [2, 4] and [8, 12]
    assert stats.subtract_cover([(0, 10)], [(2, 4), (8, 12)]) == 6
    assert stats.subtract_cover([(0, 1), (2, 3)], []) == 2
    assert stats.subtract_cover([(0, 1)], [(0, 1)]) == 0


def test_sustained_rate_ignores_a_pause_and_follows_a_slower_step():
    # 40 ticks of 1 s that each end 10 tokens; stretches of 8 s
    marks = list(range(41))
    steady = stats.sustained_rate(marks, [10] * 40, 8.0)
    assert steady == pytest.approx(10.0)
    # a pause of 1.2 s inside tick 17: the whole window loses 3 %
    paused = [m + (1.2 if m > 17 else 0.0) for m in marks]
    assert 400 / (paused[-1] - paused[0]) == pytest.approx(9.709, abs=1e-3)
    assert stats.sustained_rate(paused, [10] * 40, 8.0) == pytest.approx(10)
    # every tick 3 % longer: the sustained rate shows all of it
    slower = [m * 1.03 for m in marks]
    assert stats.sustained_rate(slower, [10] * 40, 8.0) == pytest.approx(
        10 / 1.03)
    # a stretch ends on a mark: 8 ticks of 0.999 s do not make 8 s, 9 do
    short = [m * 0.999 for m in marks]
    assert stats.sustained_rate(short, [10] * 40, 8.0) == pytest.approx(
        10 / 0.999)
    # no stretch fits: the whole window; nothing to count: nothing
    assert stats.sustained_rate([0, 1, 2], [4, 6], 8.0) == 5.0
    assert stats.sustained_rate([0.0], [], 8.0) is None


def test_strata_hold_the_same_work_in_every_block():
    spec = {"dist": "uniform", "min": 4, "max": 16, "strata": 52}
    a = traffic.lengths(spec, np.random.default_rng(1), 520)
    b = traffic.lengths(spec, np.random.default_rng(2), 520)
    assert (a != b).any()                       # the order is the seed's
    for x in (a, b):
        for i in range(0, 520, 52):             # four of each length
            assert (np.bincount(x[i:i + 52], minlength=17)[4:] == 4).all()
    logn = {"dist": "lognormal", "median": 192, "sigma": 0.8, "min": 16,
            "max": 768, "strata": 64}
    x = traffic.lengths(logn, np.random.default_rng(0), 640)
    means = [x[i:i + 64].mean() for i in range(0, 640, 64)]
    assert x.min() >= 16 and x.max() <= 768 and np.median(x) == 192
    assert max(means) - min(means) < 0.02 * np.mean(means)
    # without the key the draw is what it was: independent
    plain = {k: v for k, v in spec.items() if k != "strata"}
    assert (traffic.lengths(plain, np.random.default_rng(1), 520)
            == np.random.default_rng(1).integers(4, 17, 520)).all()


@pytest.fixture(scope="module")
def medium():
    with open(os.path.join(ROOT, "configs", "gpt2-medium.json")) as f:
        return gpt2.shape(json.load(f))


def test_gpt2_medium_flops_by_hand(medium):
    # 24 layers x 12 x 1024^2 + 50257 x 1024 (published vocabulary)
    assert flops.matmul_params(medium) == 301_989_888 + 51_463_168
    # 6N + 12 * 24 * 1024 * 1024 = 2.1207e9 + 0.3020e9
    assert flops.train_flops_per_token(medium, 1024) == 2_422_708_224
    # 1024 * 1025 / 2 pairs x 4 * 64 FLOPs x 16 heads x 24 layers
    fwd = flops.flash_flops([1024], medium, backward=False)
    assert fwd == 524_800 * 256 * 16 * 24
    assert flops.flash_flops([1024], medium, backward=True) == 3.5 * fwd
    # padding is not needed work: two prompts of 100 cost twice one
    assert flops.flash_flops([100, 100], medium, backward=False) == \
        2 * flops.flash_flops([100], medium, backward=False)


def test_decode_bytes_by_hand(medium):
    # K and V, 24 layers, 16 heads x 64, bf16: 98304 bytes a position
    assert flops.decode_attn_bytes([100], medium) == 100 * 98_304
    assert flops.decode_attn_bytes([1, 2, 3], medium) == 6 * 98_304


def test_lengths_are_seeded_and_clipped():
    spec = {"dist": "lognormal", "median": 192, "sigma": 0.8,
            "min": 16, "max": 768}
    a = traffic.lengths(spec, np.random.default_rng(3), 5000)
    b = traffic.lengths(spec, np.random.default_rng(3), 5000)
    assert (a == b).all() and a.min() >= 16 and a.max() == 768
    assert 170 < np.median(a) < 215
    mix = {"dist": "mixture", "parts": [
        {"weight": 0.8, "dist": "uniform", "min": 16, "max": 128},
        {"weight": 0.2, "dist": "fixed", "value": 900}]}
    m = traffic.lengths(mix, np.random.default_rng(0), 4000)
    assert 0.15 < (m == 900).mean() < 0.25 and m.min() >= 16


@pytest.mark.parametrize("spec", [
    {"process": "poisson", "rate_per_s": 50.0},
    {"process": "gamma", "rate_per_s": 50.0, "cv": 3.0}])
def test_arrivals_hold_their_rate(spec):
    t = traffic.arrival_times(spec, np.random.default_rng(1), 400.0)
    assert (np.diff(t) >= 0).all() and t[-1] < 400.0
    assert len(t) / 400.0 == pytest.approx(50.0, rel=0.1)
    gaps = np.diff(t)
    assert gaps.std() / gaps.mean() == pytest.approx(
        spec.get("cv", 1.0), rel=0.15)


def test_requests_fit_the_horizon():
    tr = {"prompt_len": {"dist": "uniform", "min": 900, "max": 1000},
          "output_len": {"dist": "fixed", "value": 200}}
    reqs = traffic.requests(tr, np.random.default_rng(0), 50, 50257, 1024)
    assert all(len(r["prompt"]) + r["max_tokens"] <= 1024 for r in reqs)
    assert all(0 <= t < 50257 for r in reqs for t in r["prompt"])


def test_zipf_stream_is_skewed_and_in_range():
    tok = traffic.zipf_tokens({"exponent": 1.1}, np.random.default_rng(0),
                              200_000, 50257)
    assert tok.min() >= 0 and tok.max() < 50257
    top = np.bincount(tok).max() / tok.size
    assert 0.05 < top < 0.2     # rank 1 of Zipf(1.1) over 50k: about 10 %
