"""The benchmark's seeded traffic generator (bench/traffic)."""
import pathlib
import sys

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench.traffic import (serving_requests, stratified_gaps,  # noqa: E402
                           stratified_lognormal)

MIX = {"rate_per_s": 7.0,
       "prompt_tokens": {"median": 96, "sigma": 0.5, "min": 32, "max": 256},
       "output_tokens": {"median": 384, "sigma": 0.7, "min": 64, "max": 1536}}


def test_lognormal_lengths_same_multiset_any_seed_other_order():
    a = stratified_lognormal(200, 96, 0.5, 32, 256, np.random.default_rng(1))
    b = stratified_lognormal(200, 96, 0.5, 32, 256, np.random.default_rng(2))
    assert sorted(a) == sorted(b)
    assert list(a) != list(b)
    assert a.min() >= 32 and a.max() <= 256
    assert abs(np.median(a) - 96) <= 2


def test_lognormal_lengths_clip_to_bounds():
    x = stratified_lognormal(1000, 384, 0.7, 64, 1536,
                             np.random.default_rng(0))
    assert x.min() == 64 and x.max() == 1536


def test_gaps_have_the_rate_and_the_same_multiset():
    g1 = stratified_gaps(500, 8.0, np.random.default_rng(3))
    g2 = stratified_gaps(500, 8.0, np.random.default_rng(4))
    assert np.allclose(sorted(g1), sorted(g2))
    assert abs(np.mean(g1) - 1 / 8.0) < 0.01 / 8.0 * 5
    assert np.all(g1 > 0)


def test_requests_deterministic_per_seed_and_inside_the_window():
    big = 2**31 + 12345
    r1 = serving_requests(MIX, 30.0, big, vocab=151936)
    r2 = serving_requests(MIX, 30.0, big, vocab=151936)
    assert len(r1) == 210
    for x, y in zip(r1, r2):
        assert x["arrival"] == y["arrival"] and x["max_new"] == y["max_new"]
        assert np.array_equal(x["prompt"], y["prompt"])
    arrivals = [r["arrival"] for r in r1]
    assert arrivals[0] == 0.0 and np.all(np.diff(arrivals) > 0)
    assert arrivals[-1] < 30.0
    assert all(0 <= r["prompt"].min() and r["prompt"].max() < 151936
               for r in r1)


def test_other_seed_same_work_other_order():
    r1 = serving_requests(MIX, 30.0, 5, vocab=1000)
    r2 = serving_requests(MIX, 30.0, 6, vocab=1000)
    assert sorted(len(r["prompt"]) for r in r1) == sorted(
        len(r["prompt"]) for r in r2)
    assert sorted(r["max_new"] for r in r1) == sorted(
        r["max_new"] for r in r2)
    assert [r["max_new"] for r in r1] != [r["max_new"] for r in r2]
