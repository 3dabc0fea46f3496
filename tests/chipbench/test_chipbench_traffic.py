"""The traffic generator: determinism, schedule, length and arrival
distributions, and new traffic files found by name."""

from __future__ import annotations

import json

from chipbench import spec
from chipbench import traffic
import numpy as np
import pytest

POISSON = {"arrivals": {"process": "poisson", "rate": 2.0},
           "prompt_len": {"values": [256, 512, 1024, 2048],
                          "weights": [0.2, 0.3, 0.3, 0.2]},
           "output_len": {"dist": "lognormal", "median": 64, "sigma": 0.8,
                          "min": 8, "max": 512},
           "schedule_seed": 3}
BURSTS = dict(POISSON, arrivals={"process": "bursts", "rate": 2.0,
                                 "burst_mean": 4})
BIG_SEED = 2 ** 31 + 12345


def test_same_seed_same_requests():
    a = traffic.generate(POISSON, 60, BIG_SEED, 1000)
    b = traffic.generate(POISSON, 60, BIG_SEED, 1000)
    assert [(p.due_s, p.max_new) for p in a] == \
        [(p.due_s, p.max_new) for p in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_seed_draws_tokens_not_the_schedule():
    a = traffic.generate(POISSON, 60, 1, 1000)
    b = traffic.generate(POISSON, 60, 2, 1000)
    assert [(p.due_s, len(p.prompt), p.max_new) for p in a] == \
        [(p.due_s, len(p.prompt), p.max_new) for p in b]
    assert not np.array_equal(a[0].prompt, b[0].prompt)
    assert all(p.prompt.min() >= 2 and p.prompt.max() < 1000 for p in a)


def test_poisson_offers_the_rate_inside_the_window():
    reqs = traffic.generate(POISSON, 60, 0, 1000)
    due = np.array([p.due_s for p in reqs])
    assert len(reqs) == 120
    assert np.all(np.diff(due) >= 0)
    assert due[0] >= 0 and due[-1] < 60
    gaps = np.diff(due)
    # exponential gaps: the mean near 1/rate, the spread near the mean
    assert gaps.mean() == pytest.approx(0.5, rel=0.05)
    assert gaps.std() == pytest.approx(0.5, rel=0.25)


def test_bursts_arrive_together_with_the_mean_size():
    reqs = traffic.generate(BURSTS, 60, 0, 1000)
    due = [p.due_s for p in reqs]
    sizes = np.unique(due, return_counts=True)[1]
    assert sizes.mean() == pytest.approx(4.0, rel=0.15)
    assert sizes.max() > 4 and sizes.min() == 1
    assert len(reqs) == pytest.approx(120, rel=0.1)


def test_lengths_follow_the_mix():
    reqs = traffic.generate(POISSON, 100, 0, 1000)
    plen = np.array([len(p.prompt) for p in reqs])
    for v, w in zip(POISSON["prompt_len"]["values"],
                    POISSON["prompt_len"]["weights"]):
        assert np.mean(plen == v) == pytest.approx(w, abs=1 / len(reqs))
    out = np.array([p.max_new for p in reqs])
    assert np.median(out) == pytest.approx(64, rel=0.05)
    assert out.min() >= 8 and out.max() <= 512


def test_a_new_traffic_file_is_found_by_name(bench_root):
    mix = dict(POISSON, schedule_seed=9)
    (bench_root / "chipbench" / "traffic" / "brand-new.json").write_text(
        json.dumps(mix))
    assert spec.load_traffic(bench_root, "brand-new") == mix
    assert len(traffic.generate(mix, 10, 0, 100)) == 20
