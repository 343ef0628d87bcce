"""The traffic generator: deterministic per seed, the same work for
every seed, and the distributions its mix files state."""
import numpy as np
import pytest

from bench import spec, traffic

MIXES = ["household-chat", "household-docqa", "household-docqa-cold",
         "nightly-summaries"]


def _sizes(plan):
    return sorted((s.prefix, s.document,
                   tuple((t.question, t.answer, round(t.think_s, 9))
                         for t in s.turns)) for s in plan.sessions)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_plan(name):
    mix = spec.load_traffic(name)
    a = traffic.make_plan(mix, 4294967311, 30, 1000)
    b = traffic.make_plan(mix, 4294967311, 30, 1000)
    assert [s.arrival_s for s in a.sessions] == [s.arrival_s
                                                  for s in b.sessions]
    assert _sizes(a) == _sizes(b)
    assert all(np.array_equal(x, y) for x, y in zip(a.prefixes, b.prefixes))


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_replays_the_same_schedule_with_its_own_tokens(name):
    mix = spec.load_traffic(name)
    a = traffic.make_plan(mix, 1, 30, 1000)
    b = traffic.make_plan(mix, 2, 30, 1000)
    assert [s.turns for s in a.sessions] == [s.turns for s in b.sessions]
    assert ([s.arrival_s for s in a.sessions]
            == [s.arrival_s for s in b.sessions])
    if mix["loop"] == "open":
        n = round(mix["rate_per_s"] * 30)
        assert len(a.sessions) == n
        t = [s.arrival_s for s in a.sessions]
        assert t == sorted(t) and 0 <= t[0] and t[-1] < 30
    if a.prefixes:
        assert not np.array_equal(a.prefixes[0], b.prefixes[0])


@pytest.mark.parametrize("spec_, median", [
    ({"dist": "lognormal", "median": 128, "sigma": 0.8, "min": 16,
      "max": 1024}, 128),
    ({"dist": "loguniform", "min": 1024, "max": 3072}, (1024 * 3072) ** 0.5),
    ({"dist": "uniform", "min": 32, "max": 128}, 80),
    ({"dist": "const", "value": 256}, 256),
])
def test_length_distributions(spec_, median):
    x = traffic.draw_lengths(spec_, np.random.default_rng(0), 20000)
    assert x.min() >= spec_.get("min", spec_.get("value"))
    assert x.max() <= spec_.get("max", spec_.get("value"))
    assert abs(np.median(x) / median - 1) < 0.03


def test_zipf_prefix_popularity():
    mix = spec.load_traffic("household-chat")
    plan = traffic.make_plan(mix, 5, 600, 1000)
    counts = np.bincount([s.prefix for s in plan.sessions], minlength=8)
    w = 1 / np.arange(1, 9) ** mix["shared_prefixes"]["zipf_s"]
    assert np.allclose(counts / counts.sum(), w / w.sum(), atol=0.03)
    assert all(len(p) == 256 for p in plan.prefixes)


def test_exponential_gaps_match_the_rate():
    mix = dict(spec.load_traffic("household-chat"), rate_per_s=5.0)
    plan = traffic.make_plan(mix, 9, 400, 1000)
    gaps = np.diff([s.arrival_s for s in plan.sessions])
    assert abs(gaps.mean() * 5.0 - 1) < 0.05
    assert abs(np.std(gaps) / gaps.mean() - 1) < 0.1   # exponential: cv 1
