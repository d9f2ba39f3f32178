"""The replayed request set and the paced arrivals."""
import collections
import json
import math
import os

import pytest

from preset_tree import ROOT
from perfbench.harness import shapes

def _traffic(*parts):
    with open(os.path.join(ROOT, "perfbench", *parts)) as f:
        return json.load(f)


# the chat mix is kept under unproven/ until its cell repeats (PERF.md)
CHAT = _traffic("unproven", "chat-steady.json")
BATCH = _traffic("traffic", "longprompt-batch.json")


@pytest.mark.parametrize("traffic", [CHAT, BATCH], ids=["chat", "batch"])
def test_same_multiset_for_two_seeds_in_another_order(traffic):
    base = shapes.replayed_set(traffic["prompt"], traffic["output"], 101,
                               traffic["shape_seed"])
    a = shapes.seeded_order(base, 1, "measured")
    b = shapes.seeded_order(base, 2 ** 31 + 7, "measured")
    assert collections.Counter(a) == collections.Counter(b) \
        == collections.Counter(base)
    assert a != b
    assert shapes.seeded_order(base, 1, "measured") == a


def test_replayed_set_does_not_depend_on_anything_but_its_arguments():
    args = (CHAT["prompt"], CHAT["output"], 64, CHAT["shape_seed"])
    assert shapes.replayed_set(*args) == shapes.replayed_set(*args)
    other = shapes.replayed_set(*args[:3], CHAT["shape_seed"] + 1)
    assert sorted(p for p, _ in other) == sorted(p for p, _ in
                                                 shapes.replayed_set(*args))
    assert other != shapes.replayed_set(*args)


@pytest.mark.parametrize("side", ["prompt", "output"])
def test_lognormal_quantiles_match_the_stated_distribution(side):
    dist = CHAT[side]
    n = 2001
    vals = sorted(shapes.quantile(dist, (i + 0.5) / n) for i in range(n))
    assert vals[n // 2] == dist["median"]
    # the 84.13th percentile of a log-normal is median * exp(sigma)
    want = dist["median"] * math.exp(dist["sigma"])
    got = vals[int(0.8413 * n)]
    assert abs(got - want) / want < 0.01
    assert vals[0] >= dist["min"] and vals[-1] <= dist["max"]
    assert vals[0] == dist["min"] and vals[-1] == dist["max"]


def test_uniform_quantiles_cover_the_range_evenly():
    dist = BATCH["prompt"]
    n = 449
    vals = [shapes.quantile(dist, (i + 0.5) / n) for i in range(n)]
    assert vals == sorted(vals)
    assert min(vals) >= dist["min"] and max(vals) <= dist["max"]
    assert abs(sum(vals) / n - (dist["min"] + dist["max"]) / 2) < 1.0


def test_batch_requests_fit_the_context():
    pairs = shapes.replayed_set(BATCH["prompt"], BATCH["output"],
                                BATCH["set_size"], BATCH["shape_seed"])
    assert max(p + o for p, o in pairs) <= 1024
    pairs = shapes.replayed_set(CHAT["prompt"], CHAT["output"], 500,
                                CHAT["shape_seed"])
    assert max(p + o for p, o in pairs) <= 1024


def test_unknown_distribution_is_an_error():
    with pytest.raises(ValueError):
        shapes.quantile({"kind": "zipf", "min": 1, "max": 2}, 0.5)


@pytest.mark.parametrize("seed", [0, 5, 2 ** 31 + 11])
def test_paced_arrivals_stay_in_their_slots(seed):
    rate, n, start = 2.25, 101, 15.0
    due = shapes.paced_arrivals(n, rate, 0.2, seed, "measured", start)
    assert len(due) == n and due == sorted(due)
    for k, t in enumerate(due):
        assert start + (k + 0.3) / rate <= t <= start + (k + 0.7) / rate
    assert due[-1] < start + n / rate
    assert due == shapes.paced_arrivals(n, rate, 0.2, seed, "measured",
                                        start)
    assert due != shapes.paced_arrivals(n, rate, 0.2, seed + 1, "measured",
                                        start)
