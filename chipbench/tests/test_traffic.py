"""The traffic generator: deterministic per seed, the stated distribution
and clip range, and the same multiset of sizes and gaps for every seed;
mixtures, open-loop and on/off arrivals and shared prefixes come from a
mix's data alone."""
import math
import statistics
from collections import Counter

import numpy as np
import pytest

from chipbench import traffic

VOCAB = 151_936


def test_lengths_follow_the_stated_distribution():
    d = traffic.load("decode_backlog")["prompt_tokens"]
    sizes = traffic.sizes(d, 4001)
    assert min(sizes) >= d["min"] and max(sizes) <= d["max"]
    step = d.get("round_to", 1)
    assert all(s % step == 0 for s in sizes)
    # rounding up moves the median by less than one step
    assert d["median"] <= statistics.median(sizes) < d["median"] + step
    # the log-spread of the unclipped middle matches sigma
    q1, q3 = np.percentile(sizes, [25, 75])
    assert math.log(q3 / q1) == pytest.approx(1.349 * d["sigma"], rel=0.15)


def test_decode_backlog_is_deterministic_and_seed_only_orders_it():
    mix = traffic.load("decode_backlog")
    a = traffic.requests(mix, 3_000_000_000, VOCAB)
    b = traffic.requests(mix, 3_000_000_000, VOCAB)
    c = traffic.requests(mix, 2**33 + 7, VOCAB)
    assert len(a) == mix["block"] * mix["blocks"]
    assert all((x.prompt == y.prompt).all() and x.max_new == y.max_new
               for x, y in zip(a, b))
    sizes = Counter((len(r.prompt), r.max_new) for r in a)
    assert sizes == Counter((len(r.prompt), r.max_new) for r in c)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in c]
    n = mix["block"]               # every block holds the whole multiset
    assert Counter((len(r.prompt), r.max_new) for r in a[:n]) == \
        Counter((len(r.prompt), r.max_new) for r in a[n:2 * n])
    o = mix["output_tokens"]
    assert all(o["min"] <= r.max_new <= o["max"] for r in a)
    assert all(len(r.prompt) + r.max_new <= mix["decode_ctx"] for r in a)
    assert all(r.prompt.max() < VOCAB and r.due_s == 0.0 for r in a)
    assert {len(r.prompt) for r in a} == set(traffic.prompt_lengths(mix))


def test_two_mode_mixture_keeps_each_share():
    d = {"mix": [{"share": 3, "median": 3000, "sigma": 0.2, "min": 2048,
                  "max": 4096},
                 {"share": 1, "median": 96, "sigma": 0.5, "min": 32,
                  "max": 256}]}
    sizes = traffic.sizes(d, 40)
    assert sum(s >= 2048 for s in sizes) == 30
    assert sum(32 <= s <= 256 for s in sizes) == 10


@pytest.mark.parametrize("process", ["poisson", "on_off"])
def test_open_loop_arrivals(process):
    rate, secs = 4.0, 40.0
    mix = traffic.load("decode_backlog") | {"blocks": None}
    del mix["blocks"]
    mix["arrivals"] = {"process": process, "rate_per_s": rate,
                       "burst": 4.0, "period_s": 8.0}
    a = traffic.requests(mix, 11, VOCAB, seconds=secs)
    c = traffic.requests(mix, 12, VOCAB, seconds=secs)
    n = mix["block"]
    assert len(a) == len(c) == n * int(secs * rate / n)
    due = [r.due_s for r in a]
    assert due == sorted(due)
    # whole blocks end at the same moment for every seed, the mean rate
    # kept (to within one on/off period)
    assert due[-1] == pytest.approx(c[-1].due_s)
    assert abs(due[-1] - len(a) / rate) <= 8.0
    if process == "on_off":
        # every arrival lies in the first quarter of its 8 s period
        assert all(math.fmod(t, 8.0) <= 2.0 + 1e-9 for t in due)


def test_shared_prefix_groups():
    mix = traffic.load("decode_backlog") | {
        "shared_prefix": {"tokens": 64, "groups": 4}, "blocks": 2}
    reqs = traffic.requests(mix, 5, VOCAB)
    by_group = {}
    for r in reqs:
        by_group.setdefault(r.group, []).append(r.prompt[:64])
    assert sorted(by_group) == [0, 1, 2, 3]
    for heads in by_group.values():
        assert all((h == heads[0]).all() for h in heads)
    assert not (by_group[0][0] == by_group[1][0]).all()
    assert min(traffic.prompt_lengths(mix)) == 64 + min(
        traffic.sizes(mix["prompt_tokens"], mix["block"]))
