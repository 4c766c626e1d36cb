"""The traffic generator offers the same work whatever the seed."""
import json
import os
from collections import Counter

import pytest

from harness import traffic

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def mix(name):
    with open(os.path.join(BENCH_DIR, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["docs-batch", "chat-open"])
def test_same_multiset_other_order_other_ids(name):
    p = mix(name)
    a, b = traffic.order(p, 1), traffic.order(p, 3_000_000_001)
    assert Counter(a) == Counter(b) == Counter(map(tuple, p["pairs"]))
    assert a != b
    assert traffic.order(p, 1) == a           # the seed fixes the order
    ta = traffic.prompt_tokens(1, 0, a[0][0], 32768)
    tb = traffic.prompt_tokens(3_000_000_001, 0, a[0][0], 32768)
    assert len(ta) == a[0][0] and ta != tb
    assert ta == traffic.prompt_tokens(1, 0, a[0][0], 32768)
    assert all(1 <= t < 32768 for t in ta)
    # two requests of one run share no prefix block
    assert traffic.prompt_tokens(1, 1, 64, 32768)[:16] != ta[:16]


@pytest.mark.parametrize("name", ["docs-batch", "chat-open"])
def test_every_round_holds_every_prompt_length(name):
    p = mix(name)
    n = p["round"]
    prompts = sorted({q for q, _ in p["pairs"]})
    for seed in (0, 7):
        o = traffic.order(p, seed)
        for i in range(0, len(o), n):
            assert sorted(q for q, _ in o[i:i + n]) == prompts


def test_open_loop_due_times_are_the_seeded_schedule():
    p = dict(mix("chat-open"), rate_per_s=2.0, arrival_gaps=100)
    g = traffic.gaps(p)
    assert len(g) == 100 and sum(g) == pytest.approx(50.0)
    # exponential quantiles: the median gap is ln 2 / rate, about
    assert sorted(g)[50] == pytest.approx(0.6931 / 2.0, rel=0.05)
    a = traffic.arrivals(p, 5, 500.0)
    assert a == traffic.arrivals(p, 5, 500.0)
    assert a != traffic.arrivals(p, 6, 500.0)
    assert all(x < y for x, y in zip(a, a[1:])) and a[-1] <= 500.0
    assert len(a) in (999, 1000)        # exactly the rate, every seed
    # every seed offers the same multiset of gaps, cycle by cycle
    for seed in (5, 3_000_000_001):
        due = [0.0] + traffic.arrivals(p, seed, 50.0 + 1e-6)
        got = sorted(y - x for x, y in zip(due, due[1:]))
        assert got == pytest.approx(sorted(g))
    # a longer horizon extends the same schedule
    assert traffic.arrivals(p, 5, 600.0)[:len(a)] == a


def test_stagger_is_seeded_and_bounded():
    p = mix("docs-batch")
    s = traffic.staggers(p, 9)
    assert len(s) == p["callers"] and s == traffic.staggers(p, 9)
    assert all(0 <= x <= p["stagger_s"] for x in s)


def test_tokens_are_counted_as_produced_and_clipped():
    """serve_tok_s: prompt tokens at the first token, output tokens as
    received, only what falls inside the window."""
    from harness import spec
    from harness.loadgen import Request
    read = spec.reader("client_tokens_per_s")

    def req(prompt, times):
        r = Request(0, prompt, len(times), 0.0)
        r.t_tokens = list(times)
        return r
    window = (10.0, 20.0)
    reqs = [
        req(1000, [9.0, 9.5, 10.5, 11.0]),      # prefilled before: 2 out
        req(2000, [12.0, 13.0, 19.9, 20.0]),    # prompt + 3 (20.0 is out)
        req(3000, [20.5, 21.0]),                # all after: nothing
        req(500, []),                           # still queued: nothing
    ]
    got = read({"window": window, "requests": reqs})
    assert got == pytest.approx((2 + 2000 + 3) / 10.0)
