"""The one traffic generator. A mix is a data file; the seed never
changes WHAT is offered, only the order, the token ids, the callers'
first-send stagger and (open loop) the arrival instants.

File keys:
  kind            "closed" (callers each wait for their reply) or
                  "open" (arrivals on a schedule, whatever the server
                  does)
  pairs           the fixed list of [prompt_tokens, output_tokens]
  callers         closed: how many callers
  stagger_s       closed: first sends are spread over this many seconds
  stationary_start  closed: this many callers (the earliest to send:
                  those the server takes in at once) have their FIRST
                  reply cut to a seeded share of its length, as if the
                  run had joined a job under way, so replies end out of
                  step from the start (the cut ones end in the lead-in)
  rate_per_s      open: arrival rate
  arrival_gaps    open: how many exponential-quantile gaps make one
                  cycle (the same multiset for every seed, shuffled)
  warm_shapes     [[prompt_tokens, output_tokens], ...] sent once each
                  before anything is timed, so every program the mix
                  uses is compiled during set-up
  steady_s        seconds of the same load before the window opens
"""

from __future__ import annotations

import math
import random


def order(params: dict, seed: int) -> list:
    """The fixed list of (prompt, output) pairs in this seed's order.
    The list is cut into consecutive rounds of ``round`` pairs (one of
    every stratum when the file lists them so); the seed shuffles the
    rounds and the pairs inside each, so any stretch of the cycle
    carries nearly the same mix whatever the seed."""
    pairs = [tuple(p) for p in params["pairs"]]
    n = int(params.get("round", len(pairs)))
    rng = random.Random(seed)
    rounds = [pairs[i:i + n] for i in range(0, len(pairs), n)]
    rng.shuffle(rounds)
    out = []
    for r in rounds:
        r = list(r)
        rng.shuffle(r)
        out.extend(r)
    return out


def prompt_tokens(seed: int, index: int, n: int, vocab: int) -> list:
    """Token ids of request ``index``: distinct per request, so no two
    prompts share a prefix block beyond chance."""
    rng = random.Random((seed * 1_000_003 + index) & 0xFFFFFFFFFFFF)
    return [rng.randrange(1, vocab) for _ in range(n)]


def gaps(params: dict) -> list:
    """Open loop: the fixed multiset of inter-arrival gaps of one cycle
    - ``arrival_gaps`` quantiles of the exponential distribution at
    ``rate_per_s``, scaled so a cycle lasts exactly arrival_gaps / rate
    seconds. Every seed offers these same gaps, in another order."""
    n, rate = int(params["arrival_gaps"]), float(params["rate_per_s"])
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = n / rate / sum(raw)
    return [g * scale for g in raw]


def arrivals(params: dict, seed: int, horizon_s: float) -> list:
    """Open loop: due times (seconds from the start of load) up to
    ``horizon_s``: cycle after cycle of the same gaps, each cycle in an
    order the seed draws - a Poisson process's gaps with its count per
    cycle held fixed."""
    rng = random.Random(seed ^ 0x5EED)
    cycle = gaps(params)
    t, out = 0.0, []
    while True:
        order_ = list(cycle)
        rng.shuffle(order_)
        for g in order_:
            t += g
            if t > horizon_s:
                return out
            out.append(t)


def staggers(params: dict, seed: int) -> list:
    """Closed loop: each caller's first-send delay."""
    rng = random.Random(seed ^ 0xCA11)
    return [rng.uniform(0.0, float(params.get("stagger_s", 0.0)))
            for _ in range(int(params["callers"]))]


def first_cuts(params: dict, seed: int) -> list:
    """Closed loop: the share of its output length each caller's first
    reply keeps, in the callers' order (1.0 = whole)."""
    n = int(params["callers"])
    k = int(params.get("stationary_start", 0))
    delays = staggers(params, seed)
    rng = random.Random(seed ^ 0xF1257)
    shares = [(i + rng.random()) / max(k, 1) for i in range(k)]
    rng.shuffle(shares)
    cuts = [1.0] * n
    for share, caller in zip(shares, sorted(range(n),
                                            key=delays.__getitem__)):
        cuts[caller] = share
    return cuts
