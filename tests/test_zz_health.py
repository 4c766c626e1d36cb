"""Cluster health plane: time-series store math, SLO burn-rate
evaluation, regression sentinels, lint/knob coverage, worker final
metrics flush, CLI/endpoint surfaces — plus a slow live-cluster e2e
where an injected TTFT degradation (chaos delay at the replica) fires
the fast-burn page-tier alert with a resolvable exemplar trace id and
recovery clears it. (Late-alphabet name keeps the tier-1 cutoff
stable.)

Every window/burn test drives an injectable clock — no wall-clock
sleeps in the fast tier.
"""

import asyncio
import http.client
import importlib.util
import json
import os
import threading
import time

import pytest

from ray_tpu.config import Config
from ray_tpu.util import events
from ray_tpu.util import health as H
from ray_tpu.util import metrics as M
from ray_tpu.util.timeseries import (TimeSeriesStore, _bucket_quantile,
                                     _labels_key)


class FakeClock:
    def __init__(self, t0=1000.0):
        self.t = t0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt
        return self.t


def _store(clock, **kw):
    kw.setdefault("window_s", 10.0)
    kw.setdefault("retention_s", 900.0)
    return TimeSeriesStore(clock=clock, **kw)


# --- time-series store math -------------------------------------------------


def test_counter_gauge_ingest_and_query():
    clk = FakeClock()
    s = _store(clk)
    s.ingest_counter("reqs_total", {"dep": "a"}, 0.0, source="w1")
    for i in range(6):
        clk.advance(10.0)
        s.ingest_counter("reqs_total", {"dep": "a"}, (i + 1) * 5.0,
                         source="w1")
        s.ingest_gauge("depth", {"dep": "a"}, float(i))
    q = s.query("reqs_total", since_s=120.0)
    assert q["kind"] == "counter"
    # 5 increments per 10s window -> 0.5/s in each full window
    assert all(abs(p["rate"] - 0.5) < 1e-9 for p in q["points"])
    g = s.query("depth", since_s=120.0)
    assert g["kind"] == "gauge"
    assert [p["value"] for p in g["points"]] == [0, 1, 2, 3, 4, 5]
    assert g["points"][-1]["min"] == 5 and g["points"][-1]["max"] == 5
    # label-subset selection: an unmatched selector returns nothing
    assert s.query("reqs_total", 120.0, {"dep": "b"})["series"] == 0
    assert s.query("reqs_total", 120.0, {"dep": "a"})["series"] == 1


def test_counter_rollup_preserves_monotonic_increments():
    """The downsample contract: summed 1-min rollup increments equal
    summed raw increments over the same span, and a counter RESET
    (worker restart) contributes the post-reset value — never a
    negative increment at any resolution."""
    clk = FakeClock(t0=10_000.0)
    s = _store(clk)
    total = 0.0
    cum = 0.0
    for i in range(30):          # 5 minutes of 10s pushes
        clk.advance(10.0)
        if i == 17:              # restart: cumulative drops to 3
            cum = 3.0
        else:
            cum += 7.0
        s.ingest_counter("work_total", None, cum, source="w1")
        # the store's FIRST sight (i=0) is a baseline, not an
        # increment — a long-lived source joining a fresh store must
        # not dump its lifetime count into one window
        if i != 0:
            total += 3.0 if i == 17 else 7.0
    raw = s.window("work_total", 300.0)
    assert raw["kind"] == "counter"
    assert abs(raw["inc"] - total) < 1e-9
    # every stored window at every resolution is non-negative
    key = ("work_total", _labels_key(None))
    series = s._series[key]
    for ring in series.rings:
        for b in ring:
            assert b.get("inc", 0.0) >= 0.0
    # rollup sum == raw sum over the full span (same deltas, coarser
    # alignment — reconstructed cumulative stays monotone everywhere)
    raw_sum = sum(b.get("inc", 0.0) for b in series.rings[0])
    mid_sum = sum(b.get("inc", 0.0) for b in series.rings[1])
    assert abs(raw_sum - mid_sum) < 1e-9
    assert abs(raw_sum - total) < 1e-9


def test_histogram_mergeability_quantile_over_window():
    """quantile(window) == quantile(merged buckets): identical at raw
    and rollup resolutions because both store the same per-window
    bucket DELTAS (prometheus cumulative-le unstacked at ingest)."""
    clk = FakeClock(t0=50_000.0)
    s = _store(clk)
    bounds = (0.1, 0.25, 0.5, 1.0)
    cum = [0, 0, 0, 0, 0]
    csum = 0.0
    for i in range(24):          # 4 minutes of pushes
        clk.advance(10.0)
        # 8 fast (le .1), 2 slow (le 1.0) per push
        cum[0] += 8
        cum[3] += 2
        csum += 8 * 0.05 + 2 * 0.8
        s.ingest_hist("lat_s", {"dep": "x"}, bounds, list(cum), csum,
                      source="w1")
    # 24 pushes, the first is a baseline -> 23 increments recorded
    w = s.window("lat_s", 240.0, {"dep": "x"})
    assert w["count"] == 230
    assert w["counts"][0] == 184 and w["counts"][3] == 46
    p50 = s.quantile("lat_s", 0.5, 240.0, {"dep": "x"})
    assert p50 is not None and p50 <= 0.1
    p95 = s.quantile("lat_s", 0.95, 240.0, {"dep": "x"})
    assert 0.5 < p95 <= 1.0
    # same answer from the 1-min rollup ring (mergeable deltas)
    key = ("lat_s", _labels_key({"dep": "x"}))
    series = s._series[key]
    merged = [0.0] * 5
    for b in series.rings[1]:
        for i, c in enumerate(b.get("counts") or []):
            merged[i] += c
    assert merged == w["counts"]
    assert abs(_bucket_quantile(bounds, merged, 0.95) - p95) < 1e-9


def test_bucket_quantile_interpolation():
    bounds = (1.0, 2.0, 4.0)
    counts = [10, 10, 0, 0]
    assert _bucket_quantile(bounds, counts, 0.5) == pytest.approx(1.0)
    assert _bucket_quantile(bounds, counts, 0.75) == pytest.approx(1.5)
    # overflow bucket clamps to the largest boundary
    assert _bucket_quantile(bounds, [0, 0, 0, 5], 0.99) == 4.0
    assert _bucket_quantile((), [], 0.5) == 0.0


def test_ring_eviction_order_and_series_memory_bound():
    clk = FakeClock(t0=0.0)
    s = _store(clk, window_s=10.0, retention_s=100.0, max_series=3)
    # fill 3x the raw retention: only the newest windows survive,
    # evicted strictly oldest-first
    for i in range(30):
        clk.advance(10.0)
        s.ingest_gauge("g", None, float(i))
    ring = s._series[("g", ())].rings[0]
    ts = [b["t"] for b in ring]
    assert ts == sorted(ts)
    assert len(ring) == ring.maxlen
    assert ts[0] >= clk.t - 110.0    # oldest retained is recent
    # series bound: 4th distinct series evicts the least-recently
    # updated one
    s.ingest_gauge("a", None, 1.0)
    s.ingest_gauge("b", None, 1.0)
    clk.advance(10.0)
    s.ingest_gauge("g", None, 99.0)    # refresh g
    s.ingest_gauge("c", None, 1.0)     # 4th: evicts a or b, never g
    assert s.series_count() == 3
    assert s.dropped_series_total == 1
    assert ("g", ()) in s._series and ("c", ()) in s._series


def test_ingest_text_counters_gauges_hists_and_exemplars():
    clk = FakeClock(t0=5_000.0)
    s = _store(clk)
    text = "\n".join([
        'reqs_total{node="n1",dep="a"} 10',
        'depth{node="n1"} 3',
        'lat_s_bucket{node="n1",le="0.25"} 4',
        'lat_s_bucket{node="n1",le="1"} 9 '
        '# {trace_id="cafe01"} 0.8 4999.5',
        'lat_s_bucket{node="n1",le="+Inf"} 10',
        'lat_s_sum{node="n1"} 3.5',
        'lat_s_count{node="n1"} 10',
        '# HELP ignored comment',
    ])
    s.ingest_text("w1", text)
    clk.advance(10.0)
    s.ingest_text("w1", text.replace(" 10", " 30")
                  .replace('le="0.25"} 4', 'le="0.25"} 8')
                  .replace('le="1"} 9', 'le="1"} 19'))
    w = s.window("reqs_total", 60.0)
    # first push (10) is the baseline; second (30) -> increment 20
    assert w["kind"] == "counter" and w["inc"] == 20.0
    g = s.window("depth", 60.0)
    assert g["kind"] == "gauge" and g["last"] == 3.0
    h = s.window("lat_s", 60.0)
    assert h["kind"] == "histogram"
    assert h["boundaries"] == [0.25, 1.0]
    # first push [4,5,1] is the baseline; second unstacks cumulative
    # 8/19/30 -> [8,11,11], recorded delta [4,6,10]
    assert h["counts"] == [4.0, 6.0, 10.0]
    # the exemplar rode the bucket line into the window, index 1 (le=1)
    assert 1 in h["exemplars"]
    assert h["exemplars"][1][0] == "cafe01"
    q = s.quantile("lat_s", 0.5, 60.0)
    assert 0.25 < q <= 1.0


def test_ingest_registry_roundtrip_through_rendered_text():
    """A real metrics.Histogram rendered by render_labeled parses back
    into the store (the worker-push path end to end, in-process).
    Two pushes: the first is the store's baseline, the deltas between
    them are what lands in windows."""
    clk = FakeClock(t0=9_000.0)
    s = _store(clk)
    h = M.Histogram("zz_health_rt_s", "roundtrip test",
                    boundaries=(0.1, 1.0))
    c = M.Counter("zz_health_rt_total", "roundtrip test")
    h.observe(0.02, {"dep": "a"})
    c.inc(1.0)
    s.ingest_text("w9", M.render_labeled({"node": "n9"}))  # baseline
    clk.advance(10.0)
    h.observe(0.05, {"dep": "a"})
    h.observe(0.7, {"dep": "a"}, exemplar="beef02")
    c.inc(4.0)
    s.ingest_text("w9", M.render_labeled({"node": "n9"}))
    w = s.window("zz_health_rt_s", 60.0, {"dep": "a"})
    assert w is not None and w["count"] == 2
    assert w["exemplars"] and any(
        e[0] == "beef02" for e in w["exemplars"].values())
    cw = s.window("zz_health_rt_total", 60.0)
    assert cw["inc"] == 4.0
    # local registry ingestion: same two-phase contract
    s2 = _store(clk)
    s2.ingest_registry()
    h.observe(0.3, {"dep": "a"})
    clk.advance(10.0)
    s2.ingest_registry()
    w2 = s2.window("zz_health_rt_s", 60.0, {"dep": "a"})
    assert w2 is not None and w2["count"] == 1


def test_big_counter_renders_full_precision_for_delta_math():
    """%g rendering would freeze a pushed counter at '1e+07' and the
    store's deltas (and availability burn rates) would read 0 — the
    push path must render full precision."""
    clk = FakeClock(t0=11_000.0)
    s = _store(clk)
    c = M.Counter("zz_health_big_total", "precision test")
    c.inc(10_000_000.0)
    s.ingest_text("wb", M.render_labeled({"node": "nb"}))  # baseline
    clk.advance(10.0)
    c.inc(40.0)
    text = M.render_labeled({"node": "nb"})
    assert "10000040" in text, text.splitlines()[:3]
    s.ingest_text("wb", text)
    w = s.window("zz_health_big_total", 60.0)
    assert w["inc"] == 40.0


# --- SLO engine -------------------------------------------------------------


def _cfg(**kw):
    kw.setdefault("slo_fast_windows_s", "30,120")
    kw.setdefault("slo_slow_windows_s", "120,600")
    kw.setdefault("slo_fast_burn", 10.0)
    kw.setdefault("slo_slow_burn", 2.0)
    kw.setdefault("slo_default_objectives", False)
    return Config(**kw)


def _push_lat(s, clk, dep, n_fast, n_slow, cum, bounds=(0.25, 1.0)):
    """One push of the serve handler histogram: n_fast requests at
    ~0.1s, n_slow at ~0.8s (cumulative state threaded by caller)."""
    cum["f"] += n_fast
    cum["s"] += n_slow
    cum["sum"] += n_fast * 0.1 + n_slow * 0.8
    s.ingest_hist("serve_proxy_handler_s", {"deployment": dep}, bounds,
                  [cum["f"], cum["s"], 0.0], cum["sum"], source="w1",
                  exemplars={1: ("abad1dea", 0.8, clk.t)}
                  if n_slow else None)


def test_burn_rate_multi_window_deterministic():
    """Fast-burn page alert needs BOTH fast windows over threshold:
    a short bad burst trips the 30s window but not the 120s one (no
    page); sustained badness trips both (page fires, event recorded,
    exemplar attached); recovery resolves it. Injectable clock, zero
    sleeps."""
    clk = FakeClock(t0=100_000.0)
    s = _store(clk)
    obj = H.Objective(name="lat:a", kind="latency",
                      metric="serve_proxy_handler_s",
                      labels={"deployment": "a"}, threshold_s=0.25,
                      target=0.99, deployment="a")
    eng = H.HealthEngine(s, _cfg(), clock=clk, objectives=[obj])
    cum = {"f": 0, "s": 0, "sum": 0.0}
    # 2 minutes healthy
    for _ in range(12):
        clk.advance(10.0)
        _push_lat(s, clk, "a", n_fast=10, n_slow=0, cum=cum)
    snap = eng.evaluate()
    page = snap["objectives"][0]["tiers"]["page"]
    assert page["burn_short"] == 0.0 and not page["firing"]
    # one bad 10s window: short window burns, long window diluted
    clk.advance(10.0)
    _push_lat(s, clk, "a", n_fast=0, n_slow=10, cum=cum)
    snap = eng.evaluate()
    page = snap["objectives"][0]["tiers"]["page"]
    assert page["burn_short"] >= 10.0          # 1/3 bad over 30s
    assert not page["firing"]                  # 120s window saved us
    assert not [a for a in snap["alerts"] if a["tier"] == "page"]
    # sustained: 2 more bad minutes -> both windows over threshold
    fired_at = None
    for i in range(12):
        clk.advance(10.0)
        _push_lat(s, clk, "a", n_fast=0, n_slow=10, cum=cum)
        snap = eng.evaluate()
        if snap["objectives"][0]["tiers"]["page"]["firing"]:
            fired_at = i
            break
    assert fired_at is not None, "page alert never fired"
    assert ("lat:a", "page", "firing") in snap["transitions"]
    assert snap["alerts"] and snap["alerts"][0]["tier"] == "page"
    # exemplar from the breaching bucket names a concrete trace
    assert snap["alerts"][0]["exemplar"] == "abad1dea"
    assert snap["burn_advice"]["a"]["latency_burning"]
    assert snap["burn_advice"]["a"]["tier"] == "page"
    # the transition landed in the "health" event category
    evs = [e for e in events.dump() if e.get("cat") == "health"
           and e.get("objective") == "lat:a"
           and e.get("state") == "firing"]
    assert evs and evs[-1].get("trace") == "abad1dea"
    assert evs[-1].get("tier") == "page"
    # recovery: healthy traffic until both windows drain
    resolved = False
    for _ in range(30):
        clk.advance(10.0)
        _push_lat(s, clk, "a", n_fast=10, n_slow=0, cum=cum)
        snap = eng.evaluate()
        if ("lat:a", "page", "resolved") in snap["transitions"]:
            resolved = True
            break
    assert resolved, "alert never resolved after recovery"
    # page tier is clear (the warn tier's 600s window legitimately
    # remembers the incident longer)
    assert not [a for a in snap["alerts"] if a["tier"] == "page"]
    assert any(e.get("cat") == "health" and e.get("state") == "resolved"
               and e.get("objective") == "lat:a"
               for e in events.dump())


def test_availability_burn_counts_5xx_over_total():
    clk = FakeClock(t0=200_000.0)
    s = _store(clk)
    obj = H.Objective(
        name="avail:a", kind="availability",
        metric="serve_requests_total",
        labels={"deployment": "a"}, target=0.99,
        bad_labels=[{"deployment": "a", "code": c}
                    for c in ("500", "503", "504")],
        deployment="a")
    eng = H.HealthEngine(s, _cfg(), clock=clk, objectives=[obj])
    ok = bad = 0
    for i in range(18):         # 3 minutes; 5xx storm from minute 2
        clk.advance(10.0)
        ok += 10
        s.ingest_counter("serve_requests_total",
                         {"deployment": "a", "code": "200"}, ok,
                         source="w1")
        if i >= 12:
            bad += 10
            s.ingest_counter("serve_requests_total",
                             {"deployment": "a", "code": "503"}, bad,
                             source="w1")
    snap = eng.evaluate()
    page = snap["objectives"][0]["tiers"]["page"]
    assert page["firing"], snap["objectives"][0]
    assert snap["burn_advice"]["a"]["availability_burning"]
    # and a clean deployment's objective stays quiet
    s.ingest_counter("serve_requests_total",
                     {"deployment": "b", "code": "200"}, 50,
                     source="w1")
    obj_b = H.Objective(
        name="avail:b", kind="availability",
        metric="serve_requests_total",
        labels={"deployment": "b"}, target=0.99,
        bad_labels=[{"deployment": "b", "code": "500"}],
        deployment="b")
    eng.add_objective(obj_b)
    clk.advance(10.0)
    s.ingest_counter("serve_requests_total",
                     {"deployment": "b", "code": "200"}, 90,
                     source="w1")
    snap = eng.evaluate()
    rows = {o["name"]: o for o in snap["objectives"]}
    assert not rows["avail:b"]["tiers"]["page"]["firing"]


def test_gauge_objective_sustained_straggler():
    """allreduce_straggler_rank: -1 healthy; a rank flagged over BOTH
    windows fires (burn inf); one blip does not."""
    clk = FakeClock(t0=300_000.0)
    s = _store(clk)
    obj = H.Objective(name="straggler", kind="gauge",
                      metric="allreduce_straggler_rank",
                      threshold=-0.5, direction="above")
    eng = H.HealthEngine(s, _cfg(), clock=clk, objectives=[obj])
    for _ in range(13):
        clk.advance(10.0)
        s.ingest_gauge("allreduce_straggler_rank", None, -1.0)
    clk.advance(10.0)
    s.ingest_gauge("allreduce_straggler_rank", None, 2.0)   # one blip
    snap = eng.evaluate()
    assert not snap["objectives"][0]["tiers"]["page"]["firing"]
    for _ in range(13):         # sustained: rank 2 stuck for 130s
        clk.advance(10.0)
        s.ingest_gauge("allreduce_straggler_rank", None, 2.0)
    snap = eng.evaluate()
    assert snap["objectives"][0]["tiers"]["page"]["firing"]
    assert snap["objectives"][0]["tiers"]["page"]["burn_short"] == -1.0
    # a firing gauge alert's snapshot is STRICT JSON: inf is encoded
    # as -1 everywhere (allow_nan=False raises on a raw Infinity)
    json.dumps(snap, allow_nan=False)
    assert snap["alerts"] and snap["alerts"][0]["burn_short"] == -1.0


def test_gauge_ratio_worst_device_decides():
    """One saturated device among idle ones must fire hbm_headroom:
    the ratio is per numerator series (its own divisor), worst wins —
    merging used bytes across devices would hide the hot one."""
    clk = FakeClock(t0=350_000.0)
    s = _store(clk)
    obj = H.Objective(name="hbm", kind="gauge_ratio",
                      metric="device_hbm_used_bytes",
                      divisor_metric="device_hbm_limit_bytes",
                      threshold=0.92, direction="above")
    eng = H.HealthEngine(s, _cfg(), clock=clk, objectives=[obj])
    for _ in range(14):
        clk.advance(10.0)
        for d in range(4):
            used = 9.7e9 if d == 0 else 4.0e9   # device 0 at 97%
            s.ingest_gauge("device_hbm_used_bytes",
                           {"device": f"tpu:{d}"}, used)
            s.ingest_gauge("device_hbm_limit_bytes",
                           {"device": f"tpu:{d}"}, 10e9)
    snap = eng.evaluate()
    assert snap["objectives"][0]["tiers"]["page"]["firing"], \
        snap["objectives"][0]
    # all devices healthy -> clears
    for _ in range(14):
        clk.advance(10.0)
        for d in range(4):
            s.ingest_gauge("device_hbm_used_bytes",
                           {"device": f"tpu:{d}"}, 4.0e9)
            s.ingest_gauge("device_hbm_limit_bytes",
                           {"device": f"tpu:{d}"}, 10e9)
    snap = eng.evaluate()
    assert not snap["objectives"][0]["tiers"]["page"]["firing"]


def test_firing_alert_resolves_when_objective_vanishes():
    """A paged objective whose series disappear (deployment deleted /
    LRU-evicted) resolves instead of burning forever."""
    clk = FakeClock(t0=360_000.0)
    s = _store(clk)
    obj = H.Objective(name="lat:gone", kind="latency",
                      metric="serve_proxy_handler_s",
                      labels={"deployment": "gone"}, threshold_s=0.25,
                      target=0.99, deployment="gone")
    eng = H.HealthEngine(s, _cfg(), clock=clk, objectives=[obj])
    cum = {"f": 0, "s": 0, "sum": 0.0}
    for _ in range(14):
        clk.advance(10.0)
        _push_lat(s, clk, "gone", 0, 10, cum)
    snap = eng.evaluate()
    assert snap["alerts"] and snap["alerts"][0]["tier"] == "page"
    # the objective disappears (user deregistration here; derived
    # objectives vanish the same way when their series evict)
    eng.objectives = []
    snap = eng.evaluate()
    assert snap["alerts"] == []
    assert ("lat:gone", "page", "resolved") in snap["transitions"]
    assert any(e.get("cat") == "health"
               and e.get("objective") == "lat:gone"
               and e.get("reason") == "objective gone"
               for e in events.dump())


def test_deactivate_clears_alert_gauges():
    """deactivate() zeroes the process-global alert/burn gauges — a
    later in-process cluster must not scrape a dead cluster's page as
    still firing."""
    m = H.health_metrics()
    m["active"].set(1.0, tags={"objective": "lat:x", "tier": "page"})
    m["burn"].set(55.0, tags={"objective": "lat:x", "tier": "page"})
    H.deactivate()
    assert m["active"]._values == {}
    assert m["burn"]._values == {}
    # and the cached catalog survives a metrics.reset() (identity
    # check rebuilds it against the fresh registry)
    first = H.health_metrics()
    assert H.health_metrics() is first


def test_consult_health_stamps_cache_before_rpc():
    """The shed advisory must not stampede the head: a stale cache is
    stamped BEFORE the RPC, so concurrent sheds (and post-failure
    retries) within the TTL skip the fetch."""
    from ray_tpu.serve.proxy import HTTPProxy
    p = HTTPProxy.__new__(HTTPProxy)
    p._health_advice = {"ts": 0.0, "state": None}
    # no cluster ctx: the fetch raises inside the advisory and is
    # swallowed — but the stamp must already be in place
    asyncio.run(p._consult_health("dep"))
    assert p._health_advice["ts"] > 0.0


def test_gauge_objective_worst_series_decides():
    """Per-series gauge evaluation: node A's healthy straggler gauge
    (-1) must not mask node B's stuck rank (the two push as distinct
    worker-labelled series)."""
    clk = FakeClock(t0=370_000.0)
    s = _store(clk)
    obj = H.Objective(name="strag", kind="gauge",
                      metric="allreduce_straggler_rank",
                      threshold=-0.5, direction="above")
    eng = H.HealthEngine(s, _cfg(), clock=clk, objectives=[obj])
    for _ in range(14):
        clk.advance(10.0)
        s.ingest_gauge("allreduce_straggler_rank",
                       {"worker": "a"}, -1.0)      # healthy node
        s.ingest_gauge("allreduce_straggler_rank",
                       {"worker": "b"}, 3.0)       # stuck rank
    snap = eng.evaluate()
    assert snap["objectives"][0]["tiers"]["page"]["firing"], \
        snap["objectives"][0]
    # burn gauge reflects the boolean breach as -1, not a stale value
    key = (("objective", "strag"), ("tier", "page"))
    assert eng._m["burn"]._values[key] == -1.0
    assert eng._m["active"]._values[key] == 1.0


def test_resolved_alerts_for_gone_objectives_are_pruned():
    clk = FakeClock(t0=380_000.0)
    s = _store(clk)
    obj = H.Objective(name="lat:churn", kind="latency",
                      metric="serve_proxy_handler_s",
                      labels={"deployment": "churn"}, threshold_s=0.25,
                      target=0.99, deployment="churn")
    eng = H.HealthEngine(s, _cfg(), clock=clk, objectives=[obj])
    cum = {"f": 0, "s": 0, "sum": 0.0}
    for _ in range(14):
        clk.advance(10.0)
        _push_lat(s, clk, "churn", 0, 10, cum)
    eng.evaluate()
    assert any(st["state"] == "firing"
               for st in eng._alerts.values())
    eng.objectives = []          # the objective churns away
    eng.evaluate()               # firing -> resolved
    # the dead objective's gauges are zeroed, not frozen mid-burn
    key = (("objective", "lat:churn"), ("tier", "page"))
    assert eng._m["active"]._values[key] == 0.0
    assert eng._m["burn"]._values[key] == 0.0
    eng.evaluate()               # resolved + gone -> pruned
    assert ("lat:churn", "page") not in eng._alerts
    assert ("lat:churn", "warn") not in eng._alerts


def test_health_json_param_parsed_not_substring_matched():
    from ray_tpu.util.metrics import _wants_json
    assert _wants_json("json=1")
    assert _wants_json("a=b&json=true")
    assert not _wants_json("json=0")
    assert not _wants_json("json=false")
    assert not _wants_json("fmt=jsonp")
    assert not _wants_json("")
    assert not _wants_json(None)


def test_derived_default_objectives_from_observed_series():
    clk = FakeClock(t0=400_000.0)
    s = _store(clk)
    cum = {"f": 0, "s": 0, "sum": 0.0}
    _push_lat(s, clk, "app1", 5, 0, cum)
    s.ingest_counter("serve_requests_total",
                     {"deployment": "app1", "code": "200"}, 5,
                     source="w1")
    s.ingest_gauge("allreduce_straggler_rank", None, -1.0)
    eng = H.HealthEngine(
        s, _cfg(slo_default_objectives=True,
                slo_latency_threshold_s=0.25, slo_target=0.999),
        clock=clk)
    names = {o.name: o for o in eng.active_objectives()}
    assert "latency:app1" in names and "availability:app1" in names
    assert "collective_straggler" in names
    assert names["latency:app1"].threshold_s == 0.25
    assert names["latency:app1"].target == 0.999
    # user-registered objective wins on name collision
    eng.add_objective(H.Objective(name="latency:app1", kind="latency",
                                  metric="serve_proxy_handler_s",
                                  threshold_s=9.0))
    names = {o.name: o for o in eng.active_objectives()}
    assert names["latency:app1"].threshold_s == 9.0
    # the off switch kills derivation
    eng2 = H.HealthEngine(s, _cfg(slo_default_objectives=False),
                          clock=clk)
    assert eng2.active_objectives() == []


def test_sentinels_compare_live_windows_to_pinned_baseline():
    clk = FakeClock(t0=500_000.0)
    s = _store(clk)
    baseline = {"sentinels": [{
        "name": "handler_p99", "metric": "serve_proxy_handler_s",
        "stat": "p99", "window_s": 120, "baseline": 0.2,
        "tolerance": 2.0, "source": "unit"}]}
    eng = H.HealthEngine(s, _cfg(), clock=clk, baseline=baseline)
    cum = {"f": 0, "s": 0, "sum": 0.0}
    for _ in range(6):
        clk.advance(10.0)
        _push_lat(s, clk, "a", 10, 0, cum)      # p99 ~0.1s: fine
    snap = eng.evaluate()
    row = snap["sentinels"][0]
    assert row["live"] is not None and not row["breached"]
    for _ in range(12):
        clk.advance(10.0)
        _push_lat(s, clk, "a", 0, 10, cum)      # p99 ~0.8s: 4x base
    snap = eng.evaluate()
    row = snap["sentinels"][0]
    assert row["breached"] and row["ratio"] > 2.0
    assert ("handler_p99", "sentinel", "firing") in snap["transitions"]
    assert any(e.get("cat") == "health" and e.get("name") == "sentinel"
               and e.get("sentinel") == "handler_p99"
               for e in events.dump())
    # the metric goes quiet: the sentinel resolves AND its gauge
    # zeroes instead of exporting the last breach ratio forever
    for _ in range(20):
        clk.advance(60.0)       # drain the 120s window entirely
    snap = eng.evaluate()
    row = snap["sentinels"][0]
    assert row["live"] is None and not row["breached"]
    assert ("handler_p99", "sentinel", "resolved") in \
        snap["transitions"]
    assert eng._m["sentinel"]._values[
        (("sentinel", "handler_p99"),)] == 0.0


def test_health_baseline_file_drift_fails_loudly():
    """Every committed HEALTH_BASELINE.json value must recompute from
    its source bench file — regenerating a bench without reseeding the
    baseline is a loud failure, not a silent regression-bar shift."""
    root = os.path.join(os.path.dirname(__file__), os.pardir)
    with open(os.path.join(root, "HEALTH_BASELINE.json")) as f:
        base = json.load(f)
    sent = {s["name"]: s for s in base["sentinels"]}
    assert {"serve_handler_p50", "serve_handler_p99",
            "allreduce_round_mean"} <= set(sent)
    with open(os.path.join(root, "TRACE_BENCH.json")) as f:
        tb = json.load(f)
    best_on = max((r for r in tb["results"] if r["arm"] == "on"),
                  key=lambda r: r["req_per_s"])
    assert sent["serve_handler_p50"]["baseline"] == pytest.approx(
        best_on["p50_ms"] / 1e3, rel=1e-6)
    assert sent["serve_handler_p99"]["baseline"] == pytest.approx(
        best_on["p99_ms"] / 1e3, rel=1e-6)
    with open(os.path.join(root, "ALLREDUCE_BENCH.json")) as f:
        ab = json.load(f)
    ring256 = [r["round_s"] for r in ab["results"]
               if r["mode"] == "ring" and r["size_mb"] == 256]
    assert ring256, "ALLREDUCE_BENCH lost its 256MB ring row"
    assert sent["allreduce_round_mean"]["baseline"] == pytest.approx(
        ring256[0], rel=1e-6)
    for s in base["sentinels"]:
        assert s["tolerance"] > 1.0 and s["window_s"] > 0
        assert s.get("source"), s["name"]


def test_snapshot_contract_for_autoscaler():
    """The /health JSON shape ROADMAP item 3's autoscaler consumes:
    stable top-level keys, per-deployment burn_advice, tier windows."""
    clk = FakeClock(t0=600_000.0)
    s = _store(clk)
    eng = H.HealthEngine(s, _cfg(), clock=clk)
    snap = eng.evaluate()
    for key in ("ts", "enabled", "series", "points_total", "tiers",
                "objectives", "alerts", "sentinels", "burn_advice",
                "eval_count", "transitions"):
        assert key in snap, key
    assert snap["enabled"] is True
    assert set(snap["tiers"]) == {"page", "warn"}
    for t in snap["tiers"].values():
        assert len(t["windows_s"]) == 2 and t["burn_threshold"] > 0
    json.dumps(snap)            # wire-serializable as-is
    # inactive process shape (the disabled half of the contract)
    H.deactivate()
    off = H.local_state()
    assert off["enabled"] is False and off.get("reason")


# --- config knobs / lint ----------------------------------------------------


def _load_linter():
    path = os.path.join(os.path.dirname(__file__), os.pardir,
                        "scripts", "check_metrics_lint.py")
    spec = importlib.util.spec_from_file_location(
        "check_metrics_lint_zz", os.path.abspath(path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_health_and_slo_knobs_exercised_and_linted():
    """Every health_*/slo_* Config knob is genuinely exercised here
    (the lint's coverage guarantee), including the pre-existing head
    liveness knobs the health_ prefix sweeps in."""
    cfg = Config.from_env(
        health_enabled=True, health_window_s=1.0,
        health_retention_s=120.0, health_max_series=64,
        health_baseline_path="HEALTH_BASELINE.json",
        health_check_period_s=1.0, health_check_failure_threshold=5,
        slo_eval_interval_s=0.5, slo_fast_burn=2.0,
        slo_fast_windows_s="3,6", slo_slow_burn=1.5,
        slo_slow_windows_s="6,30", slo_default_objectives=True,
        slo_latency_threshold_s=0.25, slo_target=0.95)
    assert cfg.health_max_series == 64
    assert cfg.health_check_failure_threshold == 5
    st = TimeSeriesStore(window_s=cfg.health_window_s,
                         retention_s=cfg.health_retention_s,
                         max_series=cfg.health_max_series,
                         clock=FakeClock())
    eng = H.HealthEngine(st, cfg, clock=st.clock)
    assert eng.tiers["page"]["windows"] == (3.0, 6.0)
    assert eng.tiers["page"]["burn"] == 2.0
    assert eng.tiers["warn"]["windows"] == (6.0, 30.0)
    assert eng.tiers["warn"]["burn"] == 1.5
    # malformed window specs fall back to defaults
    assert H._parse_windows("garbage", (1.0, 2.0)) == (1.0, 2.0)
    assert H._parse_windows("10,5", (1.0, 2.0)) == (1.0, 2.0)
    mod = _load_linter()
    assert {"health", "slo"} <= set(mod.KNOB_FAMILIES)
    assert mod.lint_knob_tests(families=["health", "slo"]) == []
    knobs = set(mod.family_knobs("health")) | set(
        mod.family_knobs("slo"))
    assert {"health_enabled", "health_window_s", "slo_fast_burn",
            "slo_fast_windows_s", "slo_eval_interval_s"} <= knobs


def test_health_event_category_and_metric_families_registered():
    mod = _load_linter()
    assert "health" in events.CATEGORIES
    assert "health" in events._CATEGORY_CAPS      # budget-capped
    assert mod.lint_category_caps() == []
    registry = mod.instantiate_all()
    for name in ("health_series", "health_points_total",
                 "health_eval_s", "health_sentinel_ratio",
                 "slo_burn_rate", "slo_alerts_total",
                 "slo_alert_active"):
        assert name in registry, name
    assert mod.lint(registry) == []
    # the family scan covers health_/slo_ literals now
    assert set(mod.METRIC_FAMILY_PREFIXES) >= {"health_", "slo_"}
    assert mod.lint_device_metric_registration(registry) == []


def test_lint_requires_nonempty_descriptions():
    mod = _load_linter()

    class _Fake:
        def __init__(self, kind, description=None):
            self.kind = kind
            if description is not None:
                self.description = description

    errs = mod.lint({
        "described_total": _Fake("counter", "counts things"),
        "undocumented_total": _Fake("counter", ""),
        "whitespace_total": _Fake("counter", "   "),
        "legacy_total": _Fake("counter"),     # no attr: not a Metric
    })
    assert any("undocumented_total" in e and "description" in e
               for e in errs)
    assert any("whitespace_total" in e for e in errs)
    assert not any("described_total" in e for e in errs)
    assert not any("legacy_total" in e for e in errs)


# --- satellite: worker final metrics flush ----------------------------------


def test_push_once_sends_labeled_snapshot():
    M.Counter("zz_health_flush_total", "flush test").inc(3.0)
    calls = []

    async def call(method, **kw):
        calls.append((method, kw))

    async def go():
        return await M.push_once(call, "worker:abc",
                                 {"node": "n1", "worker": "abc"})

    assert asyncio.run(go()) is True
    assert calls and calls[0][0] == "report_metrics"
    kw = calls[0][1]
    assert kw["source"] == "worker:abc"
    assert 'zz_health_flush_total{node="n1",worker="abc"} 3' \
        in kw["text"]


def test_shutdown_worker_drains_final_metrics_push():
    """Graceful shutdown flushes events AND one final metrics snapshot
    (the push loop's last interval must not die with the worker); a
    hanging head bounds the flush instead of stalling exit."""
    from ray_tpu.runtime.worker import WorkerExecutor

    done = {"events": False, "metrics": False}

    class _Stub:
        async def flush_events(self):
            done["events"] = True

        async def _final_metrics_push(self):
            done["metrics"] = True

    stub = _Stub()

    async def go():
        return await WorkerExecutor.shutdown_worker(stub)

    r = asyncio.run(go())
    assert r == {"ok": True}
    assert done["events"] and done["metrics"]

    # a stub WITHOUT the flush attr (old workers / driver-attached
    # executors) still shuts down cleanly
    class _Bare:
        async def flush_events(self):
            pass

    assert asyncio.run(
        WorkerExecutor.shutdown_worker(_Bare())) == {"ok": True}

    # and a hanging push is bounded by the wait_for, not fatal
    class _Hang:
        async def flush_events(self):
            pass

        async def _final_metrics_push(self):
            await asyncio.sleep(30.0)

    t0 = time.monotonic()
    assert asyncio.run(
        WorkerExecutor.shutdown_worker(_Hang())) == {"ok": True}
    assert time.monotonic() - t0 < 5.0


# --- surfaces: chrome lane, CLI helpers, proxy advisory ---------------------


def test_to_chrome_renders_health_instants():
    from ray_tpu.util.tracing import to_chrome
    evs = [
        {"cat": "health", "name": "alert", "ts": 100.0,
         "objective": "latency:a", "tier": "page", "state": "firing",
         "burn_short": 50.0, "burn_long": 20.0, "trace": "feed5",
         "node": "n1"},
        {"cat": "health", "name": "sentinel", "ts": 101.0,
         "sentinel": "handler_p99", "state": "resolved",
         "live": 0.1, "baseline": 0.2, "node": "n1"},
    ]
    recs = to_chrome(evs)
    inst = [r for r in recs if r.get("cat") == "health"]
    assert len(inst) == 2
    assert all(r["ph"] == "I" and r["tid"] == "health" for r in inst)
    assert inst[0]["name"] == "page:latency:a:firing"
    assert inst[0]["args"]["trace"] == "feed5"
    assert inst[1]["name"] == "sentinel:handler_p99:resolved"


def test_parse_since_and_spark():
    assert H.parse_since("90s") == 90.0
    assert H.parse_since("15m") == 900.0
    assert H.parse_since("2h") == 7200.0
    assert H.parse_since("45") == 45.0
    assert H.parse_since("junk", 123.0) == 123.0
    line = H.spark([0, 1, 2, 3, 4, 5, 6, 7])
    assert line[0] == "▁" and line[-1] == "█"
    assert H.spark([]) == "(no data)"
    assert len(H.spark(list(range(500)))) <= 48
    assert len(H.spark([5.0])) == 1
    # decimation is MAX-aggregated: a single spike survives the fit
    flat = [1.0] * 120
    flat[57] = 100.0
    assert "█" in H.spark(flat)


def test_proxy_shed_advisory_logs_when_burning(caplog):
    """A shed while the health plane reports the deployment's budget
    burning logs one line that names the autoscale_hint it sends (the
    log comes before the hint RPC, which has no controller to reach
    here); a healthy or absent snapshot stays silent. (Cache
    pre-seeded: no health RPC.)"""
    import logging

    from ray_tpu.serve.proxy import HTTPProxy
    p = HTTPProxy.__new__(HTTPProxy)        # skip actor init
    p._health_advice = {
        "ts": time.monotonic(),
        "state": {"burn_advice": {"app1": {
            "availability_burning": True, "latency_burning": False,
            "tier": "page"}}}}
    with caplog.at_level(logging.WARNING, logger="ray_tpu.serve.proxy"):
        asyncio.run(p._consult_health("app1"))
    assert any("autoscale_hint" in r.getMessage() for r in caplog.records)
    caplog.clear()
    # rate-limited: a shed storm gets ONE line per cache window
    with caplog.at_level(logging.WARNING, logger="ray_tpu.serve.proxy"):
        asyncio.run(p._consult_health("app1"))
    assert not caplog.records
    with caplog.at_level(logging.WARNING, logger="ray_tpu.serve.proxy"):
        asyncio.run(p._consult_health("quiet_dep"))
    assert not caplog.records


def test_cli_health_and_metrics_query(monkeypatch, capsys):
    from ray_tpu import scripts as S
    state = {
        "enabled": True, "series": 4, "points_total": 99,
        "eval_count": 7,
        "tiers": {"page": {"windows_s": [60, 300],
                           "burn_threshold": 14.4},
                  "warn": {"windows_s": [300, 1800],
                           "burn_threshold": 3.0}},
        "alerts": [{"objective": "latency:a", "tier": "page",
                    "state": "firing", "since": 1000.0,
                    "exemplar": "deadbeef"}],
        "objectives": [{
            "name": "latency:a", "kind": "latency",
            "metric": "serve_proxy_handler_s", "alert": "page",
            "tiers": {"page": {"burn_short": 55.0, "burn_long": 21.0},
                      "warn": {"burn_short": None,
                               "burn_long": None}}}],
        "sentinels": [{"name": "p99", "metric": "m", "stat": "p99",
                       "window_s": 300.0, "baseline": 0.2,
                       "tolerance": 2.0, "live": 0.9, "ratio": 4.5,
                       "breached": True}],
        "burn_advice": {"a": {"availability_burning": False,
                              "latency_burning": True,
                              "tier": "page"}},
    }
    series = {"name": "serve_proxy_handler_s", "kind": "histogram",
              "window_s": 10.0, "series": 2,
              "points": [{"t": 0.0, "count": 5, "rate": 0.5,
                          "mean": 0.2, "p50": 0.1, "p99": 0.4},
                         {"t": 10.0, "count": 9, "rate": 0.9,
                          "mean": 0.5, "p50": 0.4, "p99": 0.9}]}

    def fake_call(addr, method, timeout=10.0, **kw):
        return state if method == "health_state" else series

    monkeypatch.setattr(S, "_call_head", fake_call)
    monkeypatch.setattr(S, "_resolve_address", lambda a: "h:1")
    assert S.main(["health"]) == 0
    out = capsys.readouterr().out
    assert "ALERT [PAGE] latency:a" in out
    assert "ray-tpu trace deadbeef" in out
    assert "REGRESSION" in out and "4.50x" in out
    assert S.main(["health", "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["enabled"] is True
    assert S.main(["metrics", "serve_proxy_handler_s",
                   "--since", "15m"]) == 0
    out = capsys.readouterr().out
    assert "histogram" in out and "p99" in out
    assert S.main(["metrics", "serve_proxy_handler_s", "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["kind"] == "histogram"
    # disabled plane: query explains instead of stack-tracing
    monkeypatch.setattr(
        S, "_call_head",
        lambda *a, **k: {"error": "health plane inactive"})
    assert S.main(["metrics", "x_total"]) == 1


# --- live-cluster e2e -------------------------------------------------------


@pytest.fixture(scope="module")
def health_cluster():
    """A cluster tuned for seconds-scale SLO windows, with chaos delay
    armed at the replica for requests 11..60 — the injected TTFT
    degradation phase (healthy before, recovered after)."""
    delays = ",".join(f"replica:delay:{n}:0.8" for n in range(11, 61))
    env = {
        "RAY_TPU_METRICS_EXPORT_INTERVAL_S": "0.5",
        "RAY_TPU_HEALTH_WINDOW_S": "1.0",
        "RAY_TPU_HEALTH_RETENTION_S": "120",
        "RAY_TPU_SLO_EVAL_INTERVAL_S": "0.5",
        "RAY_TPU_SLO_FAST_WINDOWS_S": "3,8",
        "RAY_TPU_SLO_FAST_BURN": "5",
        "RAY_TPU_SLO_SLOW_WINDOWS_S": "8,30",
        "RAY_TPU_SLO_LATENCY_THRESHOLD_S": "0.25",
        "RAY_TPU_METRICS_PORT": "0",
        "RAY_TPU_TESTING_SERVE_FAILURE": delays,
    }
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    import ray_tpu
    ray_tpu.init(num_cpus=8)
    yield
    from ray_tpu import serve
    serve.shutdown()
    ray_tpu.shutdown()
    for k, v in old.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


def _head_call(method, **kw):
    from ray_tpu import api
    ctx = api._require_init()
    return api._run(ctx.pool.call(ctx.head_addr, method,
                                  timeout=10.0, **kw))


def _post(addr, path, payload):
    conn = http.client.HTTPConnection(addr["host"], addr["port"],
                                      timeout=30)
    conn.request("POST", path, body=json.dumps(payload),
                 headers={"Content-Type": "application/json"})
    r = conn.getresponse()
    r.read()
    status = r.status
    conn.close()
    return status


@pytest.mark.slow
def test_ttft_degradation_fires_page_alert_with_trace_e2e(
        health_cluster):
    """The acceptance walk: chaos delay at the replica degrades TTFT →
    the fast-burn page-tier alert fires within its detection window,
    its event carries an exemplar trace id that resolves in the
    timeline (`ray-tpu trace <id>`), recovery clears the alert, and
    the /health?json=1 endpoint serves the same machine contract."""
    from ray_tpu import serve

    @serve.deployment(max_ongoing_requests=4, num_replicas=1)
    class Echo:
        async def __call__(self, v=None):
            return {"ok": True}

    serve.run(Echo.bind(), name="app_slo", route_prefix="/slo")
    addr = serve.proxy_address()
    dep = None

    # phase 1: 10 healthy requests (chaos arms at the 11th)
    for _ in range(10):
        assert _post(addr, "/slo", {"x": 1}) == 200

    # phase 2: degraded traffic (0.8s chaos delay per request) from
    # background threads while we poll the health plane for the page
    stop = threading.Event()

    def pump():
        while not stop.is_set():
            try:
                _post(addr, "/slo", {"x": 1})
            except Exception:
                time.sleep(0.2)

    threads = [threading.Thread(target=pump, daemon=True)
               for _ in range(4)]
    for t in threads:
        t.start()
    fired = None
    deadline = time.monotonic() + 45.0
    try:
        while time.monotonic() < deadline:
            s = _head_call("health_state")
            if s.get("enabled"):
                for a in s.get("alerts", []):
                    if a["tier"] == "page" and \
                            a["objective"].startswith("latency:"):
                        fired = a
                        dep = a["objective"].split(":", 1)[1]
                        break
            if fired:
                break
            time.sleep(0.5)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10.0)
    assert fired is not None, \
        f"page alert never fired; last state: {json.dumps(s)[:800]}"
    assert dep == "Echo"       # proxy tags by DEPLOYMENT name

    # the alert's exemplar trace id resolves in the cluster timeline
    ex = fired.get("exemplar")
    assert ex, fired
    from ray_tpu.util.tracing import filter_trace
    tl = _head_call("collect_timeline")
    mine = filter_trace(tl.get("events", []), ex)
    assert mine, f"exemplar trace {ex} not resolvable in the timeline"
    assert any(e.get("cat") == "request" for e in mine)
    # and the firing transition is a "health" event in the timeline
    assert any(e.get("cat") == "health" and e.get("state") == "firing"
               and str(e.get("objective", "")).startswith("latency:")
               for e in tl.get("events", []))

    # the machine-readable endpoint serves the same contract
    from ray_tpu import api
    maddr = getattr(api._g.head, "metrics_addr", None)
    if maddr:
        conn = http.client.HTTPConnection(maddr[0], maddr[1],
                                          timeout=10)
        conn.request("GET", "/health?json=1")
        r = conn.getresponse()
        doc = json.loads(r.read())
        conn.close()
        assert doc.get("enabled") is True
        assert "burn_advice" in doc and "objectives" in doc

    # phase 3: recovery — chaos rules exhausted, healthy traffic
    # drains both burn windows and the alert resolves
    resolved = False
    deadline = time.monotonic() + 60.0
    while time.monotonic() < deadline:
        _post(addr, "/slo", {"x": 1})
        s = _head_call("health_state")
        active = [a for a in s.get("alerts", [])
                  if a["tier"] == "page"
                  and a["objective"] == f"latency:{dep}"]
        if not active:
            resolved = True
            break
        time.sleep(0.5)
    assert resolved, "page alert never cleared after recovery"
    # the resolved transition joined the health event stream too
    tl = _head_call("collect_timeline")
    assert any(e.get("cat") == "health" and e.get("state") == "resolved"
               and e.get("objective") == f"latency:{dep}"
               for e in tl.get("events", []))
    serve.delete("app_slo")


@pytest.mark.slow
def test_worker_pushed_series_reach_head_store_e2e(health_cluster):
    """A counter incremented inside a worker becomes queryable history
    at the head (push_loop -> report_metrics -> timeseries ingest ->
    query_series) — the aggregation path the final graceful-shutdown
    flush (unit-tested above) drains through."""
    import ray_tpu

    # an ACTOR pins both increments to one worker process: the first
    # push containing the series is the store's baseline, so only the
    # SECOND bump's delta is expected to land in windows
    @ray_tpu.remote
    class Bumper:
        def bump(self):
            from ray_tpu.util import metrics as m
            m.Counter("zz_flush_e2e_total",
                      "push-path e2e").inc(7.0)
            return os.getpid()

    b = Bumper.remote()
    ray_tpu.get(b.bump.remote())
    time.sleep(1.5)             # > export interval: baseline push out
    ray_tpu.get(b.bump.remote())
    deadline = time.monotonic() + 15.0
    found = None
    while time.monotonic() < deadline:
        r = _head_call("query_series", name="zz_flush_e2e_total",
                       since_s=60.0)
        if r.get("points"):
            found = r
            break
        time.sleep(0.5)
    assert found, "pushed counter never reached the head store"
    assert sum(p["inc"] for p in found["points"]) >= 7.0
