"""Goodput ledger: the sum-to-wall identity (interval-stamped and
pre-aggregated paths, including the clock-skew scale-down), the
``goodput_level="off"`` zero-cost discipline, counter monotonicity
through the time-series rollup, online straggler detection, the
timeline/state anatomy rows, the bubble-rate health sentinel, and
drift pinning of the GOODPUT_BENCH-seeded baseline. (Late-alphabet
name keeps the tier-1 cutoff stable.)

Knob coverage: ``goodput_level`` (RAY_TPU_GOODPUT_LEVEL),
``goodput_straggler_z``, ``goodput_straggler_window_steps``.
"""

import json
import os
import time

import pytest

from ray_tpu.config import Config
from ray_tpu.util import events
from ray_tpu.util import goodput
from ray_tpu.util import health as H
from ray_tpu.util import state
from ray_tpu.util.timeseries import TimeSeriesStore

_ROOT = os.path.join(os.path.dirname(__file__), os.pardir)


class FakeClock:
    def __init__(self, t0=1000.0):
        self.t = t0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt
        return self.t


@pytest.fixture(autouse=True)
def _fresh_ledger():
    goodput.reset()
    goodput.set_level("step")
    goodput.set_rank(-1)
    yield
    goodput.reset()


def _seconds_total():
    m = goodput.goodput_metrics()["seconds"]
    return sum(m._values.values())


# --- the sum-to-wall identity -----------------------------------------------


def test_interval_path_identity_and_carveout():
    """Stamped intervals + add() carve-outs partition the step wall
    exactly: an add() inside an open interval is carved OUT of the
    enclosing category, synthetic add()s land verbatim, and idle
    absorbs the residual."""
    goodput.step_begin(7, rank=3)
    with goodput.interval("compute"):
        time.sleep(0.005)
        goodput.add("comm_exposed", 0.001)      # carved out of compute
    goodput.add("ckpt_stall", 0.0005)           # outside any interval
    time.sleep(0.002)                           # unclaimed -> idle
    goodput.step_end()
    rows = goodput.recent_rows()
    assert len(rows) == 1
    row = rows[0]
    assert row["step"] == 7 and row["rank"] == 3
    total = row["idle"] + sum(row[c] for c in goodput.STAMPED)
    assert total == pytest.approx(row["wall_s"], abs=1e-9)
    # the adds were not scaled (stamped < wall here), and the carve
    # kept the interval's own time exclusive of the inner add
    assert row["comm_exposed"] == pytest.approx(0.001, abs=1e-9)
    assert row["ckpt_stall"] == pytest.approx(0.0005, abs=1e-9)
    assert 0.003 < row["compute"] < row["wall_s"]
    assert row["idle"] > 0.0


def test_nested_intervals_never_double_count():
    """An inner interval's whole span is carved from its parent, so
    compute + comm_exposed <= wall even when one wraps the other."""
    goodput.step_begin(1, rank=0)
    with goodput.interval("compute"):
        time.sleep(0.002)
        with goodput.interval("comm_exposed"):
            time.sleep(0.002)
        with goodput.interval("compute"):       # same-category re-entry
            time.sleep(0.001)
    goodput.step_end()
    row = goodput.recent_rows()[0]
    assert row["compute"] + row["comm_exposed"] <= row["wall_s"] + 1e-9
    assert row["compute"] > 0.0 and row["comm_exposed"] >= 0.002 - 1e-4
    total = row["idle"] + sum(row[c] for c in goodput.STAMPED)
    assert total == pytest.approx(row["wall_s"], abs=1e-9)


def test_record_step_identity_and_scale_down():
    # residual path: unclaimed wall becomes idle
    goodput.record_step(5, 0.1, rank=2, compute=0.06, bubble=0.02)
    row = goodput.recent_rows()[-1]
    assert row["idle"] == pytest.approx(0.02, abs=1e-12)
    assert row["idle"] + sum(row[c] for c in goodput.STAMPED) == \
        pytest.approx(row["wall_s"], abs=1e-12)
    # clock-skew path: stamped > wall scales down (never negative idle),
    # preserving proportions and the exact identity
    goodput.record_step(6, 0.05, rank=2, compute=0.06,
                        comm_exposed=0.06)
    row = goodput.recent_rows()[-1]
    assert row["idle"] == 0.0
    assert row["compute"] == pytest.approx(row["comm_exposed"])
    assert sum(row[c] for c in goodput.STAMPED) == \
        pytest.approx(0.05, abs=1e-12)
    # negative/unknown categories are dropped, not booked
    goodput.record_step(8, 0.01, rank=2, compute=-1.0, nonsense=0.5)
    row = goodput.recent_rows()[-1]
    assert row["compute"] == 0.0 and row["idle"] == \
        pytest.approx(0.01, abs=1e-12)


def test_reentrant_step_window_is_depth_counted():
    """A nested trace_step (e.g. a user fn that itself calls the
    trainer) must not close the outer window early or emit two rows."""
    goodput.step_begin(1, rank=0)
    goodput.step_begin(1)
    goodput.add("compute", 0.001)
    goodput.step_end()                  # closes the nested entry only
    assert goodput.recent_rows() == []
    goodput.step_end()
    assert len(goodput.recent_rows()) == 1


# --- the off discipline ------------------------------------------------------


def test_off_level_records_nothing():
    """goodput_level="off" (RAY_TPU_GOODPUT_LEVEL=off) is the
    collective_trace_level discipline: every call early-returns — no
    rows, no counters, no events, and interval() hands back the shared
    no-op (no per-call allocation)."""
    goodput.set_level("off")
    assert not goodput.enabled()
    before = _seconds_total()
    n_events = sum(1 for e in events.dump()
                   if e.get("cat") == "goodput")
    goodput.step_begin(1, rank=0)
    with goodput.interval("compute"):
        pass
    goodput.add("comm_exposed", 1.0)
    goodput.step_end()
    goodput.record_step(2, 1.0, rank=0, compute=0.5)
    assert goodput.recent_rows() == []
    assert goodput.anatomy() is None
    assert _seconds_total() == before
    assert sum(1 for e in events.dump()
               if e.get("cat") == "goodput") == n_events
    assert goodput.interval("compute") is goodput.interval("bubble")


def test_level_knob_resolves_from_config(monkeypatch):
    """The lazily-cached level re-resolves from Config after reset():
    the goodput_level knob is the production switch."""
    assert Config().goodput_level == "step"
    assert Config(goodput_level="off").goodput_level == "off"
    monkeypatch.setenv("RAY_TPU_GOODPUT_LEVEL", "off")
    import ray_tpu.config as C
    cfg = C.Config.from_env()
    assert cfg.goodput_level == "off"


def test_straggler_knob_defaults():
    c = Config()
    assert c.goodput_straggler_z == 6.0
    assert c.goodput_straggler_window_steps == 32


# --- metrics: counters, rollup monotonicity, MFU ----------------------------


def test_counters_monotone_through_rollup():
    """goodput_seconds_total flows through the head's time-series
    store like any pushed counter: per-window increments are never
    negative and sum to the cumulative delta."""
    clk = FakeClock(t0=50_000.0)
    s = TimeSeriesStore(clock=clk, window_s=10.0, retention_s=900.0)
    key = (("category", "compute"), ("rank", "0"))
    m = goodput.goodput_metrics()["seconds"]
    first = m._values.get(key, 0.0)
    s.ingest_counter("goodput_seconds_total",
                     dict(key), first, source="w0")
    for i in range(8):
        goodput.record_step(i, 0.05, rank=0, compute=0.03)
        clk.advance(10.0)
        s.ingest_counter("goodput_seconds_total", dict(key),
                         m._values.get(key, 0.0), source="w0")
    last = m._values.get(key, 0.0)
    assert last == pytest.approx(first + 8 * 0.03, abs=1e-9)
    q = s.query("goodput_seconds_total", since_s=300.0)
    assert q["kind"] == "counter" and q["points"]
    assert all(p["inc"] >= 0.0 and p["rate"] >= 0.0
               for p in q["points"])
    assert sum(p["inc"] for p in q["points"]) == \
        pytest.approx(last - first, abs=1e-9)
    # every closed row also ticks the step counter
    steps = goodput.goodput_metrics()["steps"]
    assert steps._values.get((("rank", "0"),), 0.0) >= 8


def test_mfu_gauge_from_registered_flops():
    """train_mfu = flops_per_step / wall / peak: 1e12 FLOPs in 1s on a
    100-TFLOP part is 1% MFU. Explicit peak wins; device_kind resolves
    through accelerators.peak_tflops."""
    goodput.set_model_flops(1e12, peak_tflops=100.0)
    goodput.record_step(1, 1.0, rank=4, compute=0.9)
    g = goodput.goodput_metrics()["mfu"]
    assert g._values[(("rank", "4"),)] == pytest.approx(0.01)
    from ray_tpu.util.accelerators import peak_tflops
    assert peak_tflops("TPU v5e") == 197.0
    assert peak_tflops("TPU v5p") == 459.0
    # a v5e chip's device_kind, as jax reports it
    assert peak_tflops("TPU v5 lite") == 197.0
    # an unknown kind is an error, never a guessed denominator
    with pytest.raises(ValueError, match="TPU v99"):
        peak_tflops("TPU v99")


# --- straggler detection -----------------------------------------------------


def _an(rank, compute, comm, steps=16):
    return {"rank": rank, "steps": steps, "wall_p50": 0.1,
            "p50": {"compute": compute, "comm_exposed": comm,
                    "bubble": 0.0, "ckpt_stall": 0.0, "compile": 0.0,
                    "idle": 0.0}}


def test_straggler_detector_names_injected_slow_rank():
    det = goodput.StragglerDetector(z_threshold=6.0, min_steps=8)
    for r in range(4):
        if r == 2:      # the slow rank computes longer, waits less
            det.observe(r, _an(r, compute=0.050, comm=0.001))
        else:           # healthy ranks absorb the wait
            det.observe(r, _an(r, compute=0.010, comm=0.041))
    v = det.check()
    assert v["rank"] == 2
    assert v["z"] >= 6.0 and v["gap_s"] >= 0.005


def test_straggler_detector_quiet_on_uniform_ranks():
    det = goodput.StragglerDetector(z_threshold=6.0, min_steps=8)
    for r in range(4):
        det.observe(r, _an(r, compute=0.010 + 0.0001 * r, comm=0.040))
    assert det.check()["rank"] == -1
    # too few ranks / too few steps: never flags
    det2 = goodput.StragglerDetector(min_steps=8)
    det2.observe(0, _an(0, 0.5, 0.0))
    det2.observe(1, _an(1, 0.01, 0.04))
    assert det2.check()["rank"] == -1
    det2.observe(2, _an(2, 0.01, 0.04, steps=2))    # below min_steps
    assert det2.check()["rank"] == -1


def test_anatomy_window_feeds_detector_end_to_end():
    """Ledger rows -> anatomy() p50 summary -> detector: the shape the
    worker poll ships and the controller consumes."""
    for i in range(12):
        goodput.record_step(i, 0.1, rank=5, compute=0.08,
                            comm_exposed=0.001)
    an = goodput.anatomy()
    assert an["rank"] == 5 and an["steps"] == 12
    assert an["p50"]["compute"] == pytest.approx(0.08)
    assert an["wall_p50"] == pytest.approx(0.1)
    det = goodput.StragglerDetector(z_threshold=6.0, min_steps=8)
    det.observe(5, an)
    det.observe(0, _an(0, compute=0.010, comm=0.060))
    det.observe(1, _an(1, compute=0.010, comm=0.060))
    assert det.check()["rank"] == 5


def test_window_respects_straggler_window_knob():
    """The rolling anatomy window is goodput_straggler_window_steps
    deep — old steps age out instead of growing without bound."""
    for i in range(50):
        goodput.record_step(i, 0.01, rank=0, compute=0.005)
    rows = goodput.recent_rows()
    assert len(rows) == Config().goodput_straggler_window_steps
    assert rows[0]["step"] == 50 - len(rows)


# --- timeline events / state rows -------------------------------------------


def test_step_events_and_state_anatomy_rows():
    goodput.set_model_flops(1e12, peak_tflops=100.0)
    for i in range(4):
        goodput.record_step(i, 0.1, rank=1, compute=0.06,
                            comm_exposed=0.02, bubble=0.01)
    evts = [e for e in events.dump() if e.get("cat") == "goodput"
            and e.get("name") == "step" and e.get("rank") == 1]
    assert len(evts) >= 4
    e = evts[-1]
    assert e["wall_s"] == pytest.approx(0.1, abs=1e-6)
    booked = (e["idle_s"]
              + sum(e[f"{c}_s"] for c in goodput.STAMPED))
    assert booked == pytest.approx(e["wall_s"], abs=1e-5)
    rows = state.goodput_from_events(evts)
    assert len(rows) == 1 and rows[0]["rank"] == 1
    assert rows[0]["steps"] >= 4
    assert rows[0]["mean_compute_s"] == pytest.approx(0.06, abs=1e-6)
    assert rows[0]["goodput_fraction"] == pytest.approx(0.6, abs=1e-4)
    # 1e12 FLOPs / 0.1 s wall against 100 TFLOPs peak -> 10% MFU
    assert rows[0]["mfu"] == pytest.approx(0.1, abs=1e-4)


# --- health plane ------------------------------------------------------------


def test_bubble_sentinel_fires_through_health_engine():
    """The GOODPUT_BENCH-seeded sentinel watches the bubble counter's
    rate: exposed pipeline idle seconds per wall second beyond
    baseline*tolerance is a firing regression."""
    clk = FakeClock(t0=500_000.0)
    s = TimeSeriesStore(clock=clk, window_s=10.0, retention_s=900.0)
    baseline = {"sentinels": [{
        "name": "goodput_bubble_rate",
        "metric": "goodput_seconds_total",
        "labels": {"category": "bubble"}, "stat": "rate",
        "window_s": 120, "baseline": 0.2, "tolerance": 3.0,
        "source": "unit"}]}
    cfg = Config(slo_default_objectives=False)
    eng = H.HealthEngine(s, cfg, clock=clk, baseline=baseline)
    labels = {"category": "bubble", "rank": "0"}
    cum = 0.0
    for _ in range(12):                 # healthy: ~0.1 s/s of bubble
        clk.advance(10.0)
        cum += 1.0
        s.ingest_counter("goodput_seconds_total", labels, cum,
                         source="w0")
    snap = eng.evaluate()
    row = snap["sentinels"][0]
    assert row["live"] is not None and not row["breached"]
    for _ in range(12):                 # regressed: ~0.9 s/s
        clk.advance(10.0)
        cum += 9.0
        s.ingest_counter("goodput_seconds_total", labels, cum,
                         source="w0")
    snap = eng.evaluate()
    row = snap["sentinels"][0]
    assert row["breached"] and row["ratio"] > 3.0
    assert ("goodput_bubble_rate", "sentinel", "firing") in \
        snap["transitions"]


def test_straggler_gauge_derives_health_objective():
    clk = FakeClock(t0=1000.0)
    s = TimeSeriesStore(clock=clk, window_s=10.0, retention_s=900.0)
    s.ingest_gauge("goodput_straggler_rank", None, -1.0)
    eng = H.HealthEngine(
        s, Config(slo_default_objectives=True), clock=clk)
    names = {o.name for o in eng.active_objectives()}
    assert "goodput_straggler" in names


def test_straggler_gauge_query_exposes_last_sample():
    # a rank-id gauge is meaningless averaged: a window that saw both
    # -1 (healthy polls) and 2 (straggler fired) must still report the
    # NEWEST sample as "last" (the CLI/dashboard read that, not the
    # window-mean "value")
    clk = FakeClock(t0=1000.0)
    s = TimeSeriesStore(clock=clk, window_s=10.0, retention_s=900.0)
    for v in (-1.0, -1.0, 2.0):
        s.ingest_gauge("goodput_straggler_rank", None, v)
        clk.advance(0.5)
    q = s.query("goodput_straggler_rank", since_s=60.0)
    pt = q["points"][-1]
    assert pt["last"] == 2.0
    assert pt["value"] == pytest.approx(0.0)   # the useless mean
    assert pt["min"] == -1.0 and pt["max"] == 2.0


# --- CLI surface -------------------------------------------------------------


def test_cli_goodput_renders_anatomy_and_mfu(monkeypatch, capsys):
    from ray_tpu import scripts as S
    goodput.set_model_flops(1e12, peak_tflops=100.0)
    for i in range(6):
        goodput.record_step(i, 0.1, rank=20, compute=0.07,
                            comm_exposed=0.02)
        goodput.record_step(i, 0.1, rank=21, compute=0.05,
                            bubble=0.03)
    # the events ring is process-global: keep only this test's ranks
    evts = [e for e in events.dump() if e.get("cat") == "goodput"
            and e.get("rank") in (20, 21)]
    series = {
        "train_mfu": {"name": "train_mfu", "kind": "gauge",
                      "window_s": 10.0, "series": 1,
                      "points": [{"t": 0.0, "value": 0.08},
                                 {"t": 10.0, "value": 0.1}]},
        "goodput_straggler_rank": {
            "name": "goodput_straggler_rank", "kind": "gauge",
            "window_s": 10.0, "series": 1,
            "points": [{"t": 10.0, "value": 1.0}]},
    }

    def fake_call(addr, method, timeout=10.0, **kw):
        if method == "collect_timeline":
            return {"events": evts}
        return series[kw["name"]]

    monkeypatch.setattr(S, "_call_head", fake_call)
    monkeypatch.setattr(S, "_resolve_address", lambda a: "h:1")
    assert S.main(["goodput"]) == 0
    out = capsys.readouterr().out
    assert "anatomy" in out and "#" in out          # stacked bar
    assert "train_mfu" in out and "10.0%" in out
    assert "STRAGGLER: rank 1" in out
    assert S.main(["goodput", "--json"]) == 0
    j = json.loads(capsys.readouterr().out)
    assert {r["rank"] for r in j["rows"]} == {20, 21}
    assert j["straggler_rank"] == 1
    assert j["mfu_trend"] == [0.08, 0.1]


# --- bench drift pinning -----------------------------------------------------


def test_goodput_bench_seeds_health_baseline():
    """The committed sentinel baseline must recompute from
    GOODPUT_BENCH.json — regenerating the bench without reseeding is a
    loud failure (same contract as test_zz_health's drift test)."""
    with open(os.path.join(_ROOT, "HEALTH_BASELINE.json")) as f:
        base = json.load(f)
    sent = {x["name"]: x for x in base["sentinels"]}
    assert "goodput_bubble_rate" in sent
    with open(os.path.join(_ROOT, "GOODPUT_BENCH.json")) as f:
        gb = json.load(f)
    assert sent["goodput_bubble_rate"]["baseline"] == pytest.approx(
        gb["bubble_fraction_measured"], rel=1e-4)
    assert sent["goodput_bubble_rate"]["labels"] == {
        "category": "bubble"}
    # the bench's own acceptance: default-level stamping is noise on a
    # realistic step, and the ledger's measured bubble tracks the
    # analytic (S-1)/(M+S-1) bound for the 2-stage M=4 run
    assert gb["on_vs_off_step"] < 1.25
    assert 0.8 < gb["bubble_vs_analytic"] < 1.6
    assert gb["overhead"]["micro"]["rows_per_rep_off"] == 0
    assert gb["overhead"]["micro"]["rows_per_rep_on"] > 0
