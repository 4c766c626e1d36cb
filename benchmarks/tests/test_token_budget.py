"""The per-layer metrics that read a served token's parts (PR 37): the
four host parts of a decode block and the stream hop from the engine's
counters, and the medians over the program's request spans. On a parent
without the spans and counters each reads None and is left out of the
line. The launch around a decode step went with its reader (PR 58:
below zero by construction; ``decode_around_ms_per_step.serving`` reads
what it was meant to); the two metrics PR 56 could not enter are read
here by hand."""
import pytest

from harness import spec

CELLS = ["serve-chat-open", "serve-exaone-reason-open"]
NEW = {"engine_prepare_ms_per_block.serve": "counter_ratio",
       "engine_account_ms_per_block.serve": "counter_ratio",
       "engine_emit_ms_per_block.serve": "counter_ratio",
       "engine_yield_ms_per_block.serve": "counter_ratio",
       "engine_tpot_p50_ms.serve": "request_spans",
       "stream_consume_us_per_token.serve": "counter_ratio",
       "proxy_token_us.serve": "request_spans",
       "proxy_tpot_p50_ms.serve": "request_spans",
       "proxy_ingress_p50_ms.serve": "request_spans",
       "engine_hop_ms_per_block.serve": "counter_ratio"}


def _read(name, ctx):
    mf = spec.metric_file(name)
    return spec.reader(mf["reader"])(ctx, **mf.get("args", {}))


@pytest.mark.parametrize("cell", CELLS)
def test_new_metrics_resolve_through_both_serve_cells(cell):
    mine = {m["name"]: m for m in spec.cell(cell)["per_layer"]
            if m["name"] in NEW}
    assert set(mine) == set(NEW)
    for name, m in mine.items():
        mf = spec.metric_file(name)
        assert mf["reader"] == NEW[name]
        assert callable(spec.reader(mf["reader"]))
        assert m["moves"] == "tpot_p50_ms" == mf["moves"]
        assert m["workloads"] == CELLS
        for key in ("unit", "better", "source", "layer", "workloads"):
            assert mf[key] == m[key], (name, key)
    # they were appended, in this order, and later entries after them
    names = [m["name"] for m in spec.benchmark()["per_layer"]]
    assert [n for n in names if n in NEW] == list(NEW)


def test_the_layers_are_the_accepted_ones():
    layers = {m["layer"] for m in spec.benchmark()["per_layer"]
              if m["name"] not in NEW}
    assert {spec.metric_file(n)["layer"] for n in NEW} <= layers


# --- counters ------------------------------------------------------------

COUNTERS = {"block_steps_count": 20.0, "block_steps_sum": 130.0,
            "loop_decode_prepare_sum": 0.006,
            "loop_decode_account_sum": 0.001,
            "loop_emit_sum": 0.008, "loop_yield_sum": 0.05,
            "decode_hop_sum": 0.012,
            "stream_consume_sum": 0.013, "stream_consume_count": 260.0}


def _ctx(trace_counters, decode_s=1.274):
    return {"trace": {"programs": {"decode": {"s": decode_s, "calls": 20}},
                      "kernels": {}},
            "counters": {"window": {}, "trace": trace_counters}}


@pytest.mark.parametrize("name, want", [
    ("engine_prepare_ms_per_block.serve", 0.3),
    ("engine_account_ms_per_block.serve", 0.05),
    ("engine_emit_ms_per_block.serve", 0.4),
    ("engine_yield_ms_per_block.serve", 2.5),
    ("engine_hop_ms_per_block.serve", 0.6),
    ("stream_consume_us_per_token.serve", 50.0),
])
def test_counter_metrics(name, want):
    assert _read(name, _ctx(COUNTERS)) == pytest.approx(want)
    # a program without the counter (the parent), an untraced run, a
    # window without a block: nothing, and no exception
    gone = {k: v for k, v in COUNTERS.items() if k not in (
        "loop_decode_account_sum", "stream_consume_sum",
        "stream_consume_count", "decode_hop_sum")}
    if "account" in name or "stream" in name or "hop" in name:
        assert _read(name, _ctx(gone)) is None
    assert _read(name, _ctx({})) is None
    untraced = _ctx({})
    del untraced["counters"]["trace"]
    assert _read(name, untraced) is None
    assert _read(name, _ctx({**COUNTERS, "block_steps_count": 0.0,
                             "block_steps_sum": 0.0,
                             "stream_consume_count": 0.0})) is None


def test_the_wait_behind_the_block_in_flight_an_admission():
    """``prefill_behind_ms_per_admit.serving``: an accepted reader on the
    histogram PR 56 added, cut at the trace's edges, in all four serve
    cells."""
    name = "prefill_behind_ms_per_admit.serving"
    mf = spec.metric_file(name)
    assert (mf["reader"], mf["layer"]) == ("counter_ratio",
                                           "serving forwards")
    assert mf["args"] == {"num": "loop_prefill_behind_sum",
                          "den": "loop_prefill_behind_count",
                          "scale": 1000.0, "scope": "trace"}
    entry = next(m for m in spec.benchmark()["per_layer"]
                 if m["name"] == name)
    assert entry["workloads"] == CELLS + ["serve-mistral4-longdoc-open",
                                          "serve-xing4-rag-open"]
    # 0.3 s waited by 12 admissions
    c = {"loop_prefill_behind_sum": 0.3, "loop_prefill_behind_count": 12.0}
    assert _read(name, _ctx(c)) == pytest.approx(25.0)
    assert _read(name, _ctx({})) is None        # the parent of PR 56
    assert _read(name, _ctx({**c, "loop_prefill_behind_count": 0.0})) \
        is None                                 # no admission traced
    untraced = _ctx(c)
    del untraced["counters"]["trace"]
    assert _read(name, untraced) is None


# --- request spans ----------------------------------------------------------

W = (1000.0, 1050.0)        # the window, on the harness's monotonic clock


def _span(component, seg, trace, ts, dur, **attrs):
    return {"cat": "request", "name": "span", "component": component,
            "seg": seg, "trace": trace, "ts": ts, "dur": dur, **attrs}


def _spans_ctx(events):
    from ray_tpu.util import tracing
    wall0 = tracing.wall(W[0])
    return {"window": W, "request_spans": [
        {**e, "ts": e["ts"] + wall0} | (
            {k: e[k] + wall0 for k in ("t_first", "t_last") if k in e})
        for e in events]}


def _request(trace, arrive, tokens, tpot, shift=0.002, end=None):
    """One streamed request's spans, times relative to the window's
    opening: arrives, reaches the replica 4 ms later, first token 100
    ms after that, then ``tpot`` a token."""
    run = arrive + 0.004
    first = run + 0.1
    last = first + tpot * (tokens - 1)
    return [
        _span("proxy", "request", trace, arrive, last + shift - arrive,
              root=True),
        _span("handle", "submit", trace, arrive + 0.001, 0.001),
        _span("proxy", "handler", trace, arrive + 0.002,
              last + shift - arrive - 0.002, tokens=tokens,
              first_token_s=first + shift - arrive,
              get_s=tokens * 100e-6, free_s=tokens * 50e-6,
              write_s=tokens * 25e-6, t_first=first + shift,
              t_last=last + shift),
        _span("replica", "handler", trace, run, last - run, items=tokens,
              push_s=tokens * 10e-6),
        _span("engine", "generate", trace, run + 0.0005,
              last - run - 0.0005, tokens=tokens, tpot_s=tpot),
        {"cat": "request", "name": "batch", "links": [trace],
         "ts": first, "dur": 0.05},
    ]


def test_span_metrics_take_the_median_of_the_windows_requests():
    evs = []
    for i, tpot in enumerate((0.010, 0.012, 0.020)):
        evs += _request(f"in{i}", 5.0 + i, 51, tpot)
    # ended before the window opened, and after it closed: left out
    evs += _request("early", -3.0, 11, 0.050)
    evs += _request("late", 49.0, 101, 0.050)
    ctx = _spans_ctx(evs)
    assert _read("engine_tpot_p50_ms.serve", ctx) == pytest.approx(12.0)
    assert _read("proxy_tpot_p50_ms.serve", ctx) \
        == pytest.approx(12.0, abs=1e-3)   # wall stamps: 2e-7 s apart
    assert _read("proxy_token_us.serve", ctx) == pytest.approx(175.0)
    # arrival -> the replica's handler, of the requests that ARRIVED in
    # the window ("late" did, "early" did not)
    assert _read("proxy_ingress_p50_ms.serve", ctx) == pytest.approx(4.0, abs=1e-3)
    only_late = _spans_ctx(_request("late", 49.0, 101, 0.050))
    assert _read("proxy_ingress_p50_ms.serve", only_late) \
        == pytest.approx(4.0, abs=1e-3)
    assert _read("engine_tpot_p50_ms.serve", only_late) is None


def test_span_metrics_are_cut_at_the_traces_edges_where_there_are_any():
    """The profiler's stop stalls the rest of a traced window: the
    medians take the traced seconds, which the other parts come from."""
    evs = _request("traced", 0.5, 51, 0.010) \
        + _request("stalled", 9.0, 51, 0.030) \
        + _request("stalled2", 10.0, 51, 0.031)
    ctx = _spans_ctx(evs)
    assert _read("engine_tpot_p50_ms.serve", ctx) == pytest.approx(30.0)
    ctx["trace_edges"] = (W[0], W[0] + 8.0)
    assert _read("engine_tpot_p50_ms.serve", ctx) == pytest.approx(10.0)
    assert _read("proxy_tpot_p50_ms.serve", ctx) \
        == pytest.approx(10.0, abs=1e-3)
    assert _read("proxy_ingress_p50_ms.serve", ctx) \
        == pytest.approx(4.0, abs=1e-3)
    ctx["trace_edges"] = (W[0] + 20.0, W[0] + 28.0)     # nothing ended
    assert _read("engine_tpot_p50_ms.serve", ctx) is None


def test_a_requests_token_with_its_stalls_taken_out():
    """``engine_tpot_unstalled_p50_ms.serve`` is ``engine_tpot_p50_ms``
    less ``engine_tpot_stall_p50_ms`` request by request: the median of
    the differences, not the difference of the medians."""
    name = "engine_tpot_unstalled_p50_ms.serve"
    mf = spec.metric_file(name)
    assert mf["reader"] == "request_spans" and mf["args"] == {
        "component": "engine", "seg": "generate", "num": ["tpot_s"],
        "minus": ["tpot_stall_s"], "scale": 1000.0}
    for cell in CELLS:
        assert name in {m["name"] for m in spec.cell(cell)["per_layer"]}
    evs = []
    for i, (tpot, stall) in enumerate(((0.010, 0.001), (0.012, 0.004),
                                       (0.020, 0.0005))):
        for e in _request(f"in{i}", 5.0 + i, 51, tpot):
            if e.get("seg") == "generate":
                e = {**e, "tpot_stall_s": stall}
            evs.append(e)
    ctx = _spans_ctx(evs)
    assert _read("engine_tpot_stall_p50_ms.serve", ctx) \
        == pytest.approx(1.0)
    # 9.0, 8.0, 19.5 -> 9.0 (12.0 - 1.0 would say 11.0)
    assert _read(name, ctx) == pytest.approx(9.0)
    # the parent of PR 56: a token's time, no stall beside it
    assert _read(name, _spans_ctx(_request("p", 5.0, 51, 0.010))) is None


def test_span_metrics_on_a_program_without_the_attributes():
    """The parent's spans: same components, no stream attributes."""
    evs = []
    for e in _request("p", 5.0, 51, 0.010):
        evs.append({k: v for k, v in e.items() if k not in (
            "tokens", "first_token_s", "get_s", "free_s", "write_s",
            "t_first", "t_last", "items", "push_s", "tpot_s")})
    ctx = _spans_ctx(evs)
    for name in ("engine_tpot_p50_ms.serve", "proxy_tpot_p50_ms.serve",
                 "proxy_token_us.serve"):
        assert _read(name, ctx) is None
    # the ingress reads spans the parent records too
    assert _read("proxy_ingress_p50_ms.serve", ctx) == pytest.approx(4.0, abs=1e-3)


def test_span_metrics_skip_what_cannot_be_divided():
    one = _spans_ctx(_request("one", 5.0, 1, 0.0))
    assert _read("proxy_tpot_p50_ms.serve", one) is None    # tokens - 1
    assert _read("proxy_token_us.serve", one) == pytest.approx(175.0)
    none = _spans_ctx([_span("proxy", "handler", "x", 5.0, 1.0,
                             tokens=0)])
    assert _read("proxy_token_us.serve", none) is None
    assert _read("proxy_ingress_p50_ms.serve", none) is None


def test_spans_are_collected_once_and_nothing_without_a_runtime():
    mod = spec._module("readers", "request_spans")
    ctx = {"window": W}
    import ray_tpu
    assert mod.spans(ctx) == []         # no runtime here: no raise,
    assert not ray_tpu.is_initialized()     # and none started
    assert ctx["request_spans"] == []
    ctx["request_spans"].append("kept")
    assert mod.spans(ctx) == ["kept"]   # the next metric asks nobody
    assert _read("engine_tpot_p50_ms.serve", {"window": W}) is None
