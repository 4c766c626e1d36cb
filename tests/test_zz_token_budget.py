"""A served token's time, stamped where it is spent (PR 37): the loop's
``decode.account`` phase and what lies inside it, the engine's own time
per output token, the stream hop's two brackets, and the stream
attributes of the proxy's and the replica's handler spans. Order and
counts are exact on the CPU; times are not speed results."""

import asyncio
import http.client
import inspect
import json
import os
import time

import pytest

from ray_tpu.llm import engine as engine_mod
from ray_tpu.llm.engine import PHASES, engine_metrics
from ray_tpu.util import devmon, events, metrics as M, tracing


@pytest.fixture(scope="module")
def tiny_model():
    import jax

    from ray_tpu.models import llama
    cfg = llama.tiny(vocab_size=64, dim=32, n_layers=2, n_heads=2,
                     n_kv_heads=2, ffn_dim=64, dtype="float32",
                     logits_dtype="float32", attn_impl="reference")
    return cfg, llama.init_params(jax.random.PRNGKey(0), cfg)


def _engine(tiny_model, **kw):
    from ray_tpu.llm import LLMEngine
    cfg, params = tiny_model
    kw = {"max_slots": 2, "max_len": 64, "prefill_buckets": (8, 16),
          "cache_dtype": "float32", "steps_per_sync": 4,
          "kv_block_size": 8, "prefix_cache": False, **kw}
    return LLMEngine(cfg, params, **kw)


def _totals() -> dict:
    out = {}
    for key, h in engine_metrics().items():
        if hasattr(h, "boundaries"):
            out[key + "_sum"] = sum(h._sums.values())
            out[key + "_count"] = sum(sum(c) for c in h._counts.values())
    return out


def _delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in b}


class _Recorder:
    """In ``tracing.phase``'s place: the same stamps and histogram, and
    a line in ``log`` on entry and on exit."""
    log: list = []

    def __init__(self, name, hist=None):
        self.name, self.hist = name, hist
        self.t0 = self.t1 = 0.0

    def __enter__(self):
        self.t0 = time.monotonic()
        self.log.append(("enter", self.name, self.t0))
        return self

    def __exit__(self, *exc):
        self.t1 = time.monotonic()
        self.log.append(("exit", self.name, self.t1))
        if self.hist is not None:
            self.hist.observe(self.t1 - self.t0)
        return False

    @property
    def dur(self):
        return self.t1 - self.t0


@pytest.fixture
def recorded(monkeypatch):
    """The loop's phases and every record of a decode block's
    accounting, in one log, in the order they happened."""
    log = _Recorder.log = []
    monkeypatch.setattr(tracing, "phase", _Recorder)
    # engine_metrics() hands every caller its own handles on the shared
    # series, so the class is what gets the recorder
    watched = {engine_metrics()[key].name: key for key in (
        "block_steps", "slot_steps", "ctx_tokens", "kv_fetch_tokens",
        "batch", "tpot", "decode_hop")}
    real_observe = M.Histogram.observe

    def observe(self, value, *a, **kw):
        if self.name in watched:
            log.append(("record", watched[self.name], time.monotonic()))
        return real_observe(self, value, *a, **kw)
    monkeypatch.setattr(M.Histogram, "observe", observe)
    for mod, fn in ((tracing, "record_batch_span"),
                    (devmon, "record_device_window")):
        real = getattr(mod, fn)

        def rec(*a, _fn=fn, _real=real, **kw):
            log.append(("record", _fn, time.monotonic()))
            return _real(*a, **kw)
        monkeypatch.setattr(mod, fn, rec)
    return log


def _blocks(log):
    """The log cut at each ``engine.decode.prepare`` entry: one list a
    decode block (the last runs to the loop's end)."""
    starts = [i for i, e in enumerate(log)
              if e[:2] == ("enter", "engine.decode.prepare")]
    return [log[a:b] for a, b in zip(starts, starts[1:] + [len(log)])]


# --- (i) order, not timing ----------------------------------------------


def test_a_decode_block_leaves_its_phases_in_order_and_flat(
        tiny_model, recorded):
    tid = "7b" * 16

    async def go():
        eng = _engine(tiny_model)
        tok = tracing.set_request_context(
            tracing.TraceContext(tid, tracing.new_span_id()))
        try:
            await eng.generate([3, 5, 7, 9], max_new_tokens=10)
        finally:
            tracing.reset_request_context(tok)
        await eng.stop()

    events.clear()
    asyncio.run(go())
    # none nested in another: every entry is followed by its own exit
    open_ = None
    for kind, name, _ in recorded:
        if kind == "enter":
            assert open_ is None, (open_, name)
            open_ = name
        elif kind == "exit":
            assert open_ == name
            open_ = None
    # 9 decode steps in blocks of 4, 4 and 1, each enqueued before its
    # predecessor is read back (PR 39): the first turn only prepares
    # and dispatches; every later one prepares and dispatches the next
    # block, THEN reads back, accounts and emits the one in flight and
    # yields; the last has nothing left to enqueue
    path = [name for kind, name, _ in recorded if kind == "enter"
            and name.split(".", 1)[1] in (
                "decode.prepare", "decode.dispatch", "decode.readback",
                "decode.account", "emit", "yield")]
    path = path[path.index("engine.decode.prepare"):]
    ahead = ["engine." + p for p in ("decode.prepare", "decode.dispatch")]
    behind = ["engine." + p for p in (
        "decode.readback", "decode.account", "emit", "yield")]
    assert path == ahead + (ahead + behind) * 2 + behind, path
    blocks = _blocks(recorded)
    assert len(blocks) == 3
    for b in blocks[1:]:
        # every record of the block's counters and of _record_block
        # lies between a decode.account's two stamps
        spans = [(b.index(e0), b.index(e1)) for e0, e1 in zip(
            [e for e in b if e[:2] == ("enter", "engine.decode.account")],
            [e for e in b if e[:2] == ("exit", "engine.decode.account")])]
        records = [i for i, e in enumerate(b) if e[0] == "record"]
        assert records and all(
            any(a0 < i < a1 for a0, a1 in spans) for i in records), b
        names = {b[i][1] for i in records}
        assert {"block_steps", "slot_steps", "ctx_tokens", "batch",
                "tpot", "decode_hop", "record_batch_span",
                "record_device_window"} <= names
    assert not [e for e in blocks[0] if e[0] == "record"]
    events.clear()


def test_no_statement_of_the_block_path_lies_outside_a_phase():
    """Between ``_decode_sync``'s return and the ``yield`` phase the
    loop's source has ``with phase(...)`` blocks only, and the two
    lines that keep a block in flight (PR 39): which block that is now,
    and the turn's end when there is none to read back."""
    src = inspect.getsource(engine_mod.LLMEngine._run)
    tail = src[src.index("self._decode_sync"):]
    tail = tail[tail.index("\n") + 1:tail.index('with phase("yield")')]
    lines = [ln for ln in tail.splitlines() if ln.strip()]
    depth = len(lines[0]) - len(lines[0].lstrip())
    top = [ln.strip() for ln in lines
           if len(ln) - len(ln.lstrip()) == depth]
    assert top[:2] == ["self._inflight = new if back is not new else None",
                       "if back is None:"], top
    assert top[2:] and all(
        ln.startswith("with phase(") for ln in top[2:]), top


# --- (ii) the engine's own measure of a token --------------------------------


def test_request_tpot_is_the_block_windows_and_gaps_over_the_steps(
        tiny_model, recorded):
    new = 13
    tid = "6c" * 16

    async def go():
        eng = _engine(tiny_model)
        before = _totals()
        tok = tracing.set_request_context(
            tracing.TraceContext(tid, tracing.new_span_id()))
        try:
            got = [t async for t in eng.generate_stream(
                [2, 4, 6], max_new_tokens=new)]
        finally:
            tracing.reset_request_context(tok)
        await eng.stop()
        return got, _delta(before, _totals())

    events.clear()
    got, d = asyncio.run(go())
    assert len(got) == new
    assert d["request_tpot_count"] == 1
    tpot = d["request_tpot_sum"]
    # ONE pair of stamps, two sinks: the span carries the same number
    gen = [e for e in events.dump() if e.get("cat") == "request"
           and e.get("seg") == "generate" and e.get("trace") == tid]
    assert len(gen) == 1 and gen[0]["tokens"] == new
    assert gen[0]["tpot_s"] == pytest.approx(tpot, rel=1e-9)
    # first dispatch's start to last read-back's end is every block's
    # window: each block is enqueued before its predecessor is read
    # back (PR 39), so the windows touch and no gap lies between them
    disp = [t for kind, name, t in recorded
            if (kind, name) == ("enter", "engine.decode.dispatch")]
    back = [t for kind, name, t in recorded
            if (kind, name) == ("exit", "engine.decode.readback")]
    assert len(disp) == len(back) == 3      # 12 steps in blocks of 4
    span = back[-1] - disp[0]
    longest = max(b1 - d0 for d0, b1 in zip(disp, back))
    assert abs(tpot * (new - 1) - span) <= longest
    assert all(d1 < b0 for b0, d1 in zip(back, disp[1:]))
    # one gap observation a block that carries a request on, none of
    # them a stall; the first block after idle went out with nothing in
    # flight, the others ahead of their predecessors' read-backs
    assert d["gap_count"] == d["gap_admit_count"] == len(disp) - 1
    assert d["gap_sum"] == d["gap_admit_sum"] == 0.0
    assert d["decode_ahead_count"] == len(disp)
    assert d["decode_ahead_sum"] == len(disp) - 1
    events.clear()


def test_a_one_token_request_has_no_tpot(tiny_model):
    async def go():
        eng = _engine(tiny_model)
        before = _totals()
        await eng.generate([1, 2, 3], max_new_tokens=1)
        await eng.stop()
        return _delta(before, _totals())

    assert asyncio.run(go())["request_tpot_count"] == 0


def test_request_tpot_counts_emitted_tokens_not_trimmed_ones(tiny_model):
    """A matched stop sequence is trimmed from the result, not from the
    tokens the request's time is divided by."""
    async def go():
        eng = _engine(tiny_model)
        free = (await eng.generate([5, 6, 7], max_new_tokens=8))["tokens"]
        events.clear()
        tok = tracing.set_request_context(
            tracing.TraceContext("4d" * 16, tracing.new_span_id()))
        try:
            out = await eng.generate([5, 6, 7], max_new_tokens=8,
                                     stop=[free[3:5]])
        finally:
            tracing.reset_request_context(tok)
        await eng.stop()
        return free, out

    free, out = asyncio.run(go())
    assert out["tokens"] == free[:3]
    gen = [e for e in events.dump() if e.get("seg") == "generate"]
    assert len(gen) == 1 and gen[0]["tokens"] == 3
    assert gen[0]["tpot_s"] > 0         # five emits: four intervals
    events.clear()


# --- (iii) the stream hop's two brackets --------------------------------------


@pytest.mark.parametrize("new", [1, 2, 9])
def test_stream_lag_and_consume_are_observed_once_a_token(tiny_model, new):
    async def go():
        eng = _engine(tiny_model)
        before = _totals()
        got = []
        async for t in eng.generate_stream([5, 6, 7], max_new_tokens=new):
            got.append(t)
            await asyncio.sleep(0.002)      # the consumer's own work
        await eng.stop()
        return got, _delta(before, _totals())

    got, d = asyncio.run(go())
    assert len(got) == new
    assert d["stream_lag_count"] == d["stream_consume_count"] == new
    assert d["stream_consume_sum"] >= 0.002 * new


# --- (iv) through proxy and replica -----------------------------------------------


@pytest.fixture(scope="module")
def served():
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import LLMConfig, build_llm_deployment
    old = os.environ.get("RAY_TPU_TRACE_SLOW_THRESHOLD_S")
    os.environ["RAY_TPU_TRACE_SLOW_THRESHOLD_S"] = "60.0"
    ray_tpu.init(num_cpus=8)
    cfg = LLMConfig(model="tiny",
                    model_overrides=dict(
                        vocab_size=128, dim=64, n_layers=2, n_heads=4,
                        n_kv_heads=2, ffn_dim=128, dtype="float32",
                        logits_dtype="float32", attn_impl="reference"),
                    max_slots=2, max_len=128, prefill_buckets=(16,),
                    cache_dtype="float32")
    serve.run(build_llm_deployment(cfg, name="tok"), name="tok_app",
              route_prefix="/tok", ready_timeout_s=300.0)
    yield serve.proxy_address()
    try:
        serve.shutdown()
    finally:
        ray_tpu.shutdown()
        if old is None:
            os.environ.pop("RAY_TPU_TRACE_SLOW_THRESHOLD_S", None)
        else:
            os.environ["RAY_TPU_TRACE_SLOW_THRESHOLD_S"] = old


def _sse(addr, new: int):
    conn = http.client.HTTPConnection(addr["host"], addr["port"],
                                      timeout=120)
    conn.request("POST", "/tok",
                 body=json.dumps({"tokens": [3, 7, 11],
                                  "max_new_tokens": new}),
                 headers={"Content-Type": "application/json",
                          "Accept": "text/event-stream"})
    resp = conn.getresponse()
    assert resp.status == 200
    tid = resp.getheader("X-Trace-Id")
    raw = resp.read().decode()
    conn.close()
    return tid, raw.count("data: {\"token\"")


def _request_events(tid, timeout_s=30.0):
    """The trace's own request spans, once every hop has flushed."""
    import ray_tpu
    deadline = time.monotonic() + timeout_s
    while True:
        evs = [e for e in ray_tpu.timeline(all_nodes=True)
               if e.get("cat") == "request" and e.get("trace") == tid]
        have = {(e.get("component"), e.get("seg")) for e in evs}
        if {("proxy", "handler"), ("proxy", "request"),
                ("replica", "handler"), ("engine", "generate")} <= have:
            return evs
        assert time.monotonic() < deadline, have
        time.sleep(0.5)


@pytest.fixture(scope="module")
def streamed(served):
    """Two streamed requests of different lengths, warm: {tokens: the
    trace's request events}."""
    _sse(served, 4)                             # compile
    out = {}
    for new in (5, 21):
        tid, n = _sse(served, new)
        assert n == new and tid
        out[new] = _request_events(tid)
    return out


@pytest.mark.parametrize("new", [5, 21])
def test_a_streamed_request_leaves_one_proxy_handler_span_with_its_sums(
        streamed, new):
    spans = [e for e in streamed[new]
             if (e["component"], e["seg"]) == ("proxy", "handler")]
    assert len(spans) == 1
    e = spans[0]
    assert e["tokens"] == new
    for key in ("first_token_s", "get_s", "free_s", "write_s"):
        assert e[key] >= 0, key
    assert e["ts"] <= e["t_first"] <= e["t_last"] \
        <= e["ts"] + e["dur"] + 0.05
    # the three stages lie inside the handler's own span, one after
    # the other
    assert e["get_s"] + e["free_s"] + e["write_s"] <= e["dur"]
    root = next(x for x in streamed[new] if x.get("root"))
    assert e["first_token_s"] <= root["dur"]


@pytest.mark.parametrize("new", [5, 21])
def test_the_replica_and_engine_spans_carry_the_stream_hop_and_tpot(
        streamed, new):
    rep = [e for e in streamed[new]
           if (e["component"], e["seg"]) == ("replica", "handler")]
    assert len(rep) == 1 and rep[0]["items"] == new
    assert 0 <= rep[0]["push_s"] <= rep[0]["dur"]
    gen = [e for e in streamed[new]
           if (e["component"], e["seg"]) == ("engine", "generate")]
    assert len(gen) == 1 and gen[0]["tokens"] == new
    assert 0 < gen[0]["tpot_s"] * (new - 1) <= gen[0]["dur"]
    # delivery shifts tokens, it does not stretch the gap beyond the
    # request: the socket's span of tokens fits the proxy's handler
    prox = next(e for e in streamed[new]
                if (e["component"], e["seg"]) == ("proxy", "handler"))
    assert prox["t_last"] - prox["t_first"] <= prox["dur"]


def test_a_streams_events_do_not_grow_with_its_length(streamed):
    # (a request that waited for admission, or paid for the routing
    # table's refresh, has that segment besides: not the tokens')
    kinds = {new: sorted((e["component"], e["seg"]) for e in evs
                         if e["seg"] not in ("queue", "route")
                         or e["component"] != "proxy")
             for new, evs in streamed.items()}
    assert kinds[5] == kinds[21], kinds
    assert len(streamed[21]) <= 10


def test_the_proxy_histogram_has_a_stage_tag_and_fine_buckets():
    """One sample a stream and stage (get, free, write), never one a
    token; the proxy is its own process, so its shape is checked."""
    from ray_tpu.serve.proxy import proxy_metrics
    h = proxy_metrics()["stream_token"]
    assert h.name == "serve_proxy_stream_token_s"
    assert set(h.tag_keys) == {"deployment", "stage"}
    assert h.boundaries[0] <= 1e-5 and h.boundaries[-1] >= 1


# --- (v) catalog, phases and histograms agree ---------------------------------------


@pytest.mark.parametrize("phase", PHASES)
def test_every_phase_has_its_histogram_and_its_line_in_the_catalog(phase):
    m = engine_metrics()
    key = engine_mod._loop_key(phase)
    assert key == "loop_" + phase.replace(".", "_")
    assert m[key].name == f"llm_{key}_s"
    assert f"engine.{phase}" in m[key].description
    doc = " ".join(engine_metrics.__doc__.split())
    assert "llm_loop_<phase>_s" in doc
    assert phase in doc     # the catalog's list of phases names it


@pytest.mark.parametrize("key", sorted(
    k for k in engine_metrics() if not k.startswith("loop_")))
def test_every_series_is_named_in_the_catalog(key):
    name = engine_metrics()[key].name
    assert name in engine_metrics.__doc__, name


# --- the counting made cheap, and the waterfall's line ------------------------------------------


@pytest.mark.parametrize("window", [None, 16, 100, 128])
@pytest.mark.parametrize("block_size", [8, 16])
def test_fetched_positions_run_is_the_sum_of_its_steps(block_size, window):
    from ray_tpu.ops.pallas import paged_attention as pa
    for length in range(0, 300):
        for steps in (1, 2, 4, 8):
            want = sum(int(pa.fetched_positions(length + j, block_size,
                                                window))
                       for j in range(steps))
            assert pa.fetched_positions_run(
                length, steps, block_size, window) == want, (length, steps)


def test_stream_attrs_is_one_line_per_span_kind():
    assert tracing.stream_attrs({"component": "proxy", "seg": "queue",
                                 "dur": 0.1}) == ""
    eng = tracing.stream_attrs({"tpot_s": 0.0125})
    assert eng == "12.500 ms/token"
    rep = tracing.stream_attrs({"items": 10, "push_s": 0.0005})
    assert rep == "10 items, push 50 us each"
    prox = tracing.stream_attrs({
        "tokens": 11, "first_token_s": 0.05, "get_s": 0.0011,
        "free_s": 0.0022, "write_s": 0.0033, "t_first": 100.0,
        "t_last": 100.1})
    assert "11 tokens, first after 50.00 ms" in prox
    assert "10.000 ms/token at the socket" in prox
    assert "get/free/write 100/200/300 us a token" in prox
    assert "\n" not in prox
    # a stream that wrote nothing has no socket stamps
    assert tracing.stream_attrs({"tokens": 0}) == ""
