"""The per-layer metrics that read the stall between decode blocks
(PR 56): each new metric file resolves through the benchmark's own
lookup, agrees with its entry in BENCHMARK.json, and names only counters
and span attributes a live engine carries; read through the accepted
readers on a live tiny engine's counters they keep their order, and on a
program without the counters (the parent) every one reads nothing."""

import asyncio
import os
import sys
import types

import pytest

from ray_tpu.util import events, tracing

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
BENCH = os.path.join(ROOT, "benchmarks")
SERVE = ["serve-chat-open", "serve-exaone-reason-open",
         "serve-mistral4-longdoc-open", "serve-xing4-rag-open"]
# name -> (reader, layer, the cells that report it)
NEW = {
    "engine_stall_ms_per_step.serving":
        ("counter_ratio", "engine scheduler", SERVE),
    "engine_stall_admit_ms_per_step.serving":
        ("counter_ratio", "engine scheduler", SERVE),
    "engine_window_ms_per_step.serving":
        ("counter_ratio", "engine scheduler", SERVE),
    "prefill_own_ms_per_ktok.serving":
        ("counter_ratio", "serving forwards", SERVE),
    "decode_around_ms_per_step.serving":
        ("decode_around_ms_per_step", "serving forwards", SERVE),
    "engine_tpot_stall_p50_ms.serve":
        ("request_spans", "engine scheduler", SERVE[:2]),
}
TID = "7c" * 16


@pytest.fixture(scope="module")
def spec():
    sys.path.insert(0, BENCH)
    try:
        from harness import spec
        yield spec
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def live(spec):
    """What a traced run hands the readers, from a live tiny engine in
    which one request was admitted behind another's decode block: the
    change of ``harness/server.py bench_counters()`` and the long
    request's ``engine/generate`` span."""
    import jax

    from harness.server import BenchLLMServer
    from ray_tpu.llm import LLMEngine
    from ray_tpu.models import llama
    cfg = llama.tiny(vocab_size=64, dim=32, n_layers=2, n_heads=2,
                     n_kv_heads=2, ffn_dim=64, dtype="float32",
                     logits_dtype="float32", attn_impl="reference")
    params = llama.init_params(jax.random.PRNGKey(0), cfg)

    async def go():
        eng = LLMEngine(cfg, params, max_slots=2, max_len=64,
                        prefill_buckets=(8, 16), cache_dtype="float32",
                        steps_per_sync=4, kv_block_size=8,
                        prefix_cache=False)
        server = types.SimpleNamespace(
            engine=eng, _blocks_peak=0,
            _compiles=types.SimpleNamespace(n=0, hits=0, names=[]))
        await eng.generate([9, 8, 7], max_new_tokens=6)     # compile
        events.clear()
        c0 = BenchLLMServer.bench_counters(server)
        tok = tracing.set_request_context(
            tracing.TraceContext(TID, tracing.new_span_id()))
        try:
            long = asyncio.ensure_future(
                eng.generate([3, 5, 7, 11], max_new_tokens=58))
        finally:
            tracing.reset_request_context(tok)
        while eng.stats["tokens_generated"] < c0["tokens_generated"] + 5:
            await asyncio.sleep(0)
        await eng.generate([2, 9, 4], max_new_tokens=6)
        await long
        c1 = BenchLLMServer.bench_counters(server)
        await eng.stop()
        return c0, c1

    c0, c1 = asyncio.run(go())
    span = next(e for e in events.dump() if e.get("cat") == "request"
                and e.get("seg") == "generate" and e.get("trace") == TID)
    events.clear()
    delta = {k: c1[k] - c0[k] for k in c1
             if isinstance(c1[k], (int, float))}
    return {"keys": set(c1), "delta": delta, "span": span}


def _ctx(counters, decode_s=None):
    trace = None if decode_s is None else {
        "programs": {"decode": {"s": decode_s, "calls": 15}}, "kernels": {}}
    return {"trace": trace, "counters": {"window": {}, "trace": counters}}


@pytest.mark.parametrize("name", NEW)
def test_the_metric_file_resolves_and_names_what_the_engine_carries(
        spec, live, name):
    reader, layer, cells = NEW[name]
    mf = spec.metric_file(name)
    entry = next(m for m in spec.benchmark()["per_layer"]
                 if m["name"] == name)
    assert mf["reader"] == reader and callable(spec.reader(reader))
    # held by its reader, its arguments (below) and the cells' MEMBERSHIP:
    # a later cell may join the entry's list
    assert entry["layer"] == layer and set(entry["workloads"]) >= set(cells)
    assert entry["moves"] == "tpot_p50_ms" == mf["moves"]
    for key in ("unit", "better", "source", "layer"):
        assert mf[key] == entry[key], (name, key)
    assert set(cells) <= set(mf["workloads"]) <= set(entry["workloads"])
    for cell in cells:      # one file, every cell that lists it
        assert name in {m["name"] for m in spec.cell(cell)["per_layer"]}
    args = mf.get("args", {})
    if reader == "counter_ratio":
        # cut at the trace's edges, as every per-layer counter of a
        # serve cell is (the profiler's stop stalls the window)
        assert args["scope"] == "trace"
        named = {args["num"], args["den"]} | {args.get("minus", args["num"])}
        assert named <= live["keys"], named - live["keys"]
        # written from the new counter's side: a program without it
        # (the parent) gives nothing, where a missing `minus` raises
        assert args["num"] in ("gap_sum", "gap_admit_sum",
                               "block_window_sum",
                               "loop_prefill_behind_sum")
    elif reader == "request_spans":
        assert (args["component"], args["seg"]) == ("engine", "generate")
        for attr in (*args["num"], *args.get("minus", ())):
            assert live["span"].get(attr) is not None, attr
            assert attr in tracing._REQUEST_SPAN_ARGS


def test_the_readers_on_a_live_engine_s_counters_keep_their_order(
        spec, live):
    d = live["delta"]
    assert d["loop_prefill_behind_count"] == 1 and d["gap_sum"] > 0

    def read(name, ctx):
        mf = spec.metric_file(name)
        return spec.reader(mf["reader"])(ctx, **mf.get("args", {}))
    ctx = _ctx(d, decode_s=0.0)
    stall = read("engine_stall_ms_per_step.serving", ctx)
    admit = read("engine_stall_admit_ms_per_step.serving", ctx)
    window = read("engine_window_ms_per_step.serving", ctx)
    assert 0 < admit <= stall <= window
    assert stall == pytest.approx(1e3 * d["gap_sum"] / d["block_steps_sum"])
    # the prefill's own device time: dispatch to results less the wait
    # behind the block in flight, a thousand prompt tokens
    own = read("prefill_own_ms_per_ktok.serving", ctx)
    assert own == pytest.approx(
        1e6 * (d["ttft_device_sum"] - d["loop_prefill_behind_sum"])
        / d["prefill_tokens_sum"])
    assert 0 < own < 1e6 * d["ttft_device_sum"] / d["prefill_tokens_sum"]
    # with no device time taken out, what is around a step is the
    # window less the stall; the decode programs' time comes off it
    around = read("decode_around_ms_per_step.serving", ctx)
    assert around == pytest.approx(window - stall)
    half = 0.5 * (d["block_window_sum"] - d["gap_sum"])
    assert read("decode_around_ms_per_step.serving",
                _ctx(d, decode_s=half)) == pytest.approx(around / 2)
    # the span's stalled time a token is the stall over its steps
    span = live["span"]
    assert span["tpot_stall_s"] == pytest.approx(
        span["stall_s"] / (span["tokens"] - 1))
    assert 0 < span["stall_admit_s"] <= span["stall_s"] \
        <= d["gap_sum"] + 1e-9


@pytest.mark.parametrize("name", [n for n in NEW
                                  if NEW[n][0] != "request_spans"])
def test_without_a_trace_or_the_new_counters_a_reader_gives_nothing(
        spec, live, name):
    mf = spec.metric_file(name)
    read = spec.reader(mf["reader"])
    args = mf.get("args", {})
    new = ("block_window", "loop_prefill_behind", "request_tpot_stall")
    parent = {k: v for k, v in live["delta"].items()
              if not k.startswith(new)}
    untraced = {"trace": None, "counters": {"window": live["delta"]}}
    assert read(untraced, **args) is None           # a --trace 0 run
    if name.startswith(("engine_stall_ms", "engine_stall_admit_ms")):
        # counters the parent has too: it reads them, as 0
        assert read(_ctx(parent, decode_s=0.0), **args) is not None
    else:
        assert read(_ctx(parent, decode_s=0.0), **args) is None
    if mf["reader"] == "decode_around_ms_per_step":
        assert read(_ctx(live["delta"]), **args) is None    # no trace
        assert read(_ctx({**live["delta"], "block_steps_sum": 0.0},
                         decode_s=0.0)) is None
        no_decode = _ctx(live["delta"], decode_s=0.0)
        no_decode["trace"]["programs"] = {}
        assert read(no_decode) is None
