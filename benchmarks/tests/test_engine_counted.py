"""The per-layer metrics that read what the ENGINE counts and times
(PR 24): the two readers that take their denominators from the engine's
counters over the traced seconds, and the counter-ratio metrics of the
decode gap, the prefill and the stream. On a parent without the counters
every one of them reads None and is left out of the line."""
import pytest

from harness import kernels, peaks, spec

NEW = {"engine_gap_ms_per_block.chat": "counter_ratio",
       "engine_admit_ms_per_block.chat": "counter_ratio",
       "engine_host_ms_per_block.chat": "counter_ratio",
       "prefill_ms_per_ktok.chat": "counter_ratio",
       "stream_lag_mean_ms.chat": "counter_ratio",
       "decode_dev_ms_per_step_counted.chat":
           "decode_dev_ms_per_counted_step",
       "paged_decode_roofline_counted.chat":
           "paged_decode_roofline_counted"}

MODEL = {"num_hidden_layers": 8, "num_attention_heads": 32,
         "num_key_value_heads": 8, "hidden_size": 4096}


def _ctx(trace_counters, window_counters=None):
    trace = {"programs": {"decode": {"s": 7.5, "calls": 17}},
             "kernels": {"paged_decode": {"s": 5.0, "calls": 400}}}
    return {"trace": trace, "model": MODEL,
            "info": {"device": {"kind": "TPU v5 lite"}},
            "counters": {"window": window_counters or {},
                         "trace": trace_counters}}


def test_new_metrics_resolve_through_the_cell():
    cell = spec.cell("serve-chat-open")
    mine = {m["name"]: m for m in cell["per_layer"] if m["name"] in NEW}
    assert set(mine) == set(NEW)
    for name, m in mine.items():
        mf = spec.metric_file(name)
        assert mf["reader"] == NEW[name]
        assert callable(spec.reader(mf["reader"]))
        assert m["moves"] == "tpot_p50_ms" == mf["moves"]
        for key in ("unit", "better", "source", "layer", "workloads"):
            assert mf[key] == m[key], (name, key)
    # they were appended: what stood before them still stands first
    names = [m["name"] for m in spec.benchmark()["per_layer"]]
    assert names[-len(NEW):] == list(NEW)


def test_decode_ms_per_counted_step():
    read = spec.reader("decode_dev_ms_per_counted_step")
    # 7.5 s of decode programs over the 50 steps the engine counted
    assert read(_ctx({"block_steps_sum": 50.0})) == pytest.approx(150.0)
    assert read({**_ctx({"block_steps_sum": 50.0}), "trace": None}) is None
    assert read(_ctx({})) is None               # the parent: no counter
    assert read(_ctx({"block_steps_sum": 0.0})) is None
    untraced = _ctx({})
    del untraced["counters"]["trace"]           # a --trace 0 run
    assert read(untraced) is None


def test_paged_roofline_from_counted_contexts():
    read = spec.reader("paged_decode_roofline_counted")
    c = {"ctx_tokens_sum": 400000.0, "slot_steps_sum": 1340.0}
    kvh, g, hd = 8, 4, 128
    need = 8 * kernels.paged_decode_bytes(400000.0, 1340.0, kvh, g, hd)
    flops = 8 * kernels.paged_decode_flops(400000.0, kvh, g, hd)
    pk = peaks.peaks("TPU v5 lite")
    least = max(need / pk["hbm_bytes_per_s"], flops / pk["bf16_flops"])
    assert least == need / pk["hbm_bytes_per_s"]    # bandwidth rules
    assert read(_ctx(c)) == pytest.approx(100.0 * least / 5.0)
    assert 0 < read(_ctx(c)) < 100
    assert read({**_ctx(c), "trace": None}) is None
    assert read(_ctx({})) is None               # the parent: no counters
    assert read(_ctx({"ctx_tokens_sum": 1.0, "slot_steps_sum": 0})) is None
    no_kernel = _ctx(c)
    no_kernel["trace"] = {"programs": {}, "kernels": {}}
    assert read(no_kernel) is None


@pytest.mark.parametrize("name, want", [
    ("engine_gap_ms_per_block.chat", 40.0),
    ("engine_admit_ms_per_block.chat", 25.0),
    ("engine_host_ms_per_block.chat", 15.0),
    ("prefill_ms_per_ktok.chat", 125.0),
    ("stream_lag_mean_ms.chat", 2.0)])
def test_counter_metrics_by_hand(name, want):
    window = {"gap_sum": 4.0, "gap_admit_sum": 2.5, "gap_count": 100.0,
              "ttft_device_sum": 2.0, "prefill_tokens_sum": 16000.0,
              "stream_lag_sum": 14.0, "stream_lag_count": 7000.0}
    mf = spec.metric_file(name)
    read = spec.reader(mf["reader"])
    # the gap's three read the traced seconds (a traced run's window
    # holds the profiler's stop, which freezes the loop for seconds and
    # lands in one gap); the other two read the whole window
    assert mf["args"]["scope"] == (
        "trace" if name.startswith("engine_") else "window")
    ctx = _ctx(window, window)
    assert read(ctx, **mf["args"]) == pytest.approx(want)
    # a parent without the counters: nothing to read, nothing raised
    old = {"ttft_device_sum": 2.0}
    assert read(_ctx(old, old), **mf["args"]) is None
    untraced = _ctx(window, window)
    del untraced["counters"]["trace"]
    if mf["args"]["scope"] == "trace":
        assert read(untraced, **mf["args"]) is None


def test_admit_and_host_add_up_to_the_gap():
    c = {"gap_sum": 3.7, "gap_admit_sum": 1.3, "gap_count": 91.0}
    got = {n: spec.reader("counter_ratio")(
        _ctx(c), **spec.metric_file(n)["args"])
        for n in NEW if n.startswith("engine_")}
    assert got["engine_admit_ms_per_block.chat"] \
        + got["engine_host_ms_per_block.chat"] \
        == pytest.approx(got["engine_gap_ms_per_block.chat"])
