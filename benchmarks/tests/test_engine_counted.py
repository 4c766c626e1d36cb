"""The per-layer metrics that read what the ENGINE counts and times
(PR 24): the reader that takes its denominator from the engine's
counters over the traced seconds, and the counter-ratio metrics of the
decode gap, the prefill and the stream. On a parent without the counters
every one of them reads None and is left out of the line. Since PR 58
the chat cell's and the hybrid cell's copies are one entry each
(``.serve``); the kernel's counted roofline went with its reader, and the
prefill's time a thousand tokens is read from the copy that stays."""
import pytest

from harness import spec

CELLS = ["serve-chat-open", "serve-exaone-reason-open"]
NEW = {"engine_gap_ms_per_block.serve": "counter_ratio",
       "engine_admit_ms_per_block.serve": "counter_ratio",
       "engine_host_ms_per_block.serve": "counter_ratio",
       "stream_lag_mean_ms.serve": "counter_ratio",
       "decode_dev_ms_per_step_counted.serve":
           "decode_dev_ms_per_counted_step"}


def _ctx(trace_counters, window_counters=None):
    trace = {"programs": {"decode": {"s": 7.5, "calls": 17}}, "kernels": {}}
    return {"trace": trace,
            "counters": {"window": window_counters or {},
                         "trace": trace_counters}}


@pytest.mark.parametrize("cell", CELLS)
def test_new_metrics_resolve_through_both_cells(cell):
    mine = {m["name"]: m for m in spec.cell(cell)["per_layer"]
            if m["name"] in NEW}
    assert set(mine) == set(NEW)
    for name, m in mine.items():
        mf = spec.metric_file(name)
        assert mf["reader"] == NEW[name]
        assert callable(spec.reader(mf["reader"]))
        assert m["moves"] == "tpot_p50_ms" == mf["moves"]
        # one entry for both cells; the list is BENCHMARK.json's alone
        assert m["workloads"] == CELLS and "workloads" not in mf
        for key in ("unit", "better", "source", "layer"):
            assert mf[key] == m[key], (name, key)
    # they were appended, in this order, and later entries after them
    names = [m["name"] for m in spec.benchmark()["per_layer"]]
    assert [n for n in names if n in NEW] == list(NEW)


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


@pytest.mark.parametrize("name, want", [
    ("engine_gap_ms_per_block.serve", 40.0),
    ("engine_admit_ms_per_block.serve", 25.0),
    ("engine_host_ms_per_block.serve", 15.0),
    ("prefill_ms_per_ktok.longdoc", 125.0),
    ("stream_lag_mean_ms.serve", 2.0)])
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
    assert got["engine_admit_ms_per_block.serve"] \
        + got["engine_host_ms_per_block.serve"] \
        == pytest.approx(got["engine_gap_ms_per_block.serve"])
