"""Device-plane observability (util/devmon.py): XLA compile spans +
recompile-storm detection, HBM accounting with the CPU live-arrays
fallback, duty-cycle estimation, the "device" event sub-budget, the
/devices surfaces, and engine KV attribution + histogram exemplars.
Late-alphabet module name keeps the tier-1 870 s cutoff stable."""

import asyncio
import json
import os
import time
import urllib.request

import pytest

from ray_tpu.config import Config, set_config
from ray_tpu.util import devmon, events, tracing


def _reset():
    events.clear()
    devmon._reset_for_tests()


def _metric_sum(name) -> float:
    from ray_tpu.util import metrics as m
    mm = m._REGISTRY.get(name)
    return sum(mm._values.values()) if mm is not None else 0.0


# -- compile spans ------------------------------------------------------------


def test_compile_span_recording_and_metrics():
    _reset()
    before = _metric_sum("xla_compiles_total")
    devmon.record_compile("jit(prefill)", 0.25)
    evs = [e for e in events.dump() if e.get("cat") == "device"
           and e.get("name") == "compile"]
    assert len(evs) == 1
    e = evs[0]
    assert e["fn"] == "jit(prefill)" and not e["cache_hit"]
    assert abs(e["dur"] - 0.25) < 1e-9
    # span START backdated by the duration (the record fires at finish)
    assert e["ts"] <= time.time() - 0.25 + 1.0
    assert _metric_sum("xla_compiles_total") == before + 1


def test_cache_hit_spans_are_suppressed_from_recompile_counts():
    """A persistent-compilation-cache hit records a span (visible in
    `ray-tpu devices`) but must NOT feed the recompile counter or the
    storm detector — a cold process warming from cache is healthy."""
    _reset()
    set_config(Config.from_env(devmon_recompile_threshold=2,
                               devmon_recompile_window_s=300.0))
    try:
        rec0 = _metric_sum("xla_recompiles_total")
        hits0 = _metric_sum("xla_cache_hits_total")
        storms0 = _metric_sum("xla_recompile_storms_total")
        for _ in range(5):
            devmon.record_compile("warm_fn", 0.01, cache_hit=True)
        assert _metric_sum("xla_recompiles_total") == rec0
        assert _metric_sum("xla_recompile_storms_total") == storms0
        assert _metric_sum("xla_cache_hits_total") == hits0 + 5
        evs = [e for e in events.dump() if e.get("cat") == "device"
               and e.get("name") == "compile"]
        assert len(evs) == 5 and all(e["cache_hit"] for e in evs)
        assert not [e for e in events.dump()
                    if e.get("name") == "recompile_storm"]
    finally:
        set_config(Config.from_env())


def test_persistent_cache_hit_event_sequencing_records_one_hit_span():
    """jax fires the cache-retrieval duration INSIDE the backend-
    compile timing context and the backend event at its exit (hit or
    miss): the listener must fold the pair into ONE span flagged
    cache_hit, not a hit span plus a phantom recompile."""
    _reset()
    rec0 = _metric_sum("xla_recompiles_total")
    devmon._TLS.pending_fn = "warm_pair"
    devmon._on_duration(devmon.CACHE_RETRIEVAL_EVENT, 0.001)
    devmon._on_duration(devmon.BACKEND_COMPILE_EVENT, 0.002)
    evs = [e for e in events.dump() if e.get("name") == "compile"]
    assert len(evs) == 1 and evs[0]["cache_hit"]
    assert evs[0]["fn"] == "warm_pair"
    # the flag is consumed: the NEXT backend compile is a real miss
    devmon._TLS.pending_fn = "cold_fn"
    devmon._on_duration(devmon.BACKEND_COMPILE_EVENT, 0.2)
    by_fn = {e["fn"]: e for e in events.dump()
             if e.get("name") == "compile"}
    assert len(by_fn) == 2 and not by_fn["cold_fn"]["cache_hit"]
    assert _metric_sum("xla_recompiles_total") == rec0
    _reset()


def test_recompile_storm_gate_is_deterministic():
    """With threshold T=3 in a long window: compiles 1..2 flag
    nothing, compile 3 flags EXACTLY one storm, further compiles
    inside the same window don't re-flag; the recompile counter counts
    every compile beyond the first."""
    _reset()
    set_config(Config.from_env(devmon_recompile_threshold=3,
                               devmon_recompile_window_s=600.0))
    try:
        rec0 = _metric_sum("xla_recompiles_total")
        storms0 = _metric_sum("xla_recompile_storms_total")
        for _ in range(2):
            devmon.record_compile("hot_fn", 0.01)
        assert _metric_sum("xla_recompile_storms_total") == storms0
        for _ in range(4):
            devmon.record_compile("hot_fn", 0.01)
        storms = [e for e in events.dump()
                  if e.get("name") == "recompile_storm"]
        assert len(storms) == 1 and storms[0]["fn"] == "hot_fn"
        assert storms[0]["count"] == 3
        assert _metric_sum("xla_recompile_storms_total") == storms0 + 1
        # 6 compiles => 5 recompiles (the first is not a RE-compile)
        assert _metric_sum("xla_recompiles_total") == rec0 + 5
        # threshold 0 disables the gate entirely
        _reset()
        set_config(Config.from_env(devmon_recompile_threshold=0,
                                   devmon_recompile_window_s=600.0))
        for _ in range(10):
            devmon.record_compile("hot_fn2", 0.01)
        assert not [e for e in events.dump()
                    if e.get("name") == "recompile_storm"]
    finally:
        set_config(Config.from_env())


def test_real_jax_compiles_are_captured_with_function_names():
    """The jax.monitoring listener + log-line name correlation: a
    fresh jit compile lands in the "device" category with the jitted
    function's name; install() is idempotent (no double records)."""
    import jax
    import jax.numpy as jnp
    assert devmon.install() and devmon.install()
    _reset()

    def devmon_named_fn(x):
        return x * 3 + 1

    f = jax.jit(devmon_named_fn)
    # unique shape per run so the in-memory jit cache can't elide it
    n = 3 + (os.getpid() % 97)
    f(jnp.ones((n,))).block_until_ready()
    mine = [e for e in events.dump() if e.get("cat") == "device"
            and e.get("name") == "compile"
            and "devmon_named_fn" in str(e.get("fn"))]
    assert len(mine) == 1, [e.get("fn") for e in events.dump()
                            if e.get("name") == "compile"]
    assert mine[0]["dur"] > 0 and not mine[0]["cache_hit"]


# -- HBM accounting -----------------------------------------------------------


def test_hbm_snapshot_cpu_fallback_aggregates_live_arrays():
    """CPU devices report memory_stats() None: the snapshot must fall
    back to jax.live_arrays() aggregation, attribute a live array's
    bytes to its device, keep a peak watermark, and set the gauges."""
    import jax.numpy as jnp
    _reset()
    arr = jnp.ones((4096,), jnp.float32)      # 16 KB held live
    devmon.mark_backend_live()      # this process put work on a device
    rows = devmon.hbm_snapshot()
    assert rows, "no local devices snapshotted"
    by_dev = {r["device"]: r for r in rows}
    assert all(r["source"] == "live_arrays" for r in rows)
    d0 = by_dev[devmon._device_label(arr.devices().pop())]
    assert d0["used"] >= arr.nbytes
    assert d0["peak"] >= d0["used"]
    assert d0["limit"] == 0                   # CPU reports no capacity
    assert _metric_sum("device_hbm_used_bytes") >= arr.nbytes
    # events recorded for the /devices surfaces
    hbm = [e for e in events.dump() if e.get("cat") == "device"
           and e.get("name") == "hbm"]
    assert len(hbm) == len(rows)
    # peak survives the array dying
    del arr
    rows2 = devmon.hbm_snapshot(record=False)
    d1 = {r["device"]: r for r in rows2}[d0["device"]]
    assert d1["peak"] >= d0["used"]


# -- duty cycle ---------------------------------------------------------------


def test_duty_cycle_unions_overlapping_windows():
    _reset()
    set_config(Config.from_env(devmon_duty_horizon_s=10.0))
    try:
        now = time.time()
        devmon.record_device_window("decode", now - 9.0, now - 8.0)
        devmon.record_device_window("prefill", now - 8.5, now - 7.5)
        # overlap must union (not sum): busy = 9.0..7.5 = 1.5 s
        duty = devmon.duty_cycle(now=now)
        assert abs(duty - 0.15) < 0.01, duty
        # windows render as per-device lanes; zero-length ones drop
        devmon.record_device_window("noop", now, now)
        wins = [e for e in events.dump() if e.get("name") == "window"]
        assert {e["seg"] for e in wins} == {"decode", "prefill"}
        assert devmon.duty_cycle(horizon_s=0.25, now=now - 20) == 0.0
    finally:
        set_config(Config.from_env())


def test_trace_step_duty_window_survives_request_tracing_off(
        monkeypatch):
    """RAY_TPU_TRACE_REQUESTS=0 must not silently zero the train
    plane's duty signal: trace_step records its device window even
    when no trace context can be minted (devmon has its own
    RAY_TPU_DEVMON switch)."""
    from ray_tpu.train.api import TrainContext
    _reset()
    monkeypatch.setattr(tracing, "_REQ", False)
    ctx = TrainContext(0, 1, 0, 0, None)
    with ctx.trace_step() as tid:
        assert tid is None
        time.sleep(0.01)
    wins = [e for e in events.dump() if e.get("name") == "window"]
    assert len(wins) == 1 and wins[0]["seg"] == "train_step"
    assert not [e for e in events.dump() if e.get("cat") == "request"]
    _reset()


# -- event sub-budget ---------------------------------------------------------


def test_device_window_flood_cannot_evict_task_or_compile_spans():
    """Duty windows (high rate: one per decode block) have their OWN
    buffer budget, separate from both the task exec spans the
    timeline is built on AND the rare "device" compile/storm/hbm
    events the /devices surfaces are built on — a steady serving load
    must not age a storm flag out of view."""
    _reset()
    from ray_tpu.util.events import _CATEGORY_CAPS
    assert "device" in _CATEGORY_CAPS
    assert "device_window" in _CATEGORY_CAPS
    tracing.record_exec("ab" * 8, "task", "precious_task", 0.0, 1.0)
    devmon.record_compile("precious_compile", 0.1)
    for i in range(_CATEGORY_CAPS["device_window"] * 3):
        devmon.record_device_window("decode", float(i),
                                    float(i) + 0.001, device="cpu:0")
    evs = events.dump()
    assert [e for e in evs if e.get("name") == "exec"
            and e.get("target") == "precious_task"]
    assert [e for e in evs if e.get("name") == "compile"
            and e.get("fn") == "precious_compile"]
    n_win = sum(1 for e in evs if e.get("cat") == "device_window")
    assert n_win <= _CATEGORY_CAPS["device_window"]
    _reset()


# -- state rows + summary -----------------------------------------------------


def _synthetic_device_events():
    t = time.time()
    return [
        {"cat": "device", "name": "hbm", "device": "tpu:0", "used": 100,
         "limit": 1000, "peak": 150, "duty": 0.5,
         "source": "memory_stats", "ts": t - 10, "pid": 7, "node": "n1"},
        {"cat": "device", "name": "hbm", "device": "tpu:0", "used": 200,
         "limit": 1000, "peak": 250, "duty": 0.7,
         "source": "memory_stats", "ts": t - 1, "pid": 7, "node": "n1"},
        {"cat": "device", "name": "compile", "fn": "jit(prefill)",
         "dur": 0.5, "cache_hit": False, "ts": t - 9, "pid": 7,
         "node": "n1"},
        {"cat": "device", "name": "compile", "fn": "jit(prefill)",
         "dur": 0.3, "cache_hit": False, "ts": t - 8, "pid": 7,
         "node": "n1", "trace": "ab" * 16},
        {"cat": "device", "name": "compile", "fn": "jit(prefill)",
         "dur": 0.01, "cache_hit": True, "ts": t - 7, "pid": 7,
         "node": "n1"},
        # a DIFFERENT process cold-compiling the same fn once: a
        # healthy cluster-wide warmup, not a recompile
        {"cat": "device", "name": "compile", "fn": "jit(prefill)",
         "dur": 0.2, "cache_hit": False, "ts": t - 6.5, "pid": 8,
         "node": "n2"},
        {"cat": "device", "name": "recompile_storm", "fn": "jit(prefill)",
         "count": 3, "window_s": 60.0, "ts": t - 6, "pid": 7,
         "node": "n1"},
        {"cat": "device_window", "name": "window", "seg": "decode",
         "device": "tpu:0", "ts": t - 5, "dur": 0.1, "pid": 7,
         "node": "n1"},
        {"cat": "request", "name": "span", "trace": "cd" * 16, "ts": t},
    ]


def test_devices_from_events_and_summarize():
    from ray_tpu.util.state import devices_from_events, summarize_devices
    rows = devices_from_events(_synthetic_device_events())
    # duty windows are a chrome-trace concern; request spans excluded
    assert {r["kind"] for r in rows} == {"hbm", "compile", "storm"}
    s = summarize_devices(rows)
    assert len(s["devices"]) == 1
    d = s["devices"][0]
    # the LATEST snapshot wins per (node, pid, device)
    assert d["used"] == 200 and d["duty"] == 0.7 and d["peak"] == 250
    assert len(s["compiles"]) == 1
    c = s["compiles"][0]
    assert c["compiles"] == 3 and c["cache_hits"] == 1
    # recompiles are PER PROCESS: pid 7 compiled twice (1 recompile);
    # pid 8's single cold compile is healthy warmup, not a recompile
    assert c["recompiles"] == 1
    assert abs(c["total_s"] - 1.0) < 1e-9
    assert abs(c["max_s"] - 0.5) < 1e-9
    assert len(s["storms"]) == 1 and s["storms"][0]["count"] == 3
    assert s["hbm_used_bytes"] == 200
    # the limit applies PER KIND, newest first: steady hbm snapshots
    # must not age compile/storm rows out of the summary
    one = devices_from_events(_synthetic_device_events(), limit=1)
    assert [r["kind"] for r in one].count("hbm") == 1
    assert {r["kind"] for r in one} == {"hbm", "compile", "storm"}
    assert one[0]["kind"] == "hbm" and one[0]["used"] == 200


# -- trace-waterfall integration ---------------------------------------------


def test_compile_span_rides_the_request_trace_waterfall():
    """A compile under an ambient request context stamps the trace id;
    filter_trace pulls it into that ONE request's event set and
    to_chrome renders it on the dev:compile lane — "this request was
    slow because it compiled" in the waterfall."""
    from ray_tpu.util.tracing import filter_trace, to_chrome
    _reset()
    ctx = tracing.mint_context()
    other = tracing.mint_context()
    tok = tracing.set_request_context(ctx)
    try:
        devmon.record_compile("jit(prefill)", 0.4)
    finally:
        tracing.reset_request_context(tok)
    devmon.record_compile("jit(unrelated)", 0.1)   # no ambient trace
    devmon.record_device_window("decode", time.time() - 0.2,
                                time.time(), trace=ctx.trace_id)
    tracing.finish_request(ctx, time.time() - 1.0, time.time())
    evs = events.dump()
    mine = filter_trace(evs, ctx.trace_id)
    fns = {e.get("fn") for e in mine if e.get("name") == "compile"}
    assert fns == {"jit(prefill)"}
    assert not filter_trace(evs, other.trace_id)
    recs = to_chrome(evs, trace_id=ctx.trace_id)
    lanes = {r["tid"] for r in recs if r.get("ph") == "X"}
    assert "dev:compile" in lanes, lanes
    # the trace-stamped duty window rides along on its device lane
    assert any(str(t).startswith("dev:") and t != "dev:compile"
               for t in lanes), lanes
    comp = [r for r in recs if r.get("tid") == "dev:compile"]
    assert comp and comp[0]["name"] == "xla:jit(prefill)"
    assert comp[0]["args"]["trace"] == ctx.trace_id
    # storms render as instants on the compile lane (full timeline)
    events.record("device", "recompile_storm", fn="f", count=3,
                  window_s=60.0, ts=time.time(), pid=1)
    full = to_chrome(events.dump())
    assert [r for r in full if r.get("ph") == "I"
            and r["name"] == "storm:f"]
    _reset()


# -- engine integration: KV attribution, exemplars, duty windows -------------


@pytest.fixture(scope="module")
def tiny_model():
    import jax

    from ray_tpu.models import llama
    cfg = llama.tiny(vocab_size=64, dim=32, n_layers=2, n_heads=2,
                     n_kv_heads=2, ffn_dim=64, dtype="float32",
                     logits_dtype="float32", attn_impl="reference")
    return cfg, llama.init_params(jax.random.PRNGKey(0), cfg)


def test_engine_kv_accounting_exemplars_and_duty_windows(tiny_model):
    from ray_tpu.llm import LLMEngine
    cfg, params = tiny_model
    _reset()
    tid = "ee" * 16

    async def go():
        eng = LLMEngine(cfg, params, max_slots=2, max_len=64,
                        prefill_buckets=(8,), cache_dtype="float32",
                        steps_per_sync=4)
        # KV gauges live from construction. The paged default prices
        # LIVE blocks: none yet, and the whole pool is headroom
        from ray_tpu.llm import kvcache
        kv0 = eng._m["kv_bytes"]._values[()]
        hr0 = eng._m["kv_headroom"]._values[()]
        assert kv0 == 0
        assert hr0 == kvcache.kind_block_bytes(eng._pool)[kvcache.GLOBAL] \
            * eng._kv.free_blocks() > 0
        tok = tracing.set_request_context(
            tracing.TraceContext(tid, tracing.new_span_id()))
        try:
            await eng.generate([3, 5, 7], max_new_tokens=8)
        finally:
            tracing.reset_request_context(tok)
        await eng.stop()
        return eng

    eng = asyncio.run(go())
    # the finished request's full blocks stay, as prefix cache
    assert eng._m["kv_bytes"]._values[()] > 0
    # request HBM high-watermark on the terminal engine span
    gen = [e for e in events.dump() if e.get("cat") == "request"
           and e.get("trace") == tid and e.get("seg") == "generate"]
    assert len(gen) == 1
    from ray_tpu.llm import kvcache
    expect = kvcache.kind_block_bytes(eng._pool)[kvcache.GLOBAL] \
        // eng._block * (3 + 8)
    assert gen[0]["kv_bytes"] == expect > 0
    # PR 9 exemplars extended to TPOT and batch-size histograms: a
    # p99 bucket links to this concrete trace
    from ray_tpu.util import metrics as m
    for name in ("llm_tpot_s", "llm_batch_size"):
        h = m._REGISTRY[name]
        assert any(x[0] == tid for ex in h._exemplars.values()
                   for x in ex.values()), name
    # prefill + decode bracketed device windows (duty-cycle feed)
    wins = [e for e in events.dump()
            if e.get("cat") == "device_window"]
    segs = {e["seg"] for e in wins}
    assert {"prefill", "decode"} <= segs, segs
    assert any(e.get("trace") == tid for e in wins)
    assert devmon.duty_cycle(horizon_s=60.0) > 0.0
    _reset()


# -- lint: knob family + device metric registration ---------------------------


def test_devmon_knobs_and_device_metrics_lint():
    """The devmon_* Config knobs are a registered lint family (every
    knob test-exercised — this module references them all), and every
    device-family metric literal (device_/xla_/llm_kv_) in the source
    tree is registered by instantiate_all()."""
    import importlib.util
    path = os.path.join(os.path.dirname(__file__), os.pardir,
                        "scripts", "check_metrics_lint.py")
    spec = importlib.util.spec_from_file_location(
        "check_metrics_lint", os.path.abspath(path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert "devmon" in mod.KNOB_FAMILIES
    expect = {"_".join(["devmon", "recompile", "threshold"]),
              "_".join(["devmon", "recompile", "window", "s"]),
              "_".join(["devmon", "hbm", "interval", "s"]),
              "_".join(["devmon", "duty", "horizon", "s"])}
    assert expect <= set(mod.family_knobs("devmon"))
    assert mod.lint_knob_tests(families=["devmon"]) == []
    registry = mod.instantiate_all()
    for name in ("xla_compiles_total", "xla_recompiles_total",
                 "xla_recompile_storms_total", "xla_compile_s",
                 "device_hbm_used_bytes", "device_hbm_limit_bytes",
                 "device_hbm_peak_bytes", "device_duty_cycle",
                 "llm_kv_cache_bytes", "llm_kv_cache_headroom_bytes"):
        assert name in registry, name
    assert mod.lint_device_metric_registration(registry) == []
    # the scan has teeth: an unregistered literal is flagged
    errs = mod.lint_device_metric_registration(
        registry, [("fake.py:1", "xla_bogus_total")])
    assert len(errs) == 1 and "xla_bogus_total" in errs[0]
    assert mod.lint(registry) == []


# -- dashboard ----------------------------------------------------------------


def test_dashboard_devices_page_renders_rows():
    from ray_tpu.util import dashboard

    async def fetch(method, **kw):
        assert method == "collect_timeline"
        return {"events": _synthetic_device_events()}

    page = asyncio.run(dashboard.render("/devices", [fetch]))
    html = page.decode()
    assert "tpu:0" in html and "XLA compiles" in html
    assert "jit(prefill)" in html
    assert "recompile storm" in html          # the storm banner
    assert "/devices" in html                 # nav link present


# -- live-cluster e2e ---------------------------------------------------------


@pytest.fixture()
def devmon_cluster():
    env = {"RAY_TPU_DEVMON_RECOMPILE_THRESHOLD": "2",
           "RAY_TPU_DEVMON_RECOMPILE_WINDOW_S": "300",
           "RAY_TPU_DEVMON_HBM_INTERVAL_S": "0.5",
           "RAY_TPU_METRICS_EXPORT_INTERVAL_S": "0.4"}
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    from ray_tpu.cluster_utils import Cluster
    cfg = Config.from_env(metrics_port=0)
    c = Cluster(config=cfg)
    agent = c.add_node(num_cpus=8)
    import ray_tpu
    ray_tpu.init(address=c.address, config=cfg)
    yield c, agent
    from ray_tpu import serve
    serve.shutdown()
    ray_tpu.shutdown()
    c.shutdown()
    for k, v in old.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


def _get(addr, path):
    with urllib.request.urlopen(
            f"http://{addr[0]}:{addr[1]}{path}", timeout=15) as r:
        assert r.status == 200
        return r.read().decode()


@pytest.mark.slow
def test_forced_recompile_reaches_waterfall_devices_page_and_head_e2e(
        devmon_cluster, capsys):
    """The acceptance drive: a shape-bucket recompile forced DURING a
    traced request produces a dev:compile span in that request's
    waterfall; xla_recompiles_total crosses the storm threshold at the
    head; /devices renders live device rows; `ray-tpu devices` lists
    them."""
    import http.client

    import ray_tpu
    from ray_tpu import serve
    c, agent = devmon_cluster

    @serve.deployment(max_ongoing_requests=4)
    class Gen:
        def __init__(self):
            import jax

            from ray_tpu.llm import LLMEngine
            from ray_tpu.models import llama
            cfg = llama.tiny(vocab_size=64, dim=32, n_layers=2,
                             n_heads=2, n_kv_heads=2, ffn_dim=64,
                             dtype="float32", logits_dtype="float32",
                             attn_impl="reference")
            params = llama.init_params(jax.random.PRNGKey(0), cfg)
            self.eng = LLMEngine(cfg, params, max_slots=2, max_len=64,
                                 prefill_buckets=(8, 16),
                                 cache_dtype="float32")

        async def __call__(self, v=None):
            out = await self.eng.generate((v or {}).get("tokens",
                                                        [3, 5, 7]),
                                          max_new_tokens=6)
            return {"n": len(out["tokens"])}

    serve.run(Gen.bind(), name="app_dev", route_prefix="/gen")
    addr = serve.proxy_address()

    def post(tokens):
        conn = http.client.HTTPConnection(addr["host"], addr["port"],
                                          timeout=60)
        conn.request("POST", "/gen", body=json.dumps({"tokens": tokens}),
                     headers={"Content-Type": "application/json",
                              "X-Request-Deadline": "60"})
        r = conn.getresponse()
        out = {"status": r.status, "body": r.read(),
               "trace_id": r.getheader("X-Trace-Id")}
        conn.close()
        return out

    # request 1 warms bucket 8 and the decode variants
    r1 = post([3, 5, 7])
    assert r1["status"] == 200, r1
    # request 2's 12-token prompt forces the bucket-16 prefill compile
    # DURING this traced request
    r2 = post(list(range(1, 13)))
    assert r2["status"] == 200, r2
    tid = r2["trace_id"]
    assert tid and len(tid) == 32

    # the compile span joins request 2's waterfall (worker buffers
    # flush ~1 s; poll)
    deadline = time.monotonic() + 30
    comp = []
    while time.monotonic() < deadline:
        evs = ray_tpu.timeline(all_nodes=True, trace_id=tid)
        comp = [e for e in evs if e.get("cat") == "device"
                and e.get("name") == "compile"]
        if comp:
            break
        time.sleep(0.5)
    assert comp, "no dev compile span joined the traced request"
    assert all(e["trace"] == tid for e in comp)
    from ray_tpu.util.tracing import to_chrome
    recs = to_chrome(ray_tpu.timeline(all_nodes=True), trace_id=tid)
    lanes = {r["tid"] for r in recs if r.get("ph") == "X"}
    assert "dev:compile" in lanes, lanes

    # gauges reach the head: the replica worker's devmon snapshots and
    # compile counters ride the metrics push; recompiles crossed the
    # storm threshold (2) — bucket 16 was at least the second prefill
    # compile
    maddr = agent.metrics_addr
    deadline = time.monotonic() + 30
    ok = False
    while time.monotonic() < deadline:
        text = _get(maddr, "/metrics")
        rec = sum(float(ln.rsplit(" ", 1)[1])
                  for ln in text.splitlines()
                  if ln.startswith("xla_recompiles_total"))
        if rec >= 2 and "device_hbm_used_bytes" in text \
                and "llm_kv_cache_bytes" in text:
            ok = True
            break
        time.sleep(0.5)
    assert ok, "device gauges never reached the head"

    # /devices renders live rows (hbm snapshots from the worker loop)
    deadline = time.monotonic() + 30
    page = ""
    while time.monotonic() < deadline:
        page = _get(maddr, "/devices")
        if "cpu:0" in page and "XLA compiles" in page:
            break
        time.sleep(0.5)
    assert "cpu:0" in page and "XLA compiles" in page, page[:500]

    # the CLI surface over the same rows
    from ray_tpu import scripts
    assert scripts.main(["devices", "--address", c.address,
                         "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["summary"]["devices"], out["summary"]
    assert any(cc["compiles"] >= 1 for cc in out["summary"]["compiles"])
    serve.delete("app_dev")
