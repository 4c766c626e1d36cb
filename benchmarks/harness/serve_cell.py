"""One run of a serving cell. This process never imports JAX: the
replica's worker process holds the chip. It starts the runtime, deploys
``server.BenchLLMServer`` behind the HTTP proxy, checks the served
forwards against the reference, warms the cell's shapes, offers the
cell's load from one asyncio thread, and measures a window of it.
"""

from __future__ import annotations

import asyncio
import os
import shutil
import socket
import sys
import tempfile
import time
import traceback

from harness import loadgen, model as hmodel, result, spec

ROUTE = "bench"


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def run(cell: dict, seed: int, seconds: float, trace: bool,
        t_proc0: float) -> int:
    fam = spec.family(cell["family"])
    if not hasattr(fam, "serve_parity"):
        print(f"benchmark: family {cell['family']!r} has no serve_parity: "
              f"it cannot be served yet. No result.", file=sys.stderr)
        return 6
    # the runtime's workers inherit this environment
    os.environ.setdefault("RAY_TPU_METRICS_EXPORT_INTERVAL_S", "30")
    cache = hmodel.compile_cache()
    work = tempfile.mkdtemp(prefix="ray_tpu_bench_")
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.config import Config
    from ray_tpu.serve.api import deployment
    from ray_tpu.serve.llm import LLMConfig, build_llm_deployment
    from harness.server import BenchLLMServer

    m = hmodel.resolved(cell["model"])
    dep = m["deployment"]
    params = hmodel.traffic(cell)
    cfg = LLMConfig(
        model=fam.config(m), max_slots=dep["max_slots"],
        max_len=dep["max_len"], cache_dtype=dep["cache_dtype"],
        kv_block_size=dep["kv_block_size"], seed=seed % (2 ** 31),
        **({"prefill_buckets": tuple(dep["prefill_buckets"])}
           if "prefill_buckets" in dep else {}))
    # the program's builder decides every option of the deployment; it
    # has no hook for the class, so its deployment is made again around
    # the subclass that adds the harness's four methods
    built = build_llm_deployment(cfg, name=ROUTE)
    d = built.deployment
    app = deployment(
        BenchLLMServer, name=d.name, num_replicas=d.num_replicas,
        autoscaling_config=d.autoscaling_config,
        max_ongoing_requests=d.max_ongoing_requests,
        route_prefix=d.route_prefix, user_config=d.user_config,
        ray_actor_options=d.ray_actor_options, gang=d.gang,
    ).bind(*built.init_args, **built.init_kwargs)

    ok = False
    ray_tpu.init(num_cpus=4, config=Config.from_env(
        log_dir=os.path.join(work, "logs"),
        shm_fallback_dir=tempfile.gettempdir()))
    try:
        handle = serve.run(app, name="bench_app", http_port=_free_port(),
                           ready_timeout_s=1000)
        get = ray_tpu.get
        info = get(handle.bench_info.remote(), timeout=120)
        device = info["device"]
        result.require_tpu(device, cell["chips"])
        result.note(note="replica_ready", device=device, compile_cache=cache,
                    ready_s=time.monotonic() - t_proc0,
                    engine={k: info.get(k) for k in (
                        "kv_impl", "kv_interpret", "prefill_impl",
                        "pool_blocks", "block_size", "n_layers", "init_s")})
        parity = get(handle.bench_parity.remote(
            seed, dep["parity_prompt_len"], cell["family"]), timeout=900)
        tol = dep["parity_tolerance"]
        parity_ok = (parity["finite"]
                     and parity["prefill_rel_err"] <= tol
                     and parity["decode_rel_err"] <= tol)
        result.note(note="parity", tolerance=tol, ok=parity_ok, **parity)
        out = asyncio.run(_drive(cell, m, params, handle, seed, seconds,
                                 trace, t_proc0, work))
        info = get(handle.bench_info.remote(), timeout=120)
        impl_ok = hmodel.REHEARSAL or (
            info["kv_impl"] == "paged_flash"
            and info["kv_interpret"] is False
            and info["prefill_impl"] == "flash")
        ctx = out["ctx"]
        ctx.update(info=info, model=m, traffic=params, cell=cell)
        result.note(note="window", attempted=out["attempted"],
                    failed=out["failed"], invalid=out["invalid"],
                    first_errors=out["errors"][:3],
                    compiles_in_window=ctx["counters"]["window"]["compiles"],
                    compiled_in_window=ctx["counters"]["window"][
                        "compiled_names"],
                    impl_ok=impl_ok, setup_s=ctx["setup_s"],
                    offered={k: params.get(k) for k in (
                        "kind", "rate_per_s", "callers")})
        ok = True
        result.finish(cell, trace, ctx,
                      correct=parity_ok and impl_ok and out["invalid"] == 0,
                      attempted=out["attempted"], failed=out["failed"])
        return 0
    finally:
        if not ok:
            _tail_logs(os.path.join(work, "logs"))
        # whatever went wrong above is what the caller must see: a
        # failure of the shutdown is printed, not raised over it
        for stop in (serve.shutdown, ray_tpu.shutdown):
            try:
                stop()
            except Exception:   # noqa: BLE001 - the run is ending
                traceback.print_exc()
        shutil.rmtree(work, ignore_errors=True)


def _delta(c0: dict, c1: dict) -> dict:
    """Counters over an interval: differences, except what is a level
    (the pool's size) or a peak since the last reset."""
    levels = ("pool_blocks", "blocks_used_peak")
    out = {k: (c1[k] if k in levels else c1[k] - c0[k])
           for k in c1 if k != "compiled_names"}
    # the names of what was built between the two snapshots
    out["compiled_names"] = c1["compiled_names"][
        len(c1["compiled_names"]) - out["compiles"]:] \
        if out["compiles"] else []
    return out


async def _drive(cell, m, params, handle, seed, seconds, trace, t_proc0,
                 work) -> dict:
    import ray_tpu
    from ray_tpu import serve
    addr = serve.proxy_address()
    route = f"/{ROUTE}"
    vocab = m["vocab_size"]

    def call(method, *a, timeout=120):
        return asyncio.to_thread(
            lambda: ray_tpu.get(getattr(handle, method).remote(*a),
                                timeout=timeout))

    t_warm = time.monotonic()
    warm = await loadgen.warm(addr, route, params["warm_shapes"], seed,
                              vocab)
    result.note(note="warmed", shapes=len(warm),
                warm_s=time.monotonic() - t_warm,
                since_start_s=time.monotonic() - t_proc0)
    bad = [w.error or "short reply" for w in warm
           if w.error or len(w.t_tokens) != w.max_new]
    if bad:
        raise RuntimeError(f"warm-up requests failed: {bad[:3]}")
    steady = float(params["steady_s"])
    load = loadgen.Load(addr, route, params, seed, vocab,
                        horizon_s=steady + seconds + 5)
    load.start()
    await asyncio.sleep(steady)
    c0 = await call("bench_snapshot", True)
    t0 = time.monotonic()
    setup_s = t0 - t_proc0
    traced = None
    if trace:
        traced = asyncio.create_task(call(
            "bench_trace", os.path.join(work, "trace"),
            min(float(params.get("trace_s", 8.0)), seconds),
            timeout=seconds + 120))
    await asyncio.sleep(max(0.0, t0 + seconds - time.monotonic()))
    t1 = t0 + seconds
    c1 = await call("bench_snapshot")
    await load.stop()
    ctx = {"window": (t0, t1), "requests": load.requests,
           "late_s": load.late_s, "setup_s": setup_s,
           "counters": {"window": _delta(c0, c1)}, "trace": None}
    if traced is not None:
        tr = await traced
        if tr.get("xplane"):
            ctx["counters"]["trace"] = _delta(tr["c0"], tr["c1"])
            ctx["trace_edges"] = tuple(tr["edges"])
            ctx["trace"] = await call("bench_reduce", tr["xplane"],
                                      timeout=300)
        else:
            result.note(note="trace_failed", detail=tr)
    # information: tokens produced per second in 5-s slices from the
    # start of load, lead-in included (how steady the window was), and
    # the client's quantiles, whichever of them the cell reports
    seen = {"requests": load.requests}
    tok_s = spec.reader("client_tokens_per_s")
    edges = [load.t_start + 5.0 * i
             for i in range(int((t1 - load.t_start) // 5.0) + 1)]
    result.note(note="slices_5s", lead_in_s=t0 - load.t_start, tok_s=[
        round(tok_s({**seen, "window": w}), 1)
        for w in zip(edges, edges[1:])])
    result.note(note="client_quantiles_ms", **{
        f"{kind}_p{int(q * 100)}": spec.reader(f"client_{kind}_quantile")(
            {**seen, "window": (t0, t1)}, q)
        for kind in ("ttft", "tpot") for q in (0.5, 0.9, 0.95)})
    def in_flight(t):
        return sum(1 for r in load.requests
                   if r.t_send <= t and (r.t_end is None or r.t_end > t))
    result.note(note="backlog", in_flight_at_open=in_flight(t0),
                in_flight_at_close=in_flight(t1), sent=len(load.requests),
                caller_late_max_s=max(load.late_s, default=None))
    done = [r for r in load.requests
            if r.t_end is not None and t0 <= r.t_end < t1]
    failed = [r for r in done if r.error]
    invalid = [r for r in done if not r.error
               and (len(r.t_tokens) != r.max_new or not r.tokens_ok)]
    return {"ctx": ctx, "attempted": len(done),
            "failed": len(failed) + len(invalid), "invalid": len(invalid),
            "errors": [r.error for r in failed]}


def _tail_logs(log_dir: str, n: int = 40) -> None:
    if not os.path.isdir(log_dir):
        return
    for name in sorted(os.listdir(log_dir)):
        with open(os.path.join(log_dir, name), errors="replace") as f:
            tail = f.readlines()[-n:]
        if tail:
            print(f"--- {name}\n{''.join(tail)}", file=sys.stderr)
