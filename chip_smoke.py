#!/usr/bin/env python3
"""Proof that the system starts on the attached TPU.

Drives the two paths the system exists for through the entry points a
user calls, at Llama-2-7B published widths (depth cut to what one 16 GB
chip holds, weights random from --seed), and checks what comes out:

  train   make_train_step, bf16 params, the default optimizer, flash
          attention: a few steps on a fixed batch; the loss is finite
          and falls, and the compiled step contains the kernel.
  serve   ray_tpu.init -> serve.run(build_llm_deployment(...)) -> HTTP
          proxy -> router -> replica worker -> LLMEngine on the paged
          cache: unary and SSE requests, a prompt shorter than 128
          tokens, one longer than the largest prefill bucket, a
          repeated shared prefix. The replica reports its platform and
          the paths it resolved; no other worker touched a device.
  parity  at the served widths, flash prefill against the XLA
          reference and the paged-decode kernel against the gathered
          view: logits within bf16 tolerance.

  --chips 4 runs instead ONE phase, `sharded`, in one process that
  drives all four chips: the train step on fsdp x tensor and on
  tensor x context meshes against the same seed and batch on one
  device, and a tensor-parallel LLMEngine against the one-device one.

A chip belongs to one process at a time, so this parent never imports
JAX: each phase is a child process that takes the chip and gives it
back. The `serve` child stays off JAX too; its replica worker owns the
chip. Every line on stdout is one JSON object; the last is

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with the device as the child that held the chip saw it. Any failed
check, any device that is not a TPU, any child that exits non-zero
makes that `"ok": false` and the exit code 1. --tiny rehearses the
control flow on CPU at toy widths (Pallas in interpret mode); it cannot
pass, because the platform checks still fail there.
"""

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
LOG_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke_logs")
DEADLINE_S = 1150           # the whole run, compilation included
PHASE_TIMEOUT_S = {"train": 420, "serve": 600, "parity": 300,
                   "sharded": 1100}
# bf16 carries 8 bits of mantissa; a few layers of it separate two
# correct attention implementations by about this much of the logits'
# norm (the flash kernel also takes exp() in bf16, hence the wider one)
TOL_KERNEL_VS_GATHER = 0.02
TOL_FLASH_VS_REFERENCE = 0.05
TOL_LOSS = 0.02             # sharded vs one-device loss, relative


def emit(**row):
    print(json.dumps(row), flush=True)


# --- sizes -------------------------------------------------------------


def sizes(tiny: bool) -> dict:
    """Llama-2-7B published widths; what is cut is depth, and it is
    printed. --tiny swaps in toy widths for the CPU rehearsal."""
    if tiny:
        model = dict(vocab_size=512, dim=512, n_heads=4, n_kv_heads=4,
                     ffn_dim=512, max_seq_len=256)
        return dict(
            model="tiny", model_kw=model, train_layers=2,
            train_attn=dict(attn_impl="flash_interpret",
                            attn_block_q=128, attn_block_k=128),
            batch=2, seq=256, steps=8,
            serve_layers=2, slots=4, max_len=256, buckets=(16, 64),
            n_short=10, n_shared=100, n_long=150, max_new=9)
    return dict(
        model="llama2_7b", model_kw={},
        # bf16 params + two bf16 adam moments are 6 bytes a parameter
        # and the step holds the old and the new state: 4 of 32 layers
        # are what fits 16 GB at batch 4 x 4096 (14.0 GB by the
        # compiler's memory analysis; 5 layers need 16.4)
        train_layers=4,
        train_attn=dict(attn_impl="flash", attn_block_q=1024,
                        attn_block_k=1024),
        batch=4, seq=4096, steps=8,
        # 4 of 32 layers: 2.0 GB of weights leave room for a KV pool of
        # 8 slots x 4096 tokens (2.4 GB) three times over, which is
        # what the decode program holds while it runs
        serve_layers=4, slots=8, max_len=4096, buckets=(64, 512),
        n_short=40, n_shared=700, n_long=1200, max_new=9)


# --- what every child that holds the chip does first -------------------


def go_live(tiny: bool) -> dict:
    """Import JAX, point it at the shared compile cache, hook the
    compile listeners, and describe the device. Without a TPU the
    child stops here (unless rehearsing)."""
    from ray_tpu.util import devmon, jaxenv
    cache = jaxenv.setup_compile_cache()
    devmon.install()
    device = jaxenv.describe_device()
    if device["platform"] != "tpu" and not tiny:
        raise NoAccelerator(device)
    from ray_tpu._native import load_ringbuf
    emit(note="live", device=device, compile_cache=cache,
         native_ring=load_ringbuf() is not None)
    return device


class NoAccelerator(Exception):
    pass


def device_report(rows) -> dict:
    """Which processes touched a device and what compiling cost, from
    the devmon rows `ray-tpu devices` renders."""
    from ray_tpu.util.state import summarize_devices
    s = summarize_devices(rows)
    return {"pids": sorted({r["pid"] for r in rows
                            if r["kind"] in ("hbm", "compile")}),
            "labels": sorted({d["device"] for d in s["devices"]}),
            "compiles": sum(c["compiles"] for c in s["compiles"]),
            "cache_hits": sum(c["cache_hits"] for c in s["compiles"]),
            "compile_s": round(s["compile_total_s"], 2)}


def compile_report() -> dict:
    """This process's compiles so far."""
    from ray_tpu.util import events
    from ray_tpu.util.state import devices_from_events
    r = device_report(devices_from_events(events.dump(), limit=10**6))
    return {k: r[k] for k in ("compiles", "cache_hits", "compile_s")}


def rel_err(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


# --- train -------------------------------------------------------------


def run_train_steps(cfg, mesh, seed: int, batch: int, seq: int,
                    steps: int):
    """make_train_step on ``mesh``: init from ``seed``, ``steps`` steps
    on one fixed batch. Returns (losses, step seconds, compiled step,
    final state)."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.parallel import mesh as pmesh
    init_fn, step_fn = pmesh.make_train_step(cfg, mesh)
    with mesh:
        state = init_fn(jax.random.PRNGKey(seed))
        tokens = jax.random.randint(
            jax.random.PRNGKey(seed + 1), (batch, seq), 0,
            cfg.vocab_size, dtype=jnp.int32)
        data = {"tokens": tokens, "targets": tokens}
        # the jitted step, compiled once and kept: its text shows
        # whether the kernel is in it, and calling it is the step
        compiled = step_fn.lower(state, data).compile()
        losses, secs = [], []
        for _ in range(steps):
            t0 = time.perf_counter()
            state, metrics = compiled(state, data)
            losses.append(float(metrics["loss"]))   # waits for the step
            secs.append(time.perf_counter() - t0)
    return losses, secs, compiled, state


def phase_train(a) -> dict:
    import math
    import statistics

    import jax
    device = go_live(a.tiny)
    from ray_tpu.models import llama
    from ray_tpu.parallel import mesh as pmesh
    z = sizes(a.tiny)
    cfg = getattr(llama, z["model"])(
        n_layers=z["train_layers"], logits_dtype="bfloat16",
        **z["train_attn"], **z["model_kw"])
    mesh = pmesh.make_mesh(
        pmesh.MeshSpec(data=1, fsdp=1, tensor=1, context=1),
        devices=jax.devices()[:1])
    t0 = time.perf_counter()
    losses, secs, compiled, _ = run_train_steps(
        cfg, mesh, a.seed, z["batch"], z["seq"], z["steps"])
    mem = compiled.memory_analysis()
    step_s = statistics.median(secs[1:])
    emit(note="train", device=device,
         config=f"{z['model']} widths, {cfg.n_layers} of 32 layers, "
                f"batch {z['batch']} x seq {z['seq']}, bf16, "
                f"attn_impl={cfg.attn_impl}",
         params_m=round(cfg.num_params() / 1e6, 1),
         losses=[round(x, 4) for x in losses],
         step_ms=round(step_s * 1e3, 1),
         tokens_per_s=round(z["batch"] * z["seq"] / step_s, 1),
         program_gb=round((mem.argument_size_in_bytes
                           + mem.output_size_in_bytes
                           + mem.temp_size_in_bytes
                           - mem.alias_size_in_bytes) / 2**30, 2),
         wall_s=round(time.perf_counter() - t0, 1), **compile_report())
    checks = {
        "platform_tpu": device["platform"] == "tpu",
        "loss_finite": all(math.isfinite(x) for x in losses),
        "loss_fell": losses[-1] < losses[0],
        "flash_kernel_in_step": "tpu_custom_call" in compiled.as_text(),
    }
    return {"device": device, "checks": checks}


# --- serve -------------------------------------------------------------


def _post(addr, route, body, stream=False):
    """One request through the HTTP proxy. Unary: the JSON reply.
    SSE: {"tokens": [...], "ttft_s": first data event}."""
    import http.client
    t0 = time.monotonic()
    conn = http.client.HTTPConnection(addr["host"], addr["port"],
                                      timeout=600)
    headers = {"Content-Type": "application/json",
               "X-Request-Deadline": "600"}    # cold programs compile
    if stream:
        headers["Accept"] = "text/event-stream"
    try:
        conn.request("POST", route, body=json.dumps(body),
                     headers=headers)
        resp = conn.getresponse()
        if resp.status != 200:
            raise RuntimeError(
                f"HTTP {resp.status}: {resp.read()[:300]!r}")
        if not stream:
            return json.loads(resp.read())
        tokens, ttft, done = [], None, False
        for line in resp:               # one SSE line at a time
            line = line.decode().rstrip("\n")
            if line.startswith("event: done"):
                done = True
                break
            if line.startswith("event:"):
                raise RuntimeError(
                    f"stream ended with {line!r} {resp.read()[:300]!r}")
            if line.startswith("data: "):
                tokens.append(json.loads(line[6:])["token"])
                ttft = ttft or time.monotonic() - t0
        if not done:
            raise RuntimeError("stream closed without `event: done`")
        return {"tokens": tokens, "ttft_s": ttft}
    finally:
        conn.close()


def _cluster_device_report(want_pid: int, timeout_s: float = 30.0):
    """device_report over the whole cluster (worker buffers flush about
    once a second, so poll until the replica's rows are there)."""
    from ray_tpu.util.state import list_devices
    deadline = time.monotonic() + timeout_s
    while True:
        rows = list_devices(limit=10**6)
        if any(r["kind"] == "hbm" and r["pid"] == want_pid
               for r in rows) or time.monotonic() > deadline:
            return device_report(rows)
        time.sleep(0.5)


def phase_serve(a) -> dict:
    import random

    # the replica's devmon rows and metrics reach the head quickly
    os.environ["RAY_TPU_DEVMON_HBM_INTERVAL_S"] = "0.5"
    os.environ["RAY_TPU_METRICS_EXPORT_INTERVAL_S"] = "0.5"
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.config import Config
    from ray_tpu.serve.llm import LLMConfig, build_llm_deployment
    z = sizes(a.tiny)
    vocab = z["model_kw"].get("vocab_size", 32000)
    rng = random.Random(a.seed)

    def prompt(n):
        return [rng.randrange(1, vocab) for _ in range(n)]

    ray_tpu.init(num_cpus=4, config=Config.from_env(log_dir=LOG_DIR))
    try:
        t0 = time.monotonic()
        handle = serve.run(
            build_llm_deployment(LLMConfig(
                model=z["model"],
                model_overrides=dict(n_layers=z["serve_layers"],
                                     **z["model_kw"]),
                max_slots=z["slots"], max_len=z["max_len"],
                prefill_buckets=z["buckets"], seed=a.seed),
                name="smoke"),
            name="smoke_app", ready_timeout_s=420)
        ready_s = time.monotonic() - t0
        st0 = ray_tpu.get(handle.stats.remote(), timeout=60)
        device = st0["device"]
        if device["platform"] != "tpu" and not a.tiny:
            raise NoAccelerator(device)
        addr = serve.proxy_address()
        new = z["max_new"]

        def ask(tokens, stream=False):
            return _post(addr, "/smoke",
                         {"tokens": tokens, "max_new_tokens": new},
                         stream=stream)

        t0 = time.monotonic()
        shared = prompt(z["n_shared"])
        replies = {
            "unary_short": ask(prompt(z["n_short"])),
            "sse_short": ask(prompt(z["n_short"]), stream=True),
            "unary_shared_cold": ask(shared),
            "unary_shared_again": ask(shared),
            "sse_long": ask(prompt(z["n_long"]), stream=True),
        }
        pair = [None, None]

        def one(i):
            pair[i] = ask(prompt(z["n_short"]))
        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(600)
        replies["pair_a"], replies["pair_b"] = pair
        requests_s = time.monotonic() - t0

        st = ray_tpu.get(handle.stats.remote(), timeout=60)
        rows = _cluster_device_report(st["pid"])
        emit(note="serve", device=device,
             config=f"{z['model']} widths, {z['serve_layers']} of 32 "
                    f"layers, {z['slots']} slots x {z['max_len']} "
                    f"tokens, buckets {list(z['buckets'])}",
             ready_s=round(ready_s, 1), requests_s=round(requests_s, 1),
             ttft_s={k: round(r["ttft_s"], 3)
                     for k, r in replies.items()
                     if r and "ttft_s" in r},
             prefix_hit_tokens=replies["unary_shared_again"].get(
                 "prefix_hit_tokens"),
             repeat_same_first_token=(
                 replies["unary_shared_again"]["tokens"][:1]
                 == replies["unary_shared_cold"]["tokens"][:1]),
             engine={k: st.get(k) for k in (
                 "kv_impl", "kv_interpret", "prefill_impl",
                 "pool_blocks", "block_size", "requests",
                 "tokens_generated", "prefix_hit_tokens")},
             device_rows=rows)
        checks = {
            "platform_tpu": device["platform"] == "tpu",
            "all_answered": all(
                r is not None and len(r["tokens"]) == new
                and all(0 <= t < vocab for t in r["tokens"])
                for r in replies.values()),
            "tokens_counted": st["tokens_generated"]
            == new * len(replies),
            "cold_prefix_missed":
                replies["unary_shared_cold"]["prefix_hit_tokens"] == 0,
            "repeat_prefix_hit":
                replies["unary_shared_again"]["prefix_hit_tokens"] > 0,
            "decode_kernel": st["kv_impl"] == "paged_flash",
            "decode_not_interpreted": st["kv_interpret"] is False,
            "prefill_flash": st["prefill_impl"] == "flash",
            "replica_on_tpu": bool(rows["labels"]) and all(
                lb.startswith("tpu:") for lb in rows["labels"]),
            "only_replica_touched_a_device":
                rows["pids"] == [st["pid"]],
            "driver_stayed_off_jax": "jax" not in sys.modules,
        }
        return {"device": device, "checks": checks}
    except BaseException:
        _tail_worker_logs()
        raise
    finally:
        try:
            serve.shutdown()
        finally:
            ray_tpu.shutdown()


def _tail_worker_logs(n: int = 40) -> None:
    """A failed serve phase says why on stderr: the worker logs' ends."""
    if not os.path.isdir(LOG_DIR):
        return
    for name in sorted(os.listdir(LOG_DIR)):
        with open(os.path.join(LOG_DIR, name), errors="replace") as f:
            tail = f.readlines()[-n:]
        if tail:
            print(f"--- {name}\n{''.join(tail)}", file=sys.stderr)


# --- parity ------------------------------------------------------------


def prefill_into_pool(params, cfg, tokens, bucket, block, copies,
                      pool_sharding=None):
    """Prefill ``tokens`` once and scatter its KV into ``copies``
    disjoint runs of pool blocks. Returns (last-token logits, pool,
    tables (copies, bucket // block))."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.llm import kvcache as kc
    from ray_tpu.llm import model as lm
    logits, kv = lm.prefill(
        params, jnp.asarray(lm.pad_prompt(tokens, bucket)),
        jnp.int32(len(tokens)), cfg, bucket)
    nb = bucket // block
    pool = kc.init_pool(cfg, 1 + copies * nb, block, jnp.bfloat16)
    if pool_sharding is not None:
        pool = jax.device_put(pool, pool_sharding)
    tables = 1 + np.arange(copies * nb, dtype=np.int32).reshape(
        copies, nb)
    for row in tables:
        pool = kc.scatter_bucket(pool, kv, jnp.asarray(row), nb)
    return logits, pool, jnp.asarray(tables)


def phase_parity(a) -> dict:
    import dataclasses
    import random

    import jax
    import jax.numpy as jnp
    import numpy as np
    device = go_live(a.tiny)
    from ray_tpu.llm import kvcache as kc
    from ray_tpu.models import llama
    z = sizes(a.tiny)
    cfg = getattr(llama, z["model"])(n_layers=z["serve_layers"],
                                     **z["model_kw"])
    params = llama.init_params(jax.random.PRNGKey(a.seed), cfg)
    bucket = z["buckets"][-1]
    rng = random.Random(a.seed)
    n = bucket - bucket // 8
    tokens = [rng.randrange(1, cfg.vocab_size) for _ in range(n)]
    flash = "flash" if not a.tiny else "flash_interpret"

    # prefill: the kernel against the XLA reference
    ref_cfg = dataclasses.replace(cfg, attn_impl="reference")
    ref_logits, pool, tables = prefill_into_pool(
        params, ref_cfg, tokens, bucket, 16, copies=4)
    got_logits, _, _ = prefill_into_pool(
        params, dataclasses.replace(cfg, attn_impl=flash), tokens,
        bucket, 16, copies=1)
    prefill_err = rel_err(got_logits, ref_logits)

    # decode: four slots at different depths of the same prompt, each
    # on its own blocks — a length of 1, one that ends inside a block,
    # one on a block edge, the whole prompt
    lengths = jnp.asarray([1, n // 2 + 3, (n // 32) * 16, n], jnp.int32)
    last = jnp.asarray([tokens[int(i) - 1] for i in lengths], jnp.int32)
    want = kc.paged_decode_logits(params, pool, tables, lengths - 1,
                                  last, cfg, impl="gather")
    got = kc.paged_decode_logits(params, pool, tables, lengths - 1,
                                 last, cfg, impl="paged_flash",
                                 interpret=a.tiny)
    decode_err = rel_err(got, want)
    emit(note="parity", device=device,
         prefill_flash_vs_reference=round(prefill_err, 5),
         decode_kernel_vs_gather=round(decode_err, 5),
         decode_max_abs=round(float(jnp.max(jnp.abs(got - want))), 4),
         decode_argmax_agree=int(np.sum(
             np.argmax(got, -1) == np.argmax(want, -1))),
         logits_abs_max=round(float(jnp.max(jnp.abs(want))), 3),
         **compile_report())
    checks = {
        "platform_tpu": device["platform"] == "tpu",
        "logits_finite": bool(jnp.all(jnp.isfinite(got))
                              & jnp.all(jnp.isfinite(got_logits))),
        "prefill_flash_matches_reference":
            prefill_err <= TOL_FLASH_VS_REFERENCE,
        "decode_kernel_matches_gather":
            decode_err <= TOL_KERNEL_VS_GATHER,
    }
    return {"device": device, "checks": checks}


# --- sharded (four chips, one process) ---------------------------------


def _held_mb(tree, devices) -> dict:
    """MB of ``tree`` that each of ``devices`` holds."""
    import jax
    held = {d.id: 0 for d in devices}
    for leaf in jax.tree.leaves(tree):
        for s in leaf.addressable_shards:
            held[s.device.id] += s.data.nbytes
    return {k: round(v / 2**20, 1) for k, v in held.items()}


def phase_sharded(a) -> dict:
    import asyncio
    import random

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    device = go_live(a.tiny)
    from ray_tpu.llm import kvcache as kc
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.models import llama
    from ray_tpu.parallel import mesh as pmesh
    z = sizes(a.tiny)
    devs = jax.devices()
    if len(devs) < 4:
        raise RuntimeError(f"--chips 4 needs four devices, jax has "
                           f"{len(devs)}")
    devs = devs[:4]
    checks = {"platform_tpu": device["platform"] == "tpu"}
    steps = 6

    # (a) the train step: one device, then two four-device meshes
    def train(spec, attn, devices):
        cfg = getattr(llama, z["model"])(
            n_layers=z["train_layers"], logits_dtype="bfloat16",
            **{**z["train_attn"], **attn}, **z["model_kw"])
        mesh = pmesh.make_mesh(pmesh.MeshSpec(**spec), devices=devices)
        t0 = time.perf_counter()
        losses, secs, compiled, state = run_train_steps(
            cfg, mesh, a.seed, z["batch"], z["seq"], steps)
        row = dict(mesh=spec, attn_impl=cfg.attn_impl,
                   losses=[round(x, 4) for x in losses],
                   step_ms=round(min(secs[1:]) * 1e3, 1),
                   kernel_in_step="tpu_custom_call"
                   in compiled.as_text(),
                   wall_s=round(time.perf_counter() - t0, 1),
                   state_mb=round(sum(
                       x.nbytes for x in jax.tree.leaves(state))
                       / 2**20, 1),
                   held_mb=_held_mb(state, devices))
        return losses, row

    one = dict(data=1, fsdp=1, tensor=1, context=1)
    base, row = train(one, {}, devs[:1])
    emit(note="sharded.train.one_device", device=device, **row)
    for name, spec, attn in (
            ("fsdp_x_tensor", dict(one, fsdp=2, tensor=2), {}),
            ("tensor_x_context", dict(one, tensor=2, context=2),
             {"attn_impl": "ring"})):
        losses, row = train(spec, attn, devs)
        err = max(abs(x - y) / abs(y) for x, y in zip(losses, base))
        emit(note=f"sharded.train.{name}", device=device,
             max_rel_loss_err=round(err, 5), **row)
        checks[f"{name}_loss_matches_one_device"] = err <= TOL_LOSS
        checks[f"{name}_loss_fell"] = losses[-1] < losses[0]
        # fsdp x tensor cuts the state four ways, tensor x context
        # two: no device holds nothing, none holds (nearly) all of it
        checks[f"{name}_every_device_holds_a_shard"] = (
            0 < min(row["held_mb"].values())
            and max(row["held_mb"].values()) < 0.6 * row["state_mb"])
        if row["attn_impl"] == "flash":
            # pallas_call under shard_map (models/llama.py _attend)
            checks[f"{name}_runs_the_kernel"] = row["kernel_in_step"]

    # (b) the engine: tensor-parallel over four chips vs one chip
    cfg = getattr(llama, z["model"])(n_layers=z["serve_layers"],
                                     **z["model_kw"])
    params = llama.init_params(jax.random.PRNGKey(a.seed), cfg)
    tp_mesh = Mesh(np.asarray(devs), ("tensor",))
    kw = dict(max_slots=4, max_len=z["max_len"] // 2,
              prefill_buckets=z["buckets"], seed=a.seed)
    rng = random.Random(a.seed)
    prompts = [[rng.randrange(1, cfg.vocab_size) for _ in range(n)]
               for n in (z["n_short"], z["buckets"][-1] - 7)]

    async def generate(engine):
        outs = await asyncio.gather(*[
            engine.generate(p, max_new_tokens=z["max_new"])
            for p in prompts])
        stats = engine.stats
        await engine.stop()
        return [o["tokens"] for o in outs], stats

    engines = {"one_chip": LLMEngine(cfg, params, **kw),
               "tp4": LLMEngine(cfg, params, mesh=tp_mesh, **kw)}
    logits, toks, impls = {}, {}, {}
    for name, eng in engines.items():
        toks[name], st = asyncio.run(generate(eng))
        impls[name] = {k: st[k] for k in ("kv_impl", "kv_interpret",
                                          "prefill_impl")}
        # the first step's logits, with the engine's own weights,
        # config, mesh and resolved paths
        heads_over_mesh = None if eng.mesh is None else NamedSharding(
            eng.mesh, P(None, None, eng.tensor_axis, None, None))
        first, pool, tables = prefill_into_pool(
            eng.params, eng.cfg, prompts[1], z["buckets"][-1], 16,
            copies=1, pool_sharding=heads_over_mesh)
        n = len(prompts[1])
        step = kc.paged_decode_logits(
            eng.params, pool, tables, jnp.asarray([n], jnp.int32),
            jnp.argmax(first)[None].astype(jnp.int32), eng.cfg,
            impl=st["kv_impl"], interpret=st["kv_interpret"],
            mesh=eng.mesh, axis=eng.tensor_axis)
        logits[name] = (np.asarray(first), np.asarray(step))
        impls[name]["weights_mb"] = _held_mb(eng.params, devs)
    prefill_err = rel_err(logits["tp4"][0], logits["one_chip"][0])
    decode_err = rel_err(logits["tp4"][1], logits["one_chip"][1])
    emit(note="sharded.engine", device=device, impls=impls,
         prefill_logits_rel_err=round(prefill_err, 5),
         decode_logits_rel_err=round(decode_err, 5),
         same_tokens=[x == y for x, y in
                      zip(toks["tp4"], toks["one_chip"])],
         **compile_report())
    checks.update({
        "engine_all_answered": all(
            len(t) == z["max_new"] for ts in toks.values() for t in ts),
        "tp4_prefill_logits_match_one_chip":
            prefill_err <= TOL_FLASH_VS_REFERENCE,
        "tp4_decode_logits_match_one_chip":
            decode_err <= TOL_FLASH_VS_REFERENCE,
        "tp4_weights_on_every_device":
            min(impls["tp4"]["weights_mb"].values()) > 0,
    })
    return {"device": device, "checks": checks}


# --- parent ------------------------------------------------------------

PHASES = {"train": phase_train, "serve": phase_serve,
          "parity": phase_parity, "sharded": phase_sharded}


def child_main(a) -> int:
    """Run one phase; the last stdout line is its result."""
    try:
        out = PHASES[a.phase](a)
    except NoAccelerator as e:
        emit(phase=a.phase, ok=False, device=e.args[0],
             error="JAX found no TPU")
        return 1
    ok = all(out["checks"].values())
    emit(phase=a.phase, ok=ok, device=out["device"],
         checks=out["checks"])
    return 0 if ok else 1


def run_child(phase: str, a, timeout_s: float) -> dict:
    """One phase in its own process (and session, so that whatever it
    started dies with it). Its stdout passes through; its last line is
    its result."""
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
           "--seed", str(a.seed)] + (["--tiny"] if a.tiny else [])
    t0 = time.monotonic()
    proc = subprocess.Popen(cmd, cwd=ROOT, stdout=subprocess.PIPE,
                            text=True, start_new_session=True)
    timer = threading.Timer(timeout_s, os.killpg,
                            (proc.pid, signal.SIGKILL))
    timer.start()
    try:
        last = ""
        for line in proc.stdout:
            line = line.rstrip("\n")
            if line:
                print(line, flush=True)
                last = line
        rc = proc.wait()
    finally:
        timer.cancel()
        try:
            os.killpg(proc.pid, signal.SIGKILL)     # stragglers
        except ProcessLookupError:
            pass
    try:
        result = json.loads(last)
    except ValueError:
        result = {}
    if result.get("phase") != phase:
        result = {"phase": phase, "ok": False, "device": None}
    if rc != 0:
        result["ok"] = False
    result["rc"] = rc
    result["wall_s"] = round(time.monotonic() - t0, 1)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded phase, on four chips")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal at toy widths; cannot pass")
    ap.add_argument("--phase", choices=sorted(PHASES),
                    help=argparse.SUPPRESS)     # the child's entry
    a = ap.parse_args(argv)
    if a.phase:
        return child_main(a)

    phases = ("sharded",) if a.chips == 4 else ("train", "serve",
                                                "parity")
    t_end = time.monotonic() + DEADLINE_S
    results = []
    for phase in phases:
        left = t_end - time.monotonic()
        r = run_child(phase, a, min(PHASE_TIMEOUT_S[phase], left))
        results.append(r)
        emit(note="phase_done", phase=phase, ok=r["ok"], rc=r["rc"],
             wall_s=r["wall_s"])
        dev = r.get("device")
        if not a.tiny and dev and dev["platform"] != "tpu":
            break       # no accelerator: nothing further can pass
    devices = [r.get("device") for r in results]
    ok = (len(results) == len(phases)
          and all(r["ok"] for r in results)
          and all(d and d["platform"] == "tpu" and d == devices[0]
                  and d["count"] >= a.chips for d in devices))
    print(json.dumps({"ok": ok, "device": devices[0]}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
