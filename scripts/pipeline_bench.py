"""Pipeline-parallelism bench: 1F1B over the real stage loop, 2-stage
vs single-stage at equal global batch, bubble fraction vs the analytic
(S-1)/(M+S-1) bound, and a ZeRO-composed row.

Each stage is a REAL process running the real pinned loop
(ray_tpu/dag/runtime.py pipe_exec_loop — the same code a cluster dag
actor executes) over the real shm channels, driven by the compiled
1F1B schedule (train/pipeline.py). Run:

    python scripts/pipeline_bench.py [--quick] [--trace <chrome.json>]

Prints progress to stderr and ONE JSON line to stdout; also writes
PIPELINE_BENCH.json.

CPU ONLY (run it with JAX_PLATFORMS=cpu): the stage processes are
forked from a parent that has already imported JAX, and each of them
jits on its own. A chip belongs to one process at a time, so on a TPU
host the stages would fight the parent and each other for it.

Two stage-compute models, because this container has ONE host core:

  **device-time stages** (the headline): stage compute blocks the host
  thread with the CPU FREE — exactly what an accelerator-bound stage
  looks like to its host process (the host sleeps in
  block_until_ready while the chip works). Two such stages genuinely
  overlap on one core, so the schedule's fill/drain bubble and the
  recv-under-compute overlap are measurable against the analytic
  bound. This is the regime MPMD pipeline parallelism targets: stages
  on separate accelerators/hosts.

  **host-compute stages** (the honesty row): real jitted matmul
  stages burn the ONE host core, so two stage processes timeshare and
  the 2-stage step cannot beat 1-stage wall-clock here — reported
  as-is (ratio ~1x, bubble ~0.5) to anchor what this container can
  and cannot demonstrate; on a multi-host deployment this row turns
  into the device-time row.

The ZeRO row composes the pipeline with train/zero.py: 2 stages x 2
data-parallel replica chains, each stage pair syncing through a
per-stage ShardedOptimizer ring at step end.
"""

from __future__ import annotations

import argparse
import json
import multiprocessing as mp
import os
import sys
import time

import numpy as np

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

KB = 1 << 10


class SimStage:
    """Device-time stage: pipe-compatible (duck-typed against
    pipe_exec_loop), compute = host blocked with the CPU free, payload
    = a fixed-size activation frame."""

    def __init__(self, t_f: float, t_b: float, is_last: bool,
                 payload_kb: int = 64):
        self.t_f, self.t_b = t_f, t_b
        self.is_last = is_last
        self._act = np.zeros(payload_kb * KB // 4, np.float32)

    def pipe_forward(self, mb, payload):
        time.sleep(self.t_f)
        return None if self.is_last else self._act

    def pipe_backward(self, mb, grad):
        time.sleep(self.t_b)
        return self._act

    def pipe_step(self):
        return {"loss": 0.0} if self.is_last else {}


def _matmul_stages(depth_per_stage: int, d: int, stages: int):
    """Real jitted matmul stage fns (host-compute rows + ZeRO row):
    ``stages`` slices of a tanh-MLP, last one closing with an MSE."""
    import jax.numpy as jnp
    rng = np.random.default_rng(0)

    def make(first: bool, last: bool):
        Ws = [jnp.asarray(rng.standard_normal((d, d))
                          .astype(np.float32) / d)
              for _ in range(depth_per_stage)]

        def fn(params, payload):
            x, y = payload
            h = x
            for W in params:
                h = jnp.tanh(h @ W)
            if last:
                return jnp.mean((h[:, :1] - y) ** 2)
            return (h, y)
        return fn, Ws
    return [make(k == 0, k == stages - 1) for k in range(stages)]


def _sim_proc(spec, t_f, t_b, is_last, payload_kb, out_q):
    from ray_tpu.dag.runtime import pipe_exec_loop
    from ray_tpu.util import events
    stage = SimStage(t_f, t_b, is_last, payload_kb)
    res = pipe_exec_loop(stage, spec)
    res["events"] = [{**e, "node": f"s{spec['stage']}"}
                     for e in events.dump()
                     if e.get("cat") == "pipeline"]
    out_q.put(res)


def _real_proc(spec, stage_idx, depth, d, nstages, lr, out_q):
    from ray_tpu.dag.runtime import pipe_exec_loop
    from ray_tpu.train.pipeline import PipelineStageActor
    from ray_tpu.util import events
    import optax
    fn, Ws = _matmul_stages(depth, d, nstages)[stage_idx]
    actor = PipelineStageActor(fn, Ws, optimizer=optax.adam(lr),
                              is_last=stage_idx == nstages - 1)
    res = pipe_exec_loop(actor, spec)
    res["events"] = [{**e, "node": f"s{spec['stage']}"}
                     for e in events.dump()
                     if e.get("cat") == "pipeline"]
    out_q.put(res)


def _drive(specs, inputs, res_chans, channels, payloads, steps,
           proc_factory, timeout=120.0):
    """Spawn one process per (stage, chain) spec, feed ``steps`` steps
    of microbatches, and collect per-step driver wall + per-stage
    reports + final loop stats."""
    from ray_tpu.dag.channel import DATA, STOP
    from ray_tpu.runtime.serialization import loads_oob, serialize
    ctx = mp.get_context("fork")
    out_q = ctx.Queue()
    procs = []
    for k, row in enumerate(specs):
        for j, spec in enumerate(row):
            procs.append(ctx.Process(target=proc_factory(k, j),
                                     args=(spec, out_q), daemon=True))
    for p in procs:
        p.start()
    D = len(specs[0])
    step_walls = []
    reports_last = None
    for s in range(steps):
        t0 = time.perf_counter()
        for j in range(D):
            for mb in payloads[j::D]:
                inputs[j].write(serialize(mb), DATA, timeout=timeout)
        reports = []
        for k in range(len(specs)):
            for j in range(D):
                kind, payload = res_chans[k][j].read_bytes(timeout)
                body = loads_oob(payload)
                if kind != DATA:
                    raise body if isinstance(body, BaseException) \
                        else RuntimeError(str(body))
                reports.append({"stage": k, "chain": j, **body})
        step_walls.append(time.perf_counter() - t0)
        reports_last = reports
    for j in range(D):
        inputs[j].write(b"", STOP, timeout=10)
    loops = [out_q.get(timeout=60) for _ in procs]
    for p in procs:
        p.join(timeout=30)
    for ch in channels:
        ch.close()
        try:
            ch.unlink()
        except Exception:
            pass
    return step_walls, reports_last, loops


def bench_sim(S, M, t_op, steps, payload_kb=64):
    """One device-time config: S stages, M microbatches, t_op seconds
    per F/B op per stage."""
    from ray_tpu.train import pipeline as pl
    specs, inputs, res_chans, channels = pl.wire_local(
        S, M, schedule="1f1b", timeout_s=120.0)

    def factory(k, j):
        def run(spec, out_q):
            _sim_proc(spec, t_op, t_op, k == S - 1, payload_kb, out_q)
        return run

    payloads = [np.zeros(payload_kb * KB // 4, np.float32)
                for _ in range(M)]
    walls, reports, loops = _drive(specs, inputs, res_chans, channels,
                                   payloads, steps, factory)
    walls = walls[1:] or walls          # step 0 warms attaches
    fracs = [r["stats"]["bubble_s"] / r["stats"]["step_s"]
             for r in reports]
    overlap = sum(lp["timing"]["overlapped_recv_s"] for lp in loops)
    recv = sum(lp["timing"]["recv_s"] for lp in loops)
    return {
        "kind": "sim", "stages": S, "microbatches": M,
        "t_op_s": t_op, "steps": len(walls),
        "step_s": float(np.median(walls)),
        "bubble_fraction": float(max(fracs)),
        "analytic_bound": pl.bubble_fraction(S, M),
        "overlapped_recv_s": float(overlap),
        "recv_s": float(recv),
        "events": [e for lp in loops for e in lp.get("events", ())],
    }


def bench_real(S, M, steps, depth=2, d=192, replicas=1, lr=1e-2,
               batch=64):
    from ray_tpu.train import pipeline as pl
    specs, inputs, res_chans, channels = pl.wire_local(
        S, M, schedule="1f1b", replicas=replicas, timeout_s=300.0)

    def factory(k, j):
        def run(spec, out_q):
            _real_proc(spec, k, depth, d, S, lr, out_q)
        return run

    rng = np.random.default_rng(1)
    payloads = [(rng.standard_normal((batch, d)).astype(np.float32),
                 rng.standard_normal((batch, 1)).astype(np.float32))
                for _ in range(M)]
    walls, reports, loops = _drive(specs, inputs, res_chans, channels,
                                   payloads, steps, factory)
    walls = walls[1:] or walls          # step 0 pays jit compiles
    fracs = [r["stats"]["bubble_s"] / r["stats"]["step_s"]
             for r in reports]
    return {
        "kind": "real", "stages": S, "microbatches": M,
        "replicas": replicas, "depth_per_stage": depth, "width": d,
        "steps": len(walls), "step_s": float(np.median(walls)),
        "bubble_fraction": float(max(fracs)),
        "analytic_bound": pl.bubble_fraction(S, M),
        "loss": reports[-1]["result"].get("loss")
        if reports[-1].get("result") else None,
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--trace", default=None,
                    help="write a chrome trace of the 2-stage sim run")
    args = ap.parse_args()
    steps = 3 if args.quick else 6
    t_op = 0.01 if args.quick else 0.02

    results = []
    print("[pipeline_bench] device-time rows...", file=sys.stderr)
    # headline grid: 1-stage baseline carries the WHOLE model's device
    # time per microbatch (S*t_op per direction) — equal global batch,
    # equal total device work
    base = bench_sim(1, 8, 2 * t_op, steps)
    results.append(base)
    two_m4 = bench_sim(2, 4, t_op, steps)
    results.append(two_m4)
    two_m8 = bench_sim(2, 8, t_op, steps)
    results.append(two_m8)
    trace_events = two_m8.pop("events")
    two_m4.pop("events")
    base.pop("events")

    print("[pipeline_bench] host-compute row...", file=sys.stderr)
    real_base = bench_real(1, 8, steps, depth=4)
    real_two = bench_real(2, 8, steps, depth=2)
    results += [real_base, real_two]

    print("[pipeline_bench] zero-composed row...", file=sys.stderr)
    zero_row = bench_real(2, 8, steps, depth=2, replicas=2)
    zero_row["kind"] = "real+zero1"
    results.append(zero_row)

    if args.trace:
        from ray_tpu.util import tracing
        tracing.to_chrome(trace_events, path=args.trace)
        print(f"[pipeline_bench] chrome trace -> {args.trace}",
              file=sys.stderr)

    out = {
        "bench": "pipeline",
        "host_cores": os.cpu_count(),
        "schedule": "1f1b",
        "results": results,
        # headline: device-time 2-stage vs 1-stage at equal global batch
        "sim_two_stage_step_ratio_m8":
            two_m8["step_s"] / base["step_s"],
        "sim_bubble_fraction_m4": two_m4["bubble_fraction"],
        "sim_bubble_fraction_m8": two_m8["bubble_fraction"],
        "analytic_bound_m4": two_m4["analytic_bound"],
        "analytic_bound_m8": two_m8["analytic_bound"],
        "bubble_vs_analytic_m4":
            two_m4["bubble_fraction"] / two_m4["analytic_bound"],
        "bubble_vs_analytic_m8":
            two_m8["bubble_fraction"] / two_m8["analytic_bound"],
        "overlapped_recv_s_per_step_m8":
            two_m8["overlapped_recv_s"] / max(1, two_m8["steps"] + 1),
        "host_bound_two_stage_step_ratio_m8":
            real_two["step_s"] / real_base["step_s"],
        "zero_composed_step_s": zero_row["step_s"],
    }
    line = json.dumps(out)
    print(line)
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "PIPELINE_BENCH.json")
    with open(path, "w") as f:
        f.write(line + "\n")
    print(f"[pipeline_bench] wrote {path}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
