#!/usr/bin/env python3
"""The engine's host spans against the device plane of ONE profiler
trace of serving (an ``.xplane.pb``), and what a span costs.

    python scripts/engine_clock_check.py <trace.xplane.pb> [--out f.json]
    python scripts/engine_clock_check.py --phase-cost [--out f.json]

With a trace: ``engine.prefill.behind`` is an admission's wait for the
decode block in flight, and its exit is what the engine takes for the
block's end on the device unless a look during the dispatch found it
ended already (``llm/engine.py _block_ended``). For each such span this
compares its end with the end of the last decode program that started
before it on the device plane ("XLA Modules": a module is a decode
program if the paged kernel ran inside it, as
``benchmarks/harness/xplane.py`` decides). A span that began while the
program still ran WAITED for it, and the difference is the host's
wake-up on one shared clock; one that began after the program's end (a
second admission of a turn, or a block that ended under
``prefill.dispatch``: a chunk's launch sits it out) found it done, and
is reported apart: that difference is how late the stamp would be
without the looks. Then the device's side of the stall: from that
program's end to the next decode program's start, summed
(``device_stall_after_admission_s``: what ``llm_decode_gap_s`` should
sum to over the same admissions), and every idle gap in it named after
the ``engine.*`` span covering most of it (the trace reduction's rule),
summed by name: what the host was in while the slots stalled.

``--phase-cost`` times ``tracing.phase`` (one ``TraceAnnotation`` and
one histogram observation) with no profiler session and inside one: a
span's cost in both states, in the process that runs it.
"""

import argparse
import bisect
import json
import os
import statistics
import sys
import time

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

BEHIND = "engine.prefill.behind"


def _decode_programs(plane) -> tuple:
    """((start, end) of every decode program, merged busy intervals) of
    one device plane, in ns."""
    from harness import kernels, xplane
    lines = {ln.name: ln for ln in plane.lines}
    parsed, spans, busy = {}, [], []
    for ev in lines["XLA Ops"].events:
        op = parsed.get(ev.name)
        if op is None:
            op = parsed[ev.name] = kernels.parse_op(ev.name)
            op["kernel"] = kernels.classify(op)
        busy.append((ev.start_ns, ev.start_ns + ev.duration_ns))
        if op["kernel"] == "paged_decode":
            spans.append(ev.start_ns)
    spans.sort()
    decode = []
    for ev in lines["XLA Modules"].events:
        a, b = ev.start_ns, ev.start_ns + ev.duration_ns
        if bisect.bisect_right(spans, b) > bisect.bisect_left(spans, a):
            decode.append((a, b))
    return sorted(decode), xplane._union(busy)


def _quantiles(values: list) -> dict:
    if not values:
        return {"n": 0}
    v = sorted(values)
    return {"n": len(v), "median": statistics.median(v), "min": v[0],
            "max": v[-1], "worst_abs": max(abs(v[0]), abs(v[-1]))}


def check(path: str) -> dict:
    from harness import xplane
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    dev = next(p for p in pd.planes if p.name.startswith("/device:TPU:"))
    decode, busy = _decode_programs(dev)
    host = sorted(xplane._host_spans(pd))
    engine = [s for s in host if s[2].startswith("engine.")]
    starts = [a for a, _ in decode]
    gaps = [(b1, a2) for (_, b1), (a2, _) in zip(busy, busy[1:])]
    gap_starts = [a for a, _ in gaps]

    def named(a, b):
        best, cover = "unattributed", 0
        for s, e, name in engine:
            c = min(b, e) - max(a, s)
            if c > cover:
                best, cover = name, c
        return best

    waited, late, idle, tallied, stall = [], [], {}, set(), 0.0
    nested = 0
    for (a0, b0, _), (a1, _, _) in zip(engine, engine[1:]):
        nested += a1 < b0       # flat: the next opens after this closed
    for s, e, name in engine:
        if name != BEHIND:
            continue
        i = bisect.bisect_left(starts, e) - 1   # started before the exit
        if i < 0:
            continue
        end = decode[i][1]
        (waited if end > s else late).append((e - end) / 1e6)
        if i + 1 == len(decode) or i in tallied:
            continue        # a turn's second admission: counted already
        tallied.add(i)
        stall += (decode[i + 1][0] - end) / 1e9
        # the device's idle gaps up to the next decode program
        j = bisect.bisect_left(gap_starts, end - 1)
        while j < len(gaps) and gaps[j][1] <= decode[i + 1][0]:
            a, b = gaps[j]
            row = idle.setdefault(named(a, b), {"s": 0.0, "gaps": 0})
            row["s"] += (b - a) / 1e9
            row["gaps"] += 1
            j += 1
    total = sum(r["s"] for r in idle.values())
    return {
        "trace": os.path.basename(path), "decode_programs": len(decode),
        "engine_spans": len(engine), "engine_spans_nested": int(nested),
        "behind_spans": len(waited) + len(late),
        # exit less the program's end, ms: the host's wake-up after a
        # real wait; the host's own lateness where the block was done
        "waited_exit_minus_program_end_ms": _quantiles(waited),
        "found_done_exit_minus_program_end_ms": _quantiles(late),
        "admissions_behind_a_block": len(tallied),
        "device_stall_after_admission_s": stall,
        "idle_after_admission_s": total,
        "idle_after_admission_by_span": dict(sorted(
            idle.items(), key=lambda kv: -kv[1]["s"])),
        "idle_named_share": (1.0 - idle.get("unattributed", {"s": 0.0})["s"]
                             / total) if total else None,
    }


def phase_cost(n: int = 20000) -> dict:
    """Seconds a ``tracing.phase`` of nothing takes, profiler off and
    on (jax imported in both: a TraceAnnotation is made either way)."""
    import tempfile

    import jax

    from ray_tpu.util import metrics, tracing
    h = metrics.Histogram("clock_check_phase_s", "an empty phase",
                          boundaries=(.001, 1))

    def run():
        t0 = time.perf_counter()
        for _ in range(n):
            with tracing.phase("engine.clock_check", h):
                pass
        return (time.perf_counter() - t0) / n
    run()
    off = min(run() for _ in range(3))
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            on = min(run() for _ in range(3))
        finally:
            jax.profiler.stop_trace()
    return {"phases": n, "profiler_off_us": off * 1e6,
            "profiler_on_us": on * 1e6,
            "device": jax.devices()[0].device_kind}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("xplane", nargs="?")
    ap.add_argument("--phase-cost", action="store_true")
    ap.add_argument("--out")
    a = ap.parse_args()
    if not a.phase_cost and not a.xplane:
        ap.error("a trace file, or --phase-cost")
    row = phase_cost() if a.phase_cost else check(a.xplane)
    text = json.dumps(row, indent=1)
    if a.out:
        os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
        with open(a.out, "w") as f:
            f.write(text + "\n")
    print(json.dumps(row))
    return 0


if __name__ == "__main__":
    sys.exit(main())
