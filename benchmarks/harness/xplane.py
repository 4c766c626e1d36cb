"""From a profiler trace (.xplane.pb) to numbers: device busy and idle
time, time per kernel and per program, collective time and its exposed
part, the largest operations and the longest idle gaps.

Read with ``jax.profiler.ProfileData`` alone. A TPU device plane
("/device:TPU:<n>") carries the lines "XLA Modules" (one event per
executed program), "XLA Ops" (one per HLO instruction; a ``while``
contains its body's events, so busy time is a UNION of intervals) and
"Async XLA Ops" (start-to-done spans of asynchronous copies and
collectives). Event names on the ops lines are the HLO text.
"""

from __future__ import annotations

from harness import kernels


def _union(intervals: list) -> list:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def _length(merged: list) -> float:
    return sum(b - a for a, b in merged)


def _subtract(a: list, b: list) -> list:
    """Parts of merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def _is_collective(opcode: str) -> bool:
    return any(opcode.startswith(c) for c in kernels.COLLECTIVES)


def reduce_plane(plane) -> dict | None:
    """One device plane. Times in seconds."""
    lines = {ln.name: ln for ln in plane.lines}
    if "XLA Ops" not in lines:
        return None
    parsed = {}
    ops = []          # (start, end, parsed op)
    for ev in lines["XLA Ops"].events:
        op = parsed.get(ev.name)
        if op is None:
            op = parsed[ev.name] = kernels.parse_op(ev.name)
            op["kernel"] = kernels.classify(op)
        ops.append((ev.start_ns, ev.start_ns + ev.duration_ns, op))
    if not ops:
        return None
    t_first = min(o[0] for o in ops)
    t_last = max(o[1] for o in ops)
    busy = _union([(a, b) for a, b, _ in ops])

    by_kernel, by_op = {}, {}
    compute, coll = [], []
    for a, b, op in ops:
        code = op["opcode"]
        if code in kernels.CONTAINERS:
            continue
        label = (f"{op['kernel']}:" if op["kernel"] else "") \
            + kernels.short_name(op)
        by_op[label] = by_op.get(label, 0) + (b - a)
        if op["kernel"]:
            k = by_kernel.setdefault(op["kernel"], {
                "s": 0.0, "calls": 0,
                "q_shape": list(op["operands"][2 if op["kernel"]
                                == "paged_decode" else 0][1])})
            k["s"] += (b - a) / 1e9
            k["calls"] += 1
        if _is_collective(code):
            if not code.endswith("-start"):
                coll.append((a, b))     # blocking, or the wait in -done
        elif not (code.endswith("-start") or code.endswith("-done")):
            compute.append((a, b))
    if "Async XLA Ops" in lines:
        for ev in lines["Async XLA Ops"].events:
            if _is_collective(kernels.parse_op(ev.name)["opcode"]):
                coll.append((ev.start_ns, ev.start_ns + ev.duration_ns))
    coll_u, compute_u = _union(coll), _union(compute)

    # programs: an executed module is "decode" if the paged kernel ran
    # inside it, "prefill" if a flash forward did, "train" if a flash
    # backward did
    kernel_spans = sorted((a, op["kernel"]) for a, _, op in ops
                          if op["kernel"])
    programs = {}
    if "XLA Modules" in lines:
        import bisect
        starts = [k[0] for k in kernel_spans]
        for ev in lines["XLA Modules"].events:
            a, b = ev.start_ns, ev.start_ns + ev.duration_ns
            inside = {k for _, k in kernel_spans[
                bisect.bisect_left(starts, a):bisect.bisect_right(starts, b)]}
            kind = ("train" if "flash_bwd_dq" in inside
                    else "decode" if "paged_decode" in inside
                    else "prefill" if "flash_fwd" in inside else "other")
            p = programs.setdefault(kind, {"s": 0.0, "calls": 0})
            p["s"] += (b - a) / 1e9
            p["calls"] += 1

    gaps = sorted(((b2 - b1, b1, b2) for (_, b1), (b2, _)
                   in zip(busy, busy[1:])), reverse=True)[:10]
    return {
        "window_s": (t_last - t_first) / 1e9,
        "busy_s": _length(busy) / 1e9,
        "kernels": by_kernel,
        "programs": programs,
        "collective_s": _length(coll_u) / 1e9,
        "collective_exposed_s": _length(_subtract(coll_u, compute_u)) / 1e9,
        "device_ops": sorted(([k, v / 1e9] for k, v in by_op.items()),
                             key=lambda kv: -kv[1])[:10],
        "gaps": [(g / 1e9, a, b) for g, a, b in gaps],
    }


def _host_spans(pd) -> list:
    """(start_ns, end_ns, name) of the benchmark's and the program's
    TraceAnnotations on the host planes."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("bench.", "engine.", "train.")):
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name))
    return out


def reduce(pd) -> dict | None:
    """All device planes of one trace, averaged over the chips used."""
    per_dev = [r for r in (reduce_plane(p) for p in pd.planes
                           if p.name.startswith("/device:TPU:"))
               if r is not None]
    if not per_dev:
        return None     # no TPU plane: nothing ran on a device
    n = len(per_dev)
    first = per_dev[0]
    spans = _host_spans(pd)

    def what(a, b):
        """The host annotation covering most of the gap, if any."""
        best, cover = "unattributed", 0
        for s, e, name in spans:
            c = min(b, e) - max(a, s)
            if c > cover:
                best, cover = name, c
        return best

    def mean(key):
        return sum(d[key] for d in per_dev) / n

    merged = {"n_devices": n,
              "window_s": mean("window_s"), "busy_s": mean("busy_s"),
              "collective_s": mean("collective_s"),
              "collective_exposed_s": mean("collective_exposed_s"),
              "device_ops": first["device_ops"],
              "idle_gaps": [[what(a, b), g] for g, a, b in first["gaps"]]}
    for group in ("kernels", "programs"):
        names = {k for d in per_dev for k in d[group]}
        merged[group] = {
            k: {"s": sum(d[group].get(k, {}).get("s", 0.0)
                         for d in per_dev) / n,
                "calls": sum(d[group].get(k, {}).get("calls", 0)
                             for d in per_dev) / n,
                **({"q_shape": next(d[group][k]["q_shape"]
                                    for d in per_dev if k in d[group])}
                   if group == "kernels" else {})}
            for k in names}
    merged["idle_share"] = 1.0 - merged["busy_s"] / merged["window_s"] \
        if merged["window_s"] else None
    return merged


def reduce_file(path: str) -> dict | None:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(path))
