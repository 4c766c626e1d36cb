"""Device time of a Pallas kernel told by its OWN NAME in the trace, a
decode step, and the serving grouped matmuls' share of their roofline.
``harness/kernels.py classify`` has one class for every custom call it
does not know (in a serve cell the pool's writer ``kv_write`` and the
grouped matmuls ``moe_gmm_decode`` / ``moe_gmm``), and the reduced trace
keeps classes, so this reader opens the run's profile itself.

Which profile: the traced run writes it under its working directory
(``ray_tpu_bench_*/trace`` in the temporary directory; the runner removes
the directory after the metrics are read, and ``ctx`` does not name it).
Of the profiles there, only one written since THIS run's trace began
(``ctx["trace_edges"]``, the host's monotonic clock) is read: one that a
killed run left behind is older, and is passed over.

dev_ms_per_step: the kernel's time over the steps the same profile holds
(its calls over its calls a step: ``per_step`` says how many a layer, or
a sparse layer by the family's count). gmm_roofline: bandwidth rules at
a decode step's rows (0-6 a held expert): the bytes the family's
``gmm_decode_required_bytes`` counts - the three matrices of every held
expert some row reached plus the routed rows in and out - at the chip's
HBM bandwidth, over the kernel's time. Counters and kernel time are
brought to the same steps: the engine's counters between the trace's
edges give the MEAN a step (``moe_experts_hit``, ``moe_local`` over
``block_steps``), the profile gives the steps its kernel time covers (a
block in flight at an edge is in the profile and not in the counters, or
the other way round). Where the program has no such kernel or counter,
the family no such count, or the run no trace, there is nothing to read."""
import glob
import os
import re
import tempfile
import time

from harness import kernels, peaks, spec


# an instruction's name is the kernel's plus the compiler's numbering
_NUMBERED = re.compile(r"[._]*\d*$")


def _profile(ctx):
    """This run's profile: the newest written since its trace began."""
    edges = ctx.get("trace_edges")
    if not edges:
        return None
    since = time.time() - time.monotonic() + edges[0]
    files = [f for f in glob.glob(os.path.join(
        tempfile.gettempdir(), "ray_tpu_bench_*", "trace", "**",
        "*.xplane.pb"), recursive=True) if os.path.getmtime(f) >= since]
    return max(files, key=os.path.getmtime) if files else None


def kernel_time(path: str, name: str):
    """(seconds, calls) of the custom calls named ``name`` on the first
    TPU plane of the profile, or None."""
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        ops = next((ln for ln in plane.lines if ln.name == "XLA Ops"), None)
        if ops is None:
            continue
        seconds, calls, seen = 0.0, 0, {}
        for ev in ops.events:
            mine = seen.get(ev.name)
            if mine is None:
                op = kernels.parse_op(ev.name)
                mine = seen[ev.name] = bool(
                    op.get("custom_kernel")
                    and _NUMBERED.sub("", op["name"]) == name)
            if mine:
                seconds += ev.duration_ns / 1e9
                calls += 1
        return seconds, calls
    return None


def _calls_a_step(ctx, per_step: dict):
    n = per_step.get("layer", 0) * ctx["model"]["num_hidden_layers"]
    if per_step.get("sparse_layer"):
        layers = getattr(spec.family(ctx["cell"]["family"]),
                         "sparse_layers", None)
        if layers is None:
            return None
        n += per_step["sparse_layer"] * layers(ctx["model"])
    return n


def read(ctx, kernel, what, per_step):
    if not ctx.get("trace"):
        return None
    a_step = _calls_a_step(ctx, per_step)
    path = _profile(ctx)
    if not a_step or path is None:
        return None
    found = kernel_time(path, kernel)
    if not found or not found[1]:
        return None
    seconds, calls = found
    steps = calls / a_step
    if what == "dev_ms_per_step":
        return 1e3 * seconds / steps
    count = getattr(spec.family(ctx["cell"]["family"]),
                    "gmm_decode_required_bytes", None)
    c = ctx["counters"].get("trace") or {}
    if count is None or not c.get("block_steps_sum") \
            or not c.get("moe_experts_hit_sum"):
        return None
    a = steps / c["block_steps_sum"]
    need = count(ctx["model"], a * c["moe_experts_hit_sum"],
                 a * c.get("moe_local_sum", 0.0))
    pk = peaks.peaks(ctx["info"]["device"]["kind"])
    return 100.0 * need / pk["hbm_bytes_per_s"] / seconds
