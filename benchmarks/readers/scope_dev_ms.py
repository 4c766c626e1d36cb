"""Device time of the instructions that carry a ``jax.named_scope`` of the
program (``scope``, a part of the scope's name such as ``mhc.``), in the
programs whose name holds ``program`` ("decode" | "prefill"), over the
decode steps the same profile holds (``per: "step"``) or per thousand
prompt tokens whose first token reached the client between the trace's
edges (``per: "ktok"``).

The reduced trace keeps event names only, and an event's name is the HLO
instruction without its metadata, so this reader opens the run's profile
itself (the one ``readers/named_kernel.py`` finds). The profile DOES carry
the scope: a device plane's event metadata has a ``tf_op`` stat, the
instruction's ``op_name`` ("jit(paged_decode_steps)/.../layer/
attention.latent/mhc.coeff/..."; a fusion carries its root's).
``jax.profiler.ProfileData`` does not hand out the stats of event
METADATA, so the file is parsed once more with the profile's own protobuf
classes, the ``xplane_pb2`` that the installed TensorFlow generates
(loaded by its path: importing the package takes ten seconds), for the
metadata alone; the events' times come from ``ProfileData`` as everywhere
else. A ``while`` or a call contains its body's events and is passed
over. Where the program has no such scope, the run no trace or the
installation no such classes, there is nothing to read."""
import importlib.util
import os
import re
import sys

from harness import kernels, spec
from harness.window import client_counts

_JIT = re.compile(r"jit\(([^)]*)\)")
_PB2 = "tsl/profiler/protobuf/xplane_pb2.py"


def _xplane_pb2():
    """TensorFlow's generated classes of the profile's format, without
    TensorFlow: the one file, which needs ``google.protobuf`` alone."""
    name = "bench_xplane_pb2"
    if name not in sys.modules:
        tf = importlib.util.find_spec("tensorflow")
        path = tf and os.path.join(os.path.dirname(tf.origin), _PB2)
        if not path or not os.path.isfile(path):
            print(f"scope_dev_ms: no {_PB2} in this installation",
                  file=sys.stderr)
            return None
        found = importlib.util.spec_from_file_location(name, path)
        sys.modules[name] = importlib.util.module_from_spec(found)
        found.loader.exec_module(sys.modules[name])
    return sys.modules[name]


def op_names(path: str) -> dict:
    """{an "XLA Ops" event's name: its ``tf_op``} for every event metadata
    of the profile's first TPU plane that has one (the stat holds the
    string, or refers to a stat metadata whose name is the string)."""
    pb2 = _xplane_pb2()
    if pb2 is None:
        return {}
    space = pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    for plane in space.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        stats = {key: meta.name for key, meta in plane.stat_metadata.items()}
        out = {}
        for meta in plane.event_metadata.values():
            op = next((st.str_value or stats.get(st.ref_value, "")
                       for st in meta.stats
                       if stats.get(st.metadata_id) == "tf_op"), "")
            if meta.name and op:
                out[meta.name] = op
        return out
    return {}


def scope_time(path: str, scope: str, program: str):
    """(seconds, events) of the scoped instructions of such programs on
    the first TPU plane of the profile."""
    from jax.profiler import ProfileData
    ops = op_names(path)
    mine = set()
    for name, op in ops.items():
        jit = _JIT.search(op)
        if scope in op and jit and program in jit.group(1) \
                and kernels.parse_op(name)["opcode"] \
                not in kernels.CONTAINERS:
            mine.add(name)
    if not mine:
        return None
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        line = next((ln for ln in plane.lines if ln.name == "XLA Ops"), None)
        if line is None:
            continue
        seconds, events = 0.0, 0
        for ev in line.events:
            if ev.name in mine:
                seconds += ev.duration_ns / 1e9
                events += 1
        return seconds, events
    return None


def read(ctx, scope, program, per):
    tr, edges = ctx.get("trace"), ctx.get("trace_edges")
    if not tr or not edges:
        return None
    path = spec._module("readers", "named_kernel")._profile(ctx)
    found = path and scope_time(path, scope, program)
    if not found or not found[1]:
        return None
    if per == "step":
        # as readers/decode_dev_ms_per_step.py: the walk runs once a layer
        k = tr["kernels"].get("paged_decode")
        if not k or not k["calls"]:
            return None
        return 1e3 * found[0] * ctx["model"]["num_hidden_layers"] \
            / k["calls"]
    tokens = client_counts(ctx["requests"], edges)["prefill_tokens"]
    return 1e6 * found[0] / tokens if tokens else None
