"""A train cell's step on the chip, outside the harness, by NAMED SCOPE:
milliseconds a step of every ``jax.named_scope`` the models set
(``gdn.solve``, ``gdn.chunk``, ``gdn.scan``, ``gdn.conv``, ``gdn.proj``,
``gdn.gate_norm``, ``gdn.out``, ``attention``, ``attention.gate``,
``moe.route``, ``moe.experts``, ``moe.combine``, ``moe.shared``, ``loss``,
``optimizer``, ``tp.exchange``)
and of every Pallas kernel by its name, with the step's own metrics beside
them (``moe_local_share``, ``moe_compact_share``: the expert layers that
worked on their own rows only). Where the step has collectives
(``collectives_ms``): by kind and by the scope that waits on them, how
often a step, the milliseconds in flight and the EXPOSED milliseconds,
those with no compute running on the device, as
``collective_exposed_share.4chip`` counts them. The benchmark's own
reduction (``benchmarks/harness/xplane.py``) reads event names only, and
a train cell keeps no profile for its readers: this is what PERF.md's
tables by scope come from. The compiled step's text gives each instruction its
``op_name`` (the scopes it was traced under); the trace's events are
matched to it by instruction name.

    chiprun -- python scripts/train_scope_profile.py \\
        --workload train-qwen3next-ep16 --seed 1 \\
        --out chiprun_out/scopes.json

A one-off for PERF.md, no cell's code; without a TPU it exits 3.
"""

import argparse
import glob
import json
import os
import re
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "benchmarks"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

# innermost first: an instruction goes to the first scope its op_name holds
SCOPES = ("tp.exchange", "gdn.solve", "gdn.chunk", "gdn.scan", "gdn.conv",
          "gdn.proj", "gdn.gate_norm", "gdn.out", "attention.gate",
          "moe.route", "moe.experts", "moe.combine", "moe.shared", "mlp",
          "loss", "optimizer", "attention")
_EVENT = re.compile(r"%?([\w.\-]+) = (.*?)\s([a-z][a-z\-]*)\(")
_INSTRUCTION = re.compile(r"\s*(?:ROOT )?%?([\w.\-]+) = ")


def _parts(op_name: str) -> list:
    """The name stack's entries, a transformation's wrapping taken off
    (``transpose(jvp(tp.exchange))``: a scope set inside a backward)."""
    return [re.sub(r"^(?:\w+\()+|\)+$", "", p) for p in op_name.split("/")]


def scope_of(op_name: str) -> str:
    parts = _parts(op_name)
    return next((s for s in SCOPES if s in parts), "other")


def collective_scope(op_name: str) -> str:
    """The last two of the model's scopes a collective's op_name holds,
    and the pass that made it (``fwd``, the checkpoint's ``remat``
    forward, ``bwd``): ``mlp/tp.exchange bwd``."""
    named = [p for p in _parts(op_name) if p in SCOPES]
    return "/".join(named[-2:] or ["other"]) \
        + (" remat" if "rematted_computation" in op_name
           else " bwd" if "transpose(" in op_name else " fwd")


def reduce_events(ops, flights, op_names: dict, steps: int):
    """(ms a step by scope, rows by instruction, the collectives' table,
    the exposed ms a step) from the events of a device's "XLA Ops" line
    and of its "Async XLA Ops" line. The table: ``"<kind> <scope>
    [<where>]": [calls a step, ms, exposed ms]``, ``where`` being
    ``blocking`` (a synchronous collective), ``wait`` (an asynchronous
    one's ``-done``) or ``flight`` (its start-to-done span, which holds
    its wait); exposed is the part with no compute on the device. The
    last figure repeats the harness's rule (``benchmarks/harness/xplane.py
    reduce_plane``: the union of all of them less the compute) over this
    script's own trace, so it reads a little off the cell's (45.2 ms
    against 43.6, PR 55): ``collective_exposed_share.4chip`` is the
    authority, this table says where its milliseconds lie. One reader
    keyed by scope is a ``benchmark`` issue's (PERF.md section 7)."""
    from harness import kernels, xplane
    by_scope, by_op = {}, {}
    compute, collectives = [], []       # spans; (row's name, span)
    for ev in ops:
        hit = _EVENT.match(ev.name)
        if not hit or hit.group(3) in kernels.CONTAINERS:
            continue        # a container's time is its body's events'
        name, result, opcode = hit.groups()
        scope = scope_of(op_names.get(name, ""))
        span = [ev.start_ns, ev.start_ns + ev.duration_ns]
        if opcode.startswith(kernels.COLLECTIVES):
            if not opcode.endswith("-start"):
                done = opcode.endswith("-done")
                collectives.append((
                    f"{opcode.removesuffix('-done')} "
                    f"{collective_scope(op_names.get(name, ''))} "
                    f"[{'wait' if done else 'blocking'}]", span))
        elif not opcode.endswith(("-start", "-done")):
            compute.append(span)
        if opcode == "custom-call" and "tpu_custom_call" in ev.name:
            scope = "kernel:" + re.sub(r"[.\d]+$", "", name)
        ms = ev.duration_ns / 1e6 / steps
        by_scope[scope] = by_scope.get(scope, 0.0) + ms
        row = by_op.setdefault(name, [scope, name, 0.0, result[:80],
                                      op_names.get(name, "")[-120:]])
        row[2] += ms
    for ev in flights:
        hit = _EVENT.match(ev.name)
        if hit and hit.group(3).startswith(kernels.COLLECTIVES):
            collectives.append((
                f"{hit.group(3).removesuffix('-start')} "
                f"{collective_scope(op_names.get(hit.group(1), ''))} "
                "[flight]", [ev.start_ns, ev.start_ns + ev.duration_ns]))
    compute = xplane._union(compute)
    coll = {}
    for key, span in collectives:
        row = coll.setdefault(key, [0.0, 0.0, 0.0])
        row[0] += 1 / steps
        row[1] += (span[1] - span[0]) / 1e6 / steps
        row[2] += xplane._length(xplane._subtract([span], compute)) \
            / 1e6 / steps
    exposed = xplane._length(xplane._subtract(
        xplane._union([s for _, s in collectives]), compute)) / 1e6 / steps
    return by_scope, by_op, coll, exposed


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--out")
    a = ap.parse_args()
    from harness import model as hmodel, spec, train_cell
    hmodel.compile_cache()
    import jax
    from jax.profiler import ProfileData
    from ray_tpu.parallel import mesh as pmesh
    if jax.default_backend() != "tpu":
        print("train_scope_profile: no TPU", file=sys.stderr)
        return 3
    cell = spec.cell(a.workload)
    m = hmodel.resolved(cell["model"])
    dep = m["deployment"]
    fam = spec.family(cell["family"])
    cfg = fam.config(m, **dep["model_overrides"])
    seq, batch = int(hmodel.traffic(cell)["seq_len"]), int(dep["batch"])
    mesh = pmesh.make_mesh(pmesh.MeshSpec(data=1, context=1, **dep["mesh"]),
                           devices=jax.devices()[:cell["chips"]])
    # the cell's own optimizer (its peak rate, its frozen leaves): the
    # routing regime of the profiled steps is then the cell's
    init_fn, step_fn = pmesh.make_train_step(
        cfg, mesh, model=fam.module(), optimizer=train_cell.optimizer(dep))
    key = jax.random.PRNGKey(a.seed % 2 ** 31)
    with mesh:
        state = init_fn(key)
        toks = jax.random.randint(jax.random.fold_in(key, 1),
                                  (batch, seq + 1), 0, cfg.vocab_size)
        data = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
        op_names = {}
        for ln in step_fn.lower(state, data).compile().as_text().splitlines():
            name, scope = _INSTRUCTION.match(ln), re.search(
                r'op_name="([^"]*)"', ln)
            if name and scope:
                op_names[name.group(1)] = scope.group(1)
        for _ in range(3):
            state, met = step_fn(state, data)
            float(met["loss"])
        tdir = tempfile.mkdtemp(prefix="scope_profile_")
        jax.profiler.start_trace(tdir)
        for _ in range(a.steps):
            state, met = step_fn(state, data)
            float(met["loss"])      # waits for the step
        jax.profiler.stop_trace()
    pd = ProfileData.from_file(glob.glob(os.path.join(
        tdir, "**", "*.xplane.pb"), recursive=True)[0])
    lines = {ln.name: ln for ln in next(
        p for p in pd.planes if p.name.startswith("/device:TPU:")).lines}
    by_scope, by_op, coll, exposed = reduce_events(
        lines["XLA Ops"].events,
        lines["Async XLA Ops"].events if "Async XLA Ops" in lines else (),
        op_names, a.steps)
    out = {"workload": a.workload, "seed": a.seed, "steps": a.steps,
           "step_ms": [ev.duration_ns / 1e6
                       for ev in lines["XLA Modules"].events],
           "by_scope_ms": dict(sorted(by_scope.items(),
                                      key=lambda kv: -kv[1])),
           "collectives_ms": dict(sorted(coll.items(),
                                         key=lambda kv: -kv[1][2])),
           "collective_exposed_ms": exposed,
           "ops": sorted(by_op.values(), key=lambda r: -r[2])[:1500],
           "metrics": {k: float(v) for k, v in met.items()},
           "memory": jax.devices()[0].memory_stats()}
    print(json.dumps({k: out[k] for k in (
        "step_ms", "by_scope_ms", "collectives_ms", "collective_exposed_ms",
        "metrics")}, indent=1))
    if a.out:
        os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
        with open(a.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
