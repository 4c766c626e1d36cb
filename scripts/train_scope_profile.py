"""A train cell's step on the chip, outside the harness, by NAMED SCOPE:
milliseconds a step of every ``jax.named_scope`` the models set
(``gdn.solve``, ``gdn.chunk``, ``gdn.scan``, ``gdn.conv``, ``gdn.proj``,
``gdn.gate_norm``, ``gdn.out``, ``attention``, ``attention.gate``,
``moe.route``, ``moe.experts``, ``moe.combine``, ``moe.shared``, ``loss``,
``optimizer``)
and of every Pallas kernel by its name, with the step's own metrics beside
them (``moe_local_share``, ``moe_compact_share``: the expert layers that
worked on their own rows only). The benchmark's own reduction
(``benchmarks/harness/xplane.py``) reads event names only, and a train
cell keeps no profile for its readers: this is what PERF.md's tables by
scope come from. The compiled step's text gives each instruction its
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
SCOPES = ("gdn.solve", "gdn.chunk", "gdn.scan", "gdn.conv", "gdn.proj",
          "gdn.gate_norm", "gdn.out", "attention.gate", "moe.route",
          "moe.experts", "moe.combine", "moe.shared", "mlp", "loss",
          "optimizer", "attention")
_EVENT = re.compile(r"%?([\w.\-]+) = (.*?)\s([a-z][a-z\-]*)\(")
_INSTRUCTION = re.compile(r"\s*(?:ROOT )?%?([\w.\-]+) = ")


def scope_of(op_name: str) -> str:
    parts = op_name.split("/")
    return next((s for s in SCOPES if s in parts), "other")


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
    by_scope, by_op = {}, {}
    for ev in lines["XLA Ops"].events:
        hit = _EVENT.match(ev.name)
        if not hit or hit.group(3) in ("while", "conditional", "call"):
            continue        # a container's time is its body's events'
        name, result, opcode = hit.groups()
        scope = scope_of(op_names.get(name, ""))
        if opcode == "custom-call" and "tpu_custom_call" in ev.name:
            scope = "kernel:" + re.sub(r"[.\d]+$", "", name)
        ms = ev.duration_ns / 1e6 / a.steps
        by_scope[scope] = by_scope.get(scope, 0.0) + ms
        row = by_op.setdefault(name, [scope, name, 0.0, result[:80],
                                      op_names.get(name, "")[-120:]])
        row[2] += ms
    out = {"workload": a.workload, "seed": a.seed, "steps": a.steps,
           "step_ms": [ev.duration_ns / 1e6
                       for ev in lines["XLA Modules"].events],
           "by_scope_ms": dict(sorted(by_scope.items(),
                                      key=lambda kv: -kv[1])),
           "ops": sorted(by_op.values(), key=lambda r: -r[2])[:1500],
           "metrics": {k: float(v) for k, v in met.items()},
           "memory": jax.devices()[0].memory_stats()}
    print(json.dumps({k: out[k] for k in ("step_ms", "by_scope_ms",
                                          "metrics")}, indent=1))
    if a.out:
        os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
        with open(a.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
